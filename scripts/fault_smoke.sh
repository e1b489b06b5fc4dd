#!/usr/bin/env sh
# Chaos smoke lane: run ONLY the fault-injection tests (marker
# `faults` — training resilience in tests/test_resilience.py, the
# serving chaos harness in tests/test_serve_server.py, the
# multi-replica router fleet in tests/test_router.py, and the
# parameter-server fault suite in tests/test_pserver.py), so
# degradation coverage is cheap to invoke standalone:
#
#     scripts/fault_smoke.sh            # the whole faults lane
#     scripts/fault_smoke.sh pserver    # just the pserver lane
#                                       #   (leases/replication/failover)
#     scripts/fault_smoke.sh router     # just the serving-fleet lane
#                                       #   (affinity/failover/redistribute)
#     scripts/fault_smoke.sh disagg     # just the migration chaos lane
#                                       #   (dst killed mid-transfer,
#                                       #   source death while parked)
#     scripts/fault_smoke.sh fleet      # just the cross-process fleet
#                                       #   lane (socket replicas, real
#                                       #   SIGKILL, orphan watchdog)
#     scripts/fault_smoke.sh cluster    # just the multi-host control-
#                                       #   plane lane (lease/epoch
#                                       #   fencing, agents, standby
#                                       #   failover, the agent-SIGKILL
#                                       #   reform chaos case)
#     scripts/fault_smoke.sh elastic    # just the elastic gang-training
#                                       #   lane (ZeRO parity, reshard
#                                       #   restore, gang SIGKILL/wedge
#                                       #   chaos incl. the slow cases)
#     scripts/fault_smoke.sh edge       # just the HTTP front-door lane
#                                       #   (disconnect cancellation,
#                                       #   overload 429, slow-loris,
#                                       #   drain, the SIGKILL-under-
#                                       #   live-HTTP-load chaos case)
#     scripts/fault_smoke.sh data       # just the zero-copy data-
#                                       #   plane lane (shm arena
#                                       #   SIGKILL source/dst chaos,
#                                       #   orphan reclaim after
#                                       #   supervisor death, fallback
#                                       #   parity)
#     scripts/fault_smoke.sh ctr        # just the embedding-cache
#                                       #   chaos lane (shard failover
#                                       #   mid-traffic with the
#                                       #   staleness bound held,
#                                       #   reform-mid-stream exactly-
#                                       #   once)
#     scripts/fault_smoke.sh -k serve   # just the serving chaos suite
#
# CPU-only and deterministic (testing.faults FaultPlan + ManualClock;
# pserver faults via the shard fault_hook seam; replica kills via the
# replica-engine proxy); extra args pass through to pytest.
set -e
cd "$(dirname "$0")/.."
marker=faults
case "$1" in
    pserver|router|elastic)
        # elastic: INCLUDING the slow wedge-fencing case tier-1 excludes
        marker=$1
        shift ;;
    disagg|fleet|cluster|edge|ctr|data)
        # each lane whole, INCLUDING the heavyweight real-process chaos
        # cases tier-1 excludes
        marker="$1 and faults"
        shift ;;
esac
exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "$marker" \
    -p no:cacheprovider "$@"
