"""Fault-tolerant training runtime: the loop that survives.

Closes the gap between the durability primitives that already exist
(orbax CheckpointManager, HAMaster snapshots, lease-epoch task queue)
and the training loop itself, which previously died on the first
preemption, NaN, or wedged collective. The reference's Go runtime put
this logic around the pserver/master (reference: go/master/service.go
task leases + retry/timeout, go/pserver/service.go gob checkpoints,
etcd recover); at TPU-pod scale the same failure classes land on the
trainer process instead, so the recovery loop lives here:

- **Preemption-safe resume**: `ResilientTrainer.run()` auto-restores
  the newest restorable checkpoint at startup (falling back past
  corrupt/half-written steps), installs SIGTERM/SIGINT handlers that
  drain ONE final synchronous save at the next step boundary, and
  raises `Preempted` so the scheduler's restart lands exactly where
  the save left off. Per-step rng is derived by `fold_in(base, step)`
  — not a sequential split chain — so a resumed run consumes identical
  randomness and reproduces the uninterrupted run's params exactly.
- **Divergence guard**: every step's loss is checked on the host
  (non-finite, or a bounded spike over a running EMA). A bad step is
  answered by a bounded skip-or-rollback policy — the TPU-native
  analog of the reference pserver's error-rate parameter rollback
  (reference: trainer error_clipping / shrink on divergence) — with
  optional LR backoff, hard-failing with `DivergenceError` once the
  retry budget is spent.
- **Watchdog**: a cross-host progress deadline. Every completed step
  pets it; if a collective wedges (one host down, the rest blocked in
  an all-reduce that can never complete) no host progresses, every
  host's watchdog fires, and the default action force-exits the
  process so the gang scheduler restarts the job into the resume path
  above — turning an unbounded hang into bounded downtime.

Fault injection for all of these lives in `paddle_tpu.testing.faults`;
`tests/test_resilience.py` proves each path end-to-end. Semantics and
the fault model are documented in docs/RELIABILITY.md.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import signal
import threading
import time
from typing import Any, Callable, Iterable, List, Optional

import jax
import numpy as np

from paddle_tpu.train import events as E
from paddle_tpu.train.checkpoint import (CheckpointManager,
                                         ManifestMismatchError)
from paddle_tpu.train.state import TrainState
from paddle_tpu.train.trainer import Trainer, make_train_step

log = logging.getLogger(__name__)


class Preempted(RuntimeError):
    """Raised after the final drain save when a preemption signal
    arrived. `.step` is the checkpointed step; a process restarted with
    the same checkpoint_dir resumes from it."""

    def __init__(self, step: int, signum: Optional[int] = None):
        super().__init__(
            f"preempted at step {step} (signal {signum}); state saved — "
            f"restart resumes here")
        self.step = step
        self.signum = signum


class DivergenceError(RuntimeError):
    """The bad-step budget is spent: training is diverging faster than
    the recovery policy can absorb (the hard-fail arm of the reference
    pserver's rollback policy)."""

    def __init__(self, bad_steps: List["BadStep"]):
        last = bad_steps[-1] if bad_steps else None
        super().__init__(
            f"{len(bad_steps)} bad steps exhausted the recovery budget"
            + (f"; last: {last}" if last else ""))
        self.bad_steps = bad_steps


@dataclasses.dataclass
class BadStep:
    """One detected-and-handled divergent step (audit trail)."""

    step: int
    pass_id: int
    batch_id: int
    reason: str       # "non-finite loss" | "loss spike" | ...
    action: str       # "skip" | "rollback" | "fail"
    loss: float


class _Rollback(Exception):
    """Internal: unwind the drive loop back to a restored state."""

    def __init__(self, state: TrainState):
        self.state = state


class Watchdog:
    """Progress deadline for the train loop (and anything else that can
    wedge). `pet()` after every unit of progress; if `timeout_s` passes
    without one, `on_timeout(elapsed)` runs on the watchdog thread.

    The default action force-exits the process (`os._exit`): a wedged
    collective blocks the main thread inside an uninterruptible device
    wait, so raising or signalling cannot unstick it — only death can,
    and with every host running the same watchdog the whole gang dies
    within one deadline and the scheduler restarts it into
    `ResilientTrainer`'s resume path: a hang is bounded at
    `timeout_s`.
    """

    #: exit code for "aborted by watchdog" — distinct from clean exits
    #: and from SIGTERM's 143 so the scheduler/operator can tell a
    #: wedge-abort from a preemption.
    EXIT_CODE = 75

    def __init__(self, timeout_s: float,
                 on_timeout: Optional[Callable[[float], None]] = None,
                 *, poll_s: Optional[float] = None,
                 name: str = "paddle-tpu-watchdog",
                 clock: Callable[[], float] = time.monotonic):
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self.timeout_s = timeout_s
        self.on_timeout = on_timeout or self._default_abort
        self._poll_s = poll_s if poll_s is not None else min(
            timeout_s / 4.0, 1.0)
        self._name = name
        # injectable like every other timeout surface in the repo
        # (faults.ManualClock drives deterministic deadline tests);
        # the poll cadence itself still rides the real
        # threading.Event.wait
        self.clock = clock
        self._last = clock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.fired = False

    def _default_abort(self, elapsed: float) -> None:
        from paddle_tpu.parallel import distributed

        distributed.abort(
            f"watchdog: no training progress for {elapsed:.1f}s "
            f"(deadline {self.timeout_s}s) — assuming a wedged "
            f"collective; exiting for the scheduler to restart",
            exit_code=self.EXIT_CODE)

    def start(self) -> "Watchdog":
        self._last = self.clock()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name=self._name, daemon=True)
        self._thread.start()
        return self

    def pet(self) -> None:
        self._last = self.clock()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self._poll_s):
            elapsed = self.clock() - self._last
            if elapsed >= self.timeout_s:
                self.fired = True
                try:
                    self.on_timeout(elapsed)
                finally:
                    # one shot: a custom on_timeout that chooses not to
                    # kill the process should not be re-fired every poll
                    self._stop.set()
                return

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def restore_with_fallback(manager: CheckpointManager,
                          template: TrainState, *,
                          bad_steps: Optional[List[int]] = None):
    """Restore the NEWEST restorable step, walking backwards past
    corrupt ones (a half-written orbax step, a munged array file). The
    reference's Go pserver did the md5-over-gob equivalent (reference:
    go/pserver/service.go loadCheckpoint checksum); orbax's commit
    marker covers the common torn-write case and this covers the rest.

    Returns (state, step); (template, None) when the directory holds
    no checkpoints at all. Raises RuntimeError when checkpoints EXIST
    but none restores — that shape is a template/directory mismatch,
    and silently starting over would let retention garbage-collect the
    real run.

    `bad_steps`, when given, collects the step numbers that FAILED to
    restore — the caller's save path must treat those as NOT durable
    (a replay that reaches a known-corrupt newest step must overwrite
    it, not dedupe against its step number)."""
    try:
        steps = sorted(manager.all_steps(), reverse=True)
    except FileNotFoundError:
        # absent directory really is a fresh start; any OTHER listing
        # error (transient NFS outage, permissions) must NOT be — a
        # silent from-scratch restart would later garbage-collect the
        # real run's checkpoints under max_to_keep
        return template, None
    errors = []
    for step in steps:
        try:
            return manager.restore(template, step=step), step
        except ManifestMismatchError:
            # NOT corruption: the template describes a different model
            # (or optimizer layout) than the whole run — every older
            # step mismatches identically, so walking back would only
            # end in the noisier RuntimeError below. Re-raise the named
            # error; a silent misreshard must be impossible.
            raise
        except Exception as e:
            errors.append((step, e))
            if bad_steps is not None:
                bad_steps.append(step)
            log.warning("checkpoint step %d unrestorable (%s); falling "
                        "back to the previous step", step, e)
    if steps:
        # checkpoints EXIST but none restores: far more likely a
        # template mismatch (changed architecture, wrong directory)
        # than N independent corruptions. Starting from scratch here
        # would silently discard the training run — and retention
        # (max_to_keep) would then garbage-collect the intact old
        # steps. Fail loudly instead.
        raise RuntimeError(
            f"{len(steps)} checkpoint step(s) exist under "
            f"{getattr(manager, 'directory', '?')} but none is "
            f"restorable with this state template — architecture/"
            f"directory mismatch? last error: step {errors[-1][0]}: "
            f"{errors[-1][1]}")
    return template, None


def _scale_grads(optimizer, scale: float):
    """Optimizer wrapper applying `scale` to the gradients — the LR
    backoff lever that needs no optimizer-internal access (exact LR
    scaling for SGD-family; a best-effort damper for normalized
    optimizers like Adam). opt_state layout is unchanged, so restored
    checkpoints keep working across backoffs."""
    from paddle_tpu.optim.optimizers import Optimizer

    def update(grads, opt_state, params, step):
        grads = jax.tree.map(lambda g: g * scale, grads)
        return optimizer.update(grads, opt_state, params, step)

    return Optimizer(optimizer.init, update)


class ResilientTrainer:
    """Preemption-safe, divergence-guarded driver around a `Trainer`.

    Wraps the trainer's model/loss/optimizer in a NON-donating train
    step (one extra params+opt buffer of HBM — the price of being able
    to discard a bad update without a device round-trip) and drives the
    batch loop itself so every step boundary is a recovery point.

    Guarantees (tested in tests/test_resilience.py):
    - `run()` restores the newest restorable checkpoint first; with a
      deterministic `batch_iter_factory` a preempted-and-restarted run
      reaches params IDENTICAL to an uninterrupted one.
    - a non-finite (or spiking, see `loss_spike_factor`) loss triggers
      `bad_step_policy`: "skip" discards the update but still advances
      the step counter (step stays == batches-consumed, so resume
      cursors never desync), "rollback" re-restores the last
      checkpoint (optionally backing the LR off by `lr_backoff`) and
      replays; either way at most `max_bad_steps` times, then
      `DivergenceError`. The budget is for clustered failures, not a
      lifetime cap: `bad_step_reset_after` (default 100) NEW-progress
      healthy steps since the last bad one clear it, so a week-long
      run survives scattered transient flakes while a deterministic
      bad batch — whose rollback replays earn no new progress — still
      exhausts it.
    - SIGTERM/SIGINT => one synchronous save, then `Preempted`.
    - `watchdog_timeout_s` bounds any hang (wedged collective, dead
      master, stuck host) at that many seconds. Size it ABOVE the
      worst-case single step including the first step's XLA compile —
      the deadline cannot distinguish a long compile from a wedge, and
      firing during one would restart into the identical compile.
      Checkpoint saves and rollback restores pet it on both sides, so
      each gets its own full deadline rather than a step's leftovers;
      a SINGLE save/restore slower than the deadline still trips it.

    Checkpoint saves other than the preemption drain tolerate OSError
    (logged, training continues — the durability gap is visible in
    `.save_errors`); the drain save retries and then re-raises, because
    exiting without it loses work.
    """

    def __init__(self, trainer: Trainer, checkpoint_dir: str, *,
                 max_to_keep: int = 3,
                 checkpoint_every_n_batches: Optional[int] = None,
                 bad_step_policy: str = "rollback",
                 max_bad_steps: int = 3,
                 bad_step_reset_after: Optional[int] = 100,
                 loss_spike_factor: Optional[float] = None,
                 lr_backoff: Optional[float] = None,
                 watchdog_timeout_s: Optional[float] = None,
                 watchdog_on_timeout: Optional[Callable] = None,
                 install_signal_handlers: bool = True,
                 checkpoint_manager: Optional[Any] = None,
                 tracer: Optional[Any] = None,
                 flight: Optional[Any] = None,
                 flight_dir: Optional[str] = None,
                 pserver_client: Optional[Any] = None,
                 step_builder: Optional[Callable] = None,
                 gang_epoch: int = 0):
        if bad_step_policy not in ("skip", "rollback"):
            raise ValueError(
                f"bad_step_policy must be skip|rollback, got "
                f"{bad_step_policy!r}")
        if lr_backoff is not None and not (0.0 < lr_backoff < 1.0):
            raise ValueError(f"lr_backoff must be in (0, 1), got "
                             f"{lr_backoff}")
        self.trainer = trainer
        self.manager = checkpoint_manager or CheckpointManager(
            checkpoint_dir, max_to_keep=max_to_keep)
        self.checkpoint_every_n_batches = checkpoint_every_n_batches
        self.bad_step_policy = bad_step_policy
        self.max_bad_steps = max_bad_steps
        self.bad_step_reset_after = bad_step_reset_after
        self.loss_spike_factor = loss_spike_factor
        self.lr_backoff = lr_backoff
        self.watchdog_timeout_s = watchdog_timeout_s
        self.watchdog_on_timeout = watchdog_on_timeout
        self.install_signal_handlers = install_signal_handlers
        self.bad_steps: List[BadStep] = []
        self.save_errors: List[str] = []
        self.restored_step: Optional[int] = None
        self._lr_scale = 1.0
        self._preempt_signum: Optional[int] = None
        # budget accounting: bad_steps is the full audit trail; the
        # FAIL decision uses _bad_used, which bad_step_reset_after
        # NEW-progress steps (not rollback replays) clear — so a long
        # run survives scattered transient faults, while a
        # deterministically bad batch (replayed without new progress)
        # still exhausts the budget and hard-fails
        self._bad_used = 0
        self._progress_since_bad = 0
        self._max_step_reached = 0
        # steps whose checkpoints exist but FAILED to restore: the
        # latest-step save dedupe must not treat them as durable
        self._corrupt_steps: set = set()
        self._watchdog: Optional[Watchdog] = None
        # observability (paddle_tpu.obs) — host-side only, both
        # default OFF. One span per EXECUTED step (a rollback replay
        # is a fresh attempt span under the same step id); the flight
        # ring dumps next to the checkpoints on divergence rollback,
        # DivergenceError, and the preemption drain.
        self.tracer = tracer
        self.flight = flight
        self.flight_dir = flight_dir or checkpoint_dir
        # pserver push/pull events ride the live step span (the client's
        # obs_hook seam) so the trainer step -> pserver trail is one trace
        self.pserver_client = pserver_client
        # elastic gang seams: step_builder(optimizer) -> jitted step lets
        # a ZeRO/sharded step replace the plain one while keeping the
        # LR-backoff rebuild path (the builder receives the possibly
        # grad-scaled optimizer); gang_epoch tags every step span and
        # counters() so a reformed gang's spans are distinguishable from
        # the gang that died
        self.step_builder = step_builder
        self.gang_epoch = int(gang_epoch)
        self._build_step()

    def counters(self) -> dict:
        """Outcome counts, registry-source shaped (the
        `obs.MetricsRegistry.register_source` contract: numeric
        values only) — the SAME state the recovery policy decides on,
        so exported metrics cannot drift from behavior."""
        return {
            "bad_steps": len(self.bad_steps),
            "bad_used": self._bad_used,
            "progress_since_bad": self._progress_since_bad,
            "max_step_reached": self._max_step_reached,
            "save_errors": len(self.save_errors),
            "corrupt_steps": len(self._corrupt_steps),
            "restored_step": (-1 if self.restored_step is None
                              else self.restored_step),
            "lr_scale": self._lr_scale,
            "watchdog_fired": (self._watchdog is not None
                               and self._watchdog.fired),
            "gang_epoch": self.gang_epoch,
            # cross-topology restores the checkpoint manager performed
            # (0 for a plain CheckpointManager — the attribute only
            # exists on ElasticCheckpointManager)
            "reshard_restores": int(getattr(self.manager,
                                            "reshard_restores", 0)),
        }

    def bind_metrics(self, registry, *, prefix: str = "train",
                     labels: Optional[dict] = None) -> None:
        """Attach the trainer's outcome ledger (and tracer/flight
        self-accounting) to an `obs.MetricsRegistry`."""
        registry.register_source(prefix, self.counters, labels=labels)
        if self.tracer is not None:
            registry.register_source(f"{prefix}_trace",
                                     self.tracer.counters,
                                     labels=labels)
        if self.flight is not None:
            registry.register_source(f"{prefix}_flight",
                                     self.flight.counters,
                                     labels=labels)
        if self.pserver_client is not None:
            self.pserver_client.bind_metrics(
                registry, prefix=f"{prefix}_pserver", labels=labels)

    def _flight_dump(self, reason: str, /, **extra) -> None:
        # positional-only: the fault paths also carry a `reason=` tag
        # inside `extra` (the classifier's verdict), distinct from the
        # dump trigger
        if self.flight is None or not self.flight_dir:
            return
        self.flight.dump(self.flight_dir, reason,
                         extra={**extra, "counters": self.counters()})

    def _build_step(self) -> None:
        tr = self.trainer
        opt = tr.optimizer
        if self._lr_scale != 1.0:
            opt = _scale_grads(opt, self._lr_scale)
        if self.step_builder is not None:
            self._step = self.step_builder(opt)
            return
        # donate=False: the previous state must survive the step so a
        # bad update can be discarded without touching the checkpoint
        self._step = make_train_step(
            tr.model, tr.loss_fn, opt, metrics_fn=tr.metrics_fn,
            donate=False, remat=tr.remat,
            aux_loss_weight=tr.aux_loss_weight)

    # -- signals ----------------------------------------------------------

    def _install_signals(self):
        """SIGTERM/SIGINT set a flag; the loop drains at the next step
        boundary (saving mid-step would checkpoint a half-applied
        update). Returns the previous handlers for restoration, or None
        when not in the main thread (signal API restriction)."""
        self._preempt_signum = None

        # flag only (locklint LK005): the handler interrupts the
        # train loop between bytecodes — logging here re-enters the
        # logging module's non-reentrant handler locks; the banner
        # moves to _maybe_drain, the step-boundary consumer
        def handler(signum, frame):
            self._preempt_signum = signum

        try:
            prev = {s: signal.signal(s, handler)
                    for s in (signal.SIGTERM, signal.SIGINT)}
        except ValueError:      # not the main thread
            return None
        return prev

    @staticmethod
    def _restore_signals(prev) -> None:
        if prev:
            for s, h in prev.items():
                signal.signal(s, h)

    # -- checkpointing ----------------------------------------------------

    def _pet(self) -> None:
        if self._watchdog is not None:
            self._watchdog.pet()

    def _save(self, state: TrainState, *, drain: bool = False) -> None:
        """Cadence saves absorb OSError (visible in .save_errors); the
        preemption drain retries then propagates — losing the final
        save means losing every step since the last one. Petting the
        watchdog on both sides gives the save its own full deadline
        instead of whatever the last step left over."""
        self._pet()
        step = int(state.step)
        if (self.manager.latest_step() == step
                and step not in self._corrupt_steps):
            return      # this step is already durable
        attempts = 3 if drain else 1
        for i in range(attempts):
            try:
                # save() replaces an existing step directory, so a
                # known-corrupt one is overwritten here, not kept
                self.manager.save(state)
                self._corrupt_steps.discard(step)
                self._pet()
                return
            except OSError as e:
                self.save_errors.append(f"step {int(state.step)}: {e}")
                log.warning("checkpoint save at step %d failed: %s",
                            int(state.step), e)
                if drain and i + 1 < attempts:
                    time.sleep(0.1 * (2 ** i))
        if drain:
            raise OSError(
                f"drain save at step {int(state.step)} failed "
                f"{attempts} times: {self.save_errors[-1]}")

    def _maybe_drain(self, state: TrainState) -> None:
        if self._preempt_signum is None:
            return
        log.warning("preemption signal %d received; draining one "
                    "final checkpoint at step boundary %d",
                    self._preempt_signum, int(state.step))
        if self.flight is not None:
            self.flight.record("signal", "preemption-drain",
                               signum=self._preempt_signum,
                               step=int(state.step))
        self._save(state, drain=True)
        self._flight_dump(f"sigterm-{self._preempt_signum}",
                          step=int(state.step))
        raise Preempted(int(state.step), self._preempt_signum)

    # -- divergence guard -------------------------------------------------

    def _classify(self, loss: float, ema: Optional[float]) -> Optional[str]:
        if not np.isfinite(loss):
            return "non-finite loss"
        if (self.loss_spike_factor is not None and ema is not None
                and abs(loss) > self.loss_spike_factor * max(abs(ema),
                                                             1e-8)):
            return (f"loss spike: |{loss:.4g}| > "
                    f"{self.loss_spike_factor:g} * |{ema:.4g}|")
        return None

    def _handle_bad_step(self, state: TrainState, prev_state: TrainState,
                         pass_id: int, batch_id: int, loss: float,
                         reason: str) -> TrainState:
        """Returns the state to continue from (skip policy) or raises
        _Rollback/DivergenceError."""
        action = self.bad_step_policy
        self.bad_steps.append(BadStep(
            step=int(prev_state.step), pass_id=pass_id,
            batch_id=batch_id, reason=reason, action=action, loss=loss))
        self._bad_used += 1
        self._progress_since_bad = 0
        if self.flight is not None:
            self.flight.record("fault", "bad-step",
                               step=int(prev_state.step),
                               pass_id=pass_id, batch_id=batch_id,
                               reason=reason, action=action,
                               loss=loss, bad_used=self._bad_used)
        if self._bad_used > self.max_bad_steps:
            self.bad_steps[-1].action = "fail"
            self._flight_dump("divergence-budget-exhausted",
                              reason=reason)
            raise DivergenceError(self.bad_steps)
        log.warning("bad step %d (pass %d batch %d): %s -> %s "
                    "(%d/%d recoveries used)", int(prev_state.step),
                    pass_id, batch_id, reason, action,
                    self._bad_used, self.max_bad_steps)
        if action == "skip":
            # discard the poisoned update but still ADVANCE the step
            # counter: step must stay == batches-consumed, or every
            # later resume/rollback cursor (resume_from = state.step)
            # would re-apply an already-checkpointed batch. A skipped
            # step is "a step that updated nothing", costing one tick
            # of any step-indexed LR schedule — cheap next to a
            # desynced resume.
            return prev_state._replace(step=prev_state.step + 1)
        # rollback: re-restore the last durable state and replay from
        # there, optionally with the LR backed off (the pserver's
        # shrink-on-divergence analog)
        if self.lr_backoff is not None:
            self._lr_scale *= self.lr_backoff
            log.warning("LR backoff: grad scale now %.4g", self._lr_scale)
            self._build_step()
        self._pet()     # restore + possible re-jit get a fresh deadline
        bad: List[int] = []
        restored, step = restore_with_fallback(self.manager, prev_state,
                                               bad_steps=bad)
        self._corrupt_steps.update(bad)
        if step is None:
            self._flight_dump("divergence-no-restore-target",
                              reason=reason)
            raise DivergenceError(self.bad_steps)
        self._pet()
        self._flight_dump("divergence-rollback", reason=reason,
                          restored_step=step)
        raise _Rollback(restored)

    # -- the drive loop ---------------------------------------------------

    def run(self, state: TrainState,
            batch_iter_factory: Callable[[], Iterable], *,
            num_passes: int = 1,
            event_handler: Optional[Callable] = None) -> TrainState:
        """Run `num_passes` over `batch_iter_factory` with the full
        recovery loop. `state` is the FRESH-INIT state (the template);
        if checkpoints exist, the newest restorable one wins.

        Resume contract: `batch_iter_factory` must be deterministic
        (same batches, same order, every call) — resume skips the
        first `restored_step` batches and replays the rest. Per-step
        rng is `fold_in(trainer rng, global_batch_index)`, so replayed
        steps draw identical randomness and a resumed run's params are
        bit-identical to an uninterrupted one's.
        """
        bad_restore_steps: List[int] = []
        restored, step = restore_with_fallback(
            self.manager, state, bad_steps=bad_restore_steps)
        self._corrupt_steps.update(bad_restore_steps)
        if step is not None:
            log.info("resuming from checkpoint step %d under %s", step,
                     getattr(self.manager, "directory", "?"))
            self.restored_step = step
            state = restored
        else:
            # a durable step-0 anchor: the rollback policy always has
            # a target, and a preemption before the first cadence save
            # still resumes instead of restarting
            self._save(state)
        # one rng base per run() — derived per-step by fold_in, never
        # advanced sequentially, so skip-ahead costs nothing and replay
        # is exact
        base_rng = self.trainer._rng
        prev_handlers = (self._install_signals()
                         if self.install_signal_handlers else None)
        watchdog = None
        if self.watchdog_timeout_s is not None:
            watchdog = Watchdog(self.watchdog_timeout_s,
                                self.watchdog_on_timeout).start()
        self._watchdog = watchdog
        try:
            while True:
                try:
                    return self._drive(state, batch_iter_factory,
                                       base_rng, num_passes,
                                       event_handler)
                except _Rollback as rb:
                    state = rb.state
        finally:
            self._watchdog = None
            if watchdog is not None:
                watchdog.stop()
            self._restore_signals(prev_handlers)

    def _drive(self, state, batch_iter_factory, base_rng, num_passes,
               event_handler) -> TrainState:
        handler = event_handler or (lambda ev: None)
        resume_from = int(state.step)
        gidx = 0            # global batch cursor across passes
        ema: Optional[float] = None
        cadence = self.checkpoint_every_n_batches
        for pass_id in range(num_passes):
            # event parity with Trainer.train: BeginPass fires before
            # the pass's first EXECUTED batch — lazily when a resume
            # lands mid-pass, up-front otherwise
            began = gidx >= resume_from
            if began:
                handler(E.BeginPass(pass_id))
            for batch_id, batch in enumerate(batch_iter_factory()):
                if gidx < resume_from:
                    gidx += 1
                    # skip-ahead over millions of consumed batches is
                    # progress too — starving the watchdog here would
                    # turn a long resume into a crash loop
                    self._pet()
                    continue
                if not began:
                    handler(E.BeginPass(pass_id))
                    began = True
                self._maybe_drain(state)
                handler(E.BeginIteration(pass_id, batch_id))
                span = None
                if self.tracer is not None:
                    # one span per EXECUTED attempt: a rollback replay
                    # of the same gidx opens a fresh span under the
                    # same id, so the audit trail shows every attempt
                    span = self.tracer.start(
                        f"step{gidx}", "train.step",
                        pass_id=pass_id, batch_id=batch_id,
                        gang_epoch=self.gang_epoch)
                    if self.pserver_client is not None:
                        # point the client's obs seam at THIS attempt's
                        # span; Span.event on a closed span is a no-op,
                        # so a stale hook between steps is harmless
                        self.pserver_client.obs_hook = (
                            lambda event, ctx, _s=span:
                            _s.event(event, **ctx))
                prev_state = state
                with self.trainer.timeline.span("trainer.dispatch",
                                                batch_id):
                    inputs, labels = self.trainer._split_batch(batch)
                    # device_put the fold data EXPLICITLY: a bare python
                    # int here is an implicit h2d transfer every step
                    # (jax.transfer_guard flags it; analysis.guards)
                    step_rng = jax.random.fold_in(
                        base_rng, jax.device_put(np.uint32(gidx)))
                    state, loss, metrics = self._step(
                        state, step_rng, inputs, labels)
                # the guard IS a host sync per step — the price of
                # detecting divergence before it becomes the checkpoint
                lossf = float(loss)
                reason = self._classify(lossf, ema)
                if reason is not None:
                    # event parity: every BeginIteration gets a closing
                    # EndIteration even on the fault paths — carrying
                    # the disposition ("skip"/"rollback"/"fail") so
                    # stream consumers never see an unclosed iteration
                    try:
                        state = self._handle_bad_step(
                            state, prev_state, pass_id, batch_id, lossf,
                            reason)
                    except (_Rollback, DivergenceError):
                        if span is not None:
                            self.tracer.end(
                                span, self.bad_steps[-1].action,
                                reason=reason, loss=lossf)
                        handler(E.EndIteration(
                            pass_id, batch_id, cost=loss,
                            outcome=self.bad_steps[-1].action))
                        raise
                    if span is not None:
                        self.tracer.end(span, "skip", reason=reason,
                                        loss=lossf)
                    handler(E.EndIteration(pass_id, batch_id, cost=loss,
                                           outcome="skip"))
                    gidx += 1
                    self._pet()
                    continue
                ema = (lossf if ema is None
                       else 0.9 * ema + 0.1 * lossf)
                # budget hygiene: only NEW progress (beyond any step
                # ever reached, so rollback replays don't count) ticks
                # the healthy-step window that clears the budget
                if gidx + 1 > self._max_step_reached:
                    self._max_step_reached = gidx + 1
                    self._progress_since_bad += 1
                    if (self.bad_step_reset_after and self._bad_used
                            and self._progress_since_bad
                            >= self.bad_step_reset_after):
                        log.info(
                            "%d healthy new steps since the last bad "
                            "one — recovery budget reset",
                            self._progress_since_bad)
                        self._bad_used = 0
                if span is not None:
                    self.tracer.end(span, "ok", loss=lossf)
                handler(E.EndIteration(pass_id, batch_id, cost=loss,
                                       metrics=metrics))
                gidx += 1
                if cadence and (batch_id + 1) % cadence == 0:
                    self._save(state)
                self._pet()
                self._maybe_drain(state)
            if began:
                self._save(state)
                handler(E.EndPass(pass_id))
        return state


def run_resilient(model, loss_fn, optimizer, batch_iter_factory, *,
                  input_spec, checkpoint_dir: str, num_passes: int = 1,
                  metrics_fn=None, num_inputs: int = 1, seed: int = 0,
                  event_handler=None, **resilience_kwargs) -> TrainState:
    """One-call fault-tolerant training: build the Trainer, init (or
    restore) the state, and drive it through `ResilientTrainer.run`.
    `resilience_kwargs` go to `ResilientTrainer` (policy knobs,
    watchdog, cadence). Raises `Preempted` after the drain save when
    the process is being evicted — rerunning the same call resumes."""
    trainer = Trainer(model, loss_fn, optimizer, metrics_fn=metrics_fn,
                      num_inputs=num_inputs, seed=seed)
    state = trainer.init_state(input_spec)
    rt = ResilientTrainer(trainer, checkpoint_dir, **resilience_kwargs)
    return rt.run(state, batch_iter_factory, num_passes=num_passes,
                  event_handler=event_handler)
