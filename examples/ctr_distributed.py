"""CTR wide&deep with sharded embeddings on a device mesh — the
reference's sparse-remote training (row-sharded tables, only touched
rows move; reference: pserver getParameterSparse, SparseRowMatrix)
as mesh embedding-parallelism with owner-routed all-to-all.

Runs on whatever devices exist; to simulate a multi-chip mesh on CPU:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/ctr_distributed.py
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import optim
from paddle_tpu.core import mesh as mesh_lib
from paddle_tpu.models.ctr import CTRModel


def run_pserver_demo(args):
    """The pserver-tier variant of the sparse tail: the table lives in
    host RAM on replicated `native.pserver` shards (leases, exactly-once
    push epochs, chain replication), and the trainer looks up / pushes
    through `PServerEmbedding` — the same call surface as
    ShardedEmbedding. Midway, the primary of shard 0 is KILLED to show
    the failover: training finishes through the replica with no lost or
    duplicated row updates (docs/RELIABILITY.md "Parameter-server fault
    model")."""
    from paddle_tpu.native.pserver import PServerGroup
    from paddle_tpu.parallel.pserver_client import (PServerClient,
                                                    PServerEmbedding)

    vocab = (args.vocab // 4) * 4
    with PServerGroup(vocab, args.dim, n_shards=4) as group:
        with PServerClient(group.specs, args.dim, trainer_id=0) as client:
            client.register()
            emb = PServerEmbedding(client)
            table = emb.init(jax.random.key(0))
            rs = np.random.RandomState(0)
            w = np.zeros(args.dim, np.float32)
            for i in range(args.steps):
                ids = rs.randint(0, vocab, args.batch).astype(np.int64)
                labels = (ids < vocab // 5).astype(np.float32)
                vecs = np.asarray(emb.lookup(table, ids))
                logits = vecs @ w
                p = 1.0 / (1.0 + np.exp(-logits))
                g = (p - labels)[:, None]
                w -= 0.05 * (g * vecs).mean(0)
                emb.apply_row_grads(table, ids, g * w[None, :] / len(ids),
                                    lr=0.05)
                if i == args.steps // 2:
                    group.primaries[0].kill()
                    print(f"step {i}: killed shard 0 primary — failing "
                          f"over to its replica")
                if i % 10 == 0:
                    loss = float(np.mean(
                        -labels * np.log(p + 1e-7)
                        - (1 - labels) * np.log(1 - p + 1e-7)))
                    print(f"step {i} logloss {loss:.4f}")
            client.finish_pass()
            print(f"pass finished through the failover; client stats "
                  f"{client.stats}")


def run_online_demo(args):
    """The full production loop in one process: a `TaskQueue` streams
    training tasks into a `StreamingTrainer` (no pass barrier — tasks
    flow continuously, pushes numbered by the exactly-once epoch
    watermark), the pushed rows land on `native.pserver` shards, and a
    `TieredEmbedCache` + `CtrServer` serve scores concurrently — the
    cache hears every push ACK through `bind_push_feed` and never
    serves a row staler than `max_staleness` pushes
    (docs/SERVING.md "Tiered embedding serving")."""
    import json

    from paddle_tpu.native.pserver import PServerGroup
    from paddle_tpu.native.taskqueue import TaskQueue
    from paddle_tpu.parallel.pserver_client import (PServerClient,
                                                    PServerEmbedding)
    from paddle_tpu.serve.ctr import CtrServer, init_tower
    from paddle_tpu.serve.embed_cache import TieredEmbedCache
    from paddle_tpu.train.online import StreamingTrainer

    vocab = (args.vocab // 4) * 4
    with PServerGroup(vocab, args.dim, n_shards=4) as group:
        push = PServerClient(group.specs, args.dim, trainer_id=0)
        push.register()
        emb = PServerEmbedding(push)
        table = emb.init(jax.random.key(0))

        queue = TaskQueue(timeout_ms=2000, max_retries=3)
        for i in range(args.steps):
            queue.add_task(json.dumps(
                {"seed": i, "batch": 8, "slots": 4,
                 "vocab": vocab}).encode())
        trainer = StreamingTrainer(queue, emb, table, lr=0.05)

        read = PServerClient(group.specs, args.dim, trainer_id=1)
        read.register()
        cache = TieredEmbedCache(PServerEmbedding(read), table,
                                 hot_rows=1024, host_rows=4096,
                                 max_staleness=4)
        cache.bind_push_feed(push)
        server = CtrServer(cache, init_tower(jax.random.key(1),
                                             args.dim),
                           slots=args.slots, max_batch=8)

        rs = np.random.RandomState(7)
        served = 0
        while trainer.stats["tasks_done"] < args.steps:
            trainer.step()               # streams: no pass barrier
            ids = rs.randint(0, vocab, (4, args.slots))
            scores = server.score(ids.astype(np.int64))
            served += len(scores)
            cache.refresh_stale()        # maintenance tick, off path
            if trainer.stats["tasks_done"] % 10 == 0:
                c = cache.counters()
                print(f"streamed {trainer.stats['tasks_done']:3d} "
                      f"tasks | served {served:4d} scores | cache "
                      f"hits {c['hits_device']} misses {c['misses']} "
                      f"stale-refills {c['stale_refills']}")
        rec = cache.reconcile([p.stats() for p in group.primaries])
        print(f"stream drained: trainer {trainer.stats} | "
              f"reconcile ok={rec['ok']} watermarks_match="
              f"{rec.get('watermarks_match_push_ledger')}")
        push.close()
        read.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--pserver", action="store_true",
                    help="train the sparse tail against a local "
                         "fault-tolerant parameter-server tier (and "
                         "kill a primary midway to show failover)")
    ap.add_argument("--online", action="store_true",
                    help="stream tasks through a StreamingTrainer into "
                         "the pserver tier while a TieredEmbedCache + "
                         "CtrServer serve scores concurrently — the "
                         "production online-learning loop")
    args = ap.parse_args()

    if args.online:
        run_online_demo(args)
        return
    if args.pserver:
        run_pserver_demo(args)
        return

    n_dev = len(jax.devices())
    mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(data=1, model=n_dev))
    print(f"mesh: {n_dev} device(s) on the '{mesh_lib.MODEL_AXIS}' axis; "
          f"tables row-sharded, lookups owner-routed all-to-all")

    model = CTRModel(vocab=args.vocab, embed_dim=args.dim, mesh=mesh)
    params, mlp_state = model.init(jax.random.key(0), args.batch, args.slots)
    opt = optim.adam(1e-3)
    opt_state = opt.init(params["mlp"])
    step = model.make_train_step(opt, mlp_state)

    rs = np.random.RandomState(0)
    lr = jnp.asarray(0.05, jnp.float32)
    for i in range(args.steps):
        ids = rs.randint(0, args.vocab, (args.batch, args.slots))
        # clicks correlate with low feature ids (a learnable signal)
        labels = (ids.min(1) < args.vocab // 5).astype(np.float32)
        params, opt_state, loss = step(
            params, opt_state, jnp.asarray(ids, jnp.int32),
            jnp.asarray(labels), lr, jnp.asarray(i, jnp.int32),
            jax.random.key(i))
        if i % 10 == 0:
            print(f"step {i} logloss {float(loss):.4f}")
    print(f"final logloss {float(loss):.4f}")


if __name__ == "__main__":
    main()
