"""Command-line driver (reference: the `paddle` shell dispatcher,
scripts/submit_local.sh.in:3-14 — train | pserver | merge_model |
dump_config | version; TrainerMain.cpp:32).

Subcommands:
  version      — build/runtime info
  train        — run a config script's training job
  dump-config  — print a config script's resolved topology as JSON
  merge-model  — config + trained params -> single compiled artifact
  infer        — run a compiled artifact on .npy inputs
  serve        — continuous-batching LM serving (token ids in/out)
  master       — serve a task-queue master over a recordio dataset

A config script is a Python file defining `get_config()` returning a dict:
  model      (nn.Layer, required)
  input_spec (ShapeSpec or tuple shape, required)
  loss_fn / optimizer / metrics_fn / reader / num_passes (train keys)
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import runpy
import sys
import time
from typing import Optional


def _transfer_guard(enabled: bool):
    """Opt-in runtime enforcement for the hot loop (`--transfer-guard`,
    docs/ANALYSIS.md): implicit host<->device transfers raise instead
    of silently re-staging every step. Explicit staging
    (jax.device_put / jnp.asarray of numpy arrays) stays allowed."""
    if not enabled:
        return contextlib.nullcontext()
    from paddle_tpu.analysis.guards import no_implicit_transfers

    return no_implicit_transfers()


def _enable_compile_cache(args) -> None:
    """Persistent XLA compile cache, ON BY DEFAULT for serve/train/
    infer (docs/SERVING.md "AOT artifacts & compile cache"): a
    warm-cache restart skips XLA compilation for every jitted body
    the run builds. Where `JAX_COMPILATION_CACHE_DIR` is set the
    cache lives there; otherwise in `--compile-cache DIR`, or by
    default `<checkout>/.jax_cache`
    (`compilation_cache.DEFAULT_DIR`). `--no-compile-cache` opts
    out. Must run before the first jit compiles, so every cmd_*
    calls it up front; it does not initialise the backend. Corrupt
    or stale-version entries degrade to a miss, never an error."""
    if getattr(args, "no_compile_cache", False):
        return
    from paddle_tpu import compilation_cache

    compilation_cache.enable(getattr(args, "compile_cache", None))


def _obs_stack(metrics_out=None, flight_dir=None):
    """Build the (registry, tracer, flight) triple for an instrumented
    run — or (None, None, None) when neither flag asked for it, so the
    uninstrumented path allocates nothing (the <2% overhead gate)."""
    if metrics_out is None and flight_dir is None:
        return None, None, None
    from paddle_tpu.obs import (FlightRecorder, MetricsRegistry, Tracer,
                                set_default)

    if flight_dir:
        # pre-create it: FlightRecorder.dump treats a nonexistent
        # directory as an exact FILE path, which would collapse every
        # fault dump onto one overwritten file
        os.makedirs(flight_dir, exist_ok=True)

    registry = MetricsRegistry() if metrics_out else None
    if registry is not None:
        # compile-cache hit/miss counters ride the same export
        # (docs/OBSERVABILITY.md) — process-global, so they register
        # here ONCE rather than per server (a fleet run's router
        # summing per-replica counters must not multiply-count them)
        from paddle_tpu import compilation_cache

        compilation_cache.install_listeners()
        registry.register_source("compile_cache",
                                 compilation_cache.counters)
    flight = FlightRecorder()
    # finished spans feed the ring; the module default makes
    # RecompileGuard / transfer-guard violations land there too
    set_default(flight)
    return registry, Tracer(sink=flight.note_span), flight


def _timeline_source(timeline):
    """The training timeline as a registry source: its counters by
    name and each span's summary as `<span>.<total_s|count|...>`."""
    def read():
        out = dict(timeline.counters())
        for name, row in timeline.summary().items():
            for key, value in row.items():
                out[f"{name}.{key}"] = value
        return out

    return read


def _write_metrics(registry, path: str) -> None:
    """Export a registry snapshot: .json/.jsonl gets the JSON-lines
    form, anything else Prometheus text exposition."""
    if registry is None or not path:
        return
    text = (registry.to_jsonl()
            if path.endswith((".json", ".jsonl"))
            else registry.to_prometheus())
    with open(path, "w") as f:
        f.write(text)


def _load_config(path: str) -> dict:
    ns = runpy.run_path(path)
    if "get_config" not in ns:
        raise SystemExit(f"{path} does not define get_config()")
    cfg = ns["get_config"]()
    if "model" not in cfg or "input_spec" not in cfg:
        raise SystemExit("get_config() must provide 'model' and 'input_spec'")
    return cfg


def _input_spec(cfg):
    from paddle_tpu.nn.module import ShapeSpec

    spec = cfg["input_spec"]
    return spec if isinstance(spec, ShapeSpec) else ShapeSpec(tuple(spec))


def cmd_version(_args) -> int:
    import jax

    import paddle_tpu

    print(f"paddle_tpu {paddle_tpu.__version__}")
    print(f"jax {jax.__version__}")
    try:
        devs = jax.devices()
        print(f"devices: {len(devs)} x {devs[0].platform}")
    except Exception as e:  # no backend available
        print(f"devices: unavailable ({e})")
    return 0


def cmd_dump_config(args) -> int:
    import jax

    cfg = _load_config(args.config)
    model = cfg["model"]
    spec = _input_spec(cfg)
    params, mstate = model.init(jax.random.key(0), spec)
    leaves = jax.tree_util.tree_leaves(params)
    out = {
        "model": type(model).__name__,
        "input_shape": list(spec.shape),
        "num_parameters": int(sum(x.size for x in leaves)),
        "num_tensors": len(leaves),
        "parameters": {
            "/".join(map(str, path)): list(x.shape)
            for path, x in _named_leaves(params)
        },
    }
    print(json.dumps(out, indent=1))
    return 0


def _named_leaves(tree):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        keys = []
        for p in path:
            keys.append(getattr(p, "key", getattr(p, "idx", p)))
        yield keys, leaf


def _gang_job_from_config(*, config: str, batch_size: int,
                          learning_rate: float = 0.01) -> dict:
    """Gang-builder (the `parallel.launch` contract) over a train
    config script: `train --elastic N` ships THIS function's
    "module:function" name across the spawn boundary, and every gang
    member — including ones booted after a reform — rebuilds the job
    from the config file. The reader must therefore be deterministic:
    a reformed member replays the same batch sequence from the resume
    cursor, which is what makes the exactly-once step accounting hold.
    """
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import data as data_mod
    from paddle_tpu import optim
    from paddle_tpu.data.batch import stack_columns
    from paddle_tpu.ops import losses

    cfg = _load_config(config)
    loss_fn = cfg.get("loss_fn") or (
        lambda lo, la: jnp.mean(losses.softmax_cross_entropy(lo, la)))

    def batches(total_steps):
        # materialized (not streamed): the gang contract wants GLOBAL
        # batches indexable from any resume cursor; ragged tails are
        # dropped because every member slices batch/num_processes rows
        out = []
        while len(out) < total_steps:
            produced = False
            for samples in data_mod.batch_reader(
                    cfg["reader"], batch_size, drop_last=True)():
                cols = stack_columns(samples)
                if len(cols) != 2:
                    raise SystemExit(
                        "--elastic needs (input, label) samples, got "
                        f"{len(cols)}-field samples")
                out.append((np.asarray(cols[0]), np.asarray(cols[1])))
                produced = True
                if len(out) == total_steps:
                    break
            if not produced:
                raise SystemExit(
                    "config reader yielded no full batches of "
                    f"{batch_size}")
        return out

    return {
        "model": cfg["model"],
        "loss_fn": loss_fn,
        "optimizer": cfg.get("optimizer") or optim.sgd(learning_rate),
        "input_specs": (_input_spec(cfg),),
        "batches": batches,
    }


def _cmd_train_elastic(args) -> int:
    """`train --elastic N` (docs/RELIABILITY.md "Elastic training
    fault model"): the CLI process becomes the GangSupervisor — it
    never touches jax itself — and N child trainers run the ZeRO
    step over a shared coordinator. Dead/wedged members are detected
    (heartbeats + the watchdog's exit 75), the gang tears down,
    reforms at the surviving count and resumes from the durable
    sharded checkpoint. Checkpoints stay in --checkpoint-dir; a later
    plain `train --checkpoint-dir` run (or `--elastic M`) resumes
    from them at any topology."""
    from paddle_tpu.parallel.launch import GangFailedError, GangSupervisor

    if not args.checkpoint_dir:
        raise SystemExit("--elastic requires --checkpoint-dir (the gang "
                         "resumes from durable sharded checkpoints)")
    registry = None
    if args.metrics_out:
        from paddle_tpu.obs import MetricsRegistry

        registry = MetricsRegistry()
    sup = GangSupervisor(
        "paddle_tpu.cli:_gang_job_from_config",
        {"config": args.config, "batch_size": args.batch_size,
         "learning_rate": args.learning_rate},
        workdir=os.path.join(args.checkpoint_dir, "gang"),
        checkpoint_dir=args.checkpoint_dir,
        num_processes=args.elastic,
        total_steps=args.total_steps,
        checkpoint_every=args.checkpoint_every or 2,
        seed=args.seed,
        min_procs=args.min_procs,
        watchdog_timeout_s=args.watchdog_timeout)
    if registry is not None:
        sup.bind_metrics(registry)
    try:
        out = sup.run(deadline_s=args.gang_deadline)
    except GangFailedError as e:
        print(f"elastic gang failed: {e}")
        _write_metrics(registry, args.metrics_out)
        return 1
    c = sup.counters()
    print(f"elastic gang done: {len(out['results'])} member(s) at "
          f"gang epoch {int(c['gang_epoch'])}, reforms "
          f"{int(c['reforms'])}, members lost {int(c['members_lost'])}, "
          f"wedged fenced {int(c['fenced_wedged'])}")
    for res in sorted(out["results"], key=lambda r: r["rank"]):
        tail = (f" cost {res['losses'][-1]:.6f}" if res["losses"] else "")
        print(f"  rank {res['rank']}: resumed@{res['restored_step']} "
              f"finished step {res['final_step']}{tail}")
    _write_metrics(registry, args.metrics_out)
    return 0


def cmd_train(args) -> int:
    # the elastic gang path forks trainer processes; the supervisor
    # itself must stay jax-free, so it dispatches before anything else
    if getattr(args, "elastic", None):
        return _cmd_train_elastic(args)

    # multi-host join must precede any other jax-touching call
    if getattr(args, "coordinator", None):
        from paddle_tpu.parallel import distributed

        distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id)

    # before anything compiles (it does not touch the backend)
    _enable_compile_cache(args)

    import jax.numpy as jnp

    from paddle_tpu import data as data_mod
    from paddle_tpu import optim
    from paddle_tpu.ops import losses
    from paddle_tpu.train import Trainer, events as E
    from paddle_tpu.train.checkpoint import save_parameters_tar

    cfg = _load_config(args.config)
    loss_fn = cfg.get("loss_fn") or (
        lambda lo, la: jnp.mean(losses.softmax_cross_entropy(lo, la)))
    trainer = Trainer(
        cfg["model"],
        loss_fn=loss_fn,
        optimizer=cfg.get("optimizer") or optim.sgd(args.learning_rate),
        metrics_fn=cfg.get("metrics_fn"),
        num_inputs=cfg.get("num_inputs", 1),
        seed=args.seed,
    )
    state = trainer.init_state(_input_spec(cfg))
    reader = cfg.get("reader")
    if reader is None:
        raise SystemExit("config provides no 'reader' for training")
    feeder = data_mod.DataFeeder()
    batches = lambda: feeder(data_mod.batch_reader(reader, args.batch_size))
    zero_mesh = None
    if args.zero:
        import jax

        from paddle_tpu.core.mesh import (MeshConfig, batch_sharding,
                                          build_mesh)
        from paddle_tpu.parallel import make_zero_train_step
        from paddle_tpu.train.state import TrainState

        ndev = len(jax.devices())
        if args.batch_size % ndev:
            raise SystemExit(
                f"--zero: batch size {args.batch_size} must divide the "
                f"{ndev}-device data mesh")
        zero_mesh = build_mesh(MeshConfig(data=ndev))
        # same init-rng consumption as the replicated path — only the
        # optimizer-state LAYOUT changes (flat, padded, sharded over
        # the data axis); the update itself stays bit-identical
        state = TrainState.create_zero(state.params, state.model_state,
                                       trainer.optimizer, zero_mesh)
        trainer._train_step = make_zero_train_step(
            cfg["model"], loss_fn, trainer.optimizer, zero_mesh,
            metrics_fn=cfg.get("metrics_fn"))
        zero_shard = batch_sharding(zero_mesh)
        raw_zero = batches
        batches = lambda: (
            jax.tree.map(lambda a: jax.device_put(a, zero_shard), b)
            for b in raw_zero())
    if args.transfer_guard and zero_mesh is None:
        # the input feed is the hot loop's ONE sanctioned transfer —
        # stage it explicitly so `disallow` holds for everything else
        import jax

        raw_batches = batches
        batches = lambda: (jax.device_put(b) for b in raw_batches())

    # monotonic is the obs-layer clock convention (registry/tracer
    # default) — elapsed display must not jump with wall-clock slews
    t0 = time.monotonic()

    def handler(ev):
        if isinstance(ev, E.EndIteration) and ev.batch_id % args.log_period == 0:
            print(f"pass {ev.pass_id} batch {ev.batch_id} "
                  f"cost {ev.cost:.6f} ({time.monotonic() - t0:.1f}s)")
        if isinstance(ev, E.EndPass):
            print(f"=== pass {ev.pass_id} done ===")

    # explicit --num-passes wins over the config's num_passes
    num_passes = (args.num_passes if args.num_passes is not None
                  else cfg.get("num_passes", 1))
    # obs stack only when asked: flight dumps land beside the
    # checkpoints (ResilientTrainer's flight_dir default)
    registry, tracer, flight = _obs_stack(args.metrics_out)
    if registry is not None:
        # the loop and the feeder time themselves whether or not anyone
        # asked (obs.trace.Timeline); this is the operator's reading
        registry.register_source("train_timeline",
                                 _timeline_source(trainer.timeline))
    if args.checkpoint_dir:
        # fault-tolerant path: auto-restore + preemption drain +
        # divergence guard + optional watchdog (docs/RELIABILITY.md)
        from paddle_tpu.train.resilience import (Preempted,
                                                 ResilientTrainer)

        manager = step_builder = None
        if zero_mesh is not None:
            # reshard-on-restore: a ZeRO checkpoint written at one
            # device count restores bit-exactly at this one, and the
            # lr-backoff rebuild goes through the zero step, not the
            # replicated make_train_step
            from paddle_tpu.train.checkpoint import (
                ElasticCheckpointManager)

            manager = ElasticCheckpointManager(args.checkpoint_dir,
                                               mesh=zero_mesh)
            step_builder = lambda opt: make_zero_train_step(
                cfg["model"], loss_fn, opt, zero_mesh,
                metrics_fn=cfg.get("metrics_fn"), donate=False)
        rt = ResilientTrainer(
            trainer, args.checkpoint_dir,
            checkpoint_every_n_batches=args.checkpoint_every,
            bad_step_policy=args.bad_step_policy,
            max_bad_steps=args.max_bad_steps,
            lr_backoff=args.lr_backoff,
            watchdog_timeout_s=args.watchdog_timeout,
            checkpoint_manager=manager, step_builder=step_builder,
            tracer=tracer, flight=flight)
        if registry is not None:
            rt.bind_metrics(registry)
        try:
            with _transfer_guard(args.transfer_guard):
                state = rt.run(state, batches, num_passes=num_passes,
                               event_handler=handler)
        except Preempted as p:
            print(f"preempted: checkpoint saved at step {p.step}; "
                  f"re-run to resume")
            _write_metrics(registry, args.metrics_out)
            return 143   # 128 + SIGTERM: the scheduler restarts us
        _write_metrics(registry, args.metrics_out)
    else:
        with _transfer_guard(args.transfer_guard):
            state = trainer.train(
                state, batches, num_passes=num_passes,
                event_handler=handler)
        _write_metrics(registry, args.metrics_out)
    if args.save_dir:
        import os

        os.makedirs(args.save_dir, exist_ok=True)
        out = os.path.join(args.save_dir, "params.tar")
        save_parameters_tar(state.params, out)
        print(f"saved parameters to {out}")
    return 0


def _init_model_from_config(args):
    """Load config, init params (seed 0), optionally overlay a params
    tar — shared by merge-model and export-native."""
    import jax

    from paddle_tpu.train.checkpoint import load_parameters_tar

    cfg = _load_config(args.config)
    model = cfg["model"]
    spec = _input_spec(cfg)
    params, mstate = model.init(jax.random.key(0), spec)
    if getattr(args, "params", None):
        params = load_parameters_tar(params, args.params)
    return cfg, model, spec, params, mstate


def cmd_merge_model(args) -> int:
    import numpy as np

    from paddle_tpu.serve import export_compiled_model

    cfg, model, spec, params, mstate = _init_model_from_config(args)

    def forward(x):
        out, _ = model.apply(params, mstate, x, training=False)
        return out

    x = np.zeros(spec.shape, np.float32)
    export_compiled_model(forward, [x], args.output,
                          name=cfg.get("name", "model"))
    print(f"wrote compiled artifact {args.output}")
    return 0


def cmd_export_native(args) -> int:
    """Export a model to the .ptni artifact served by the Python-free
    native engine (native/src/infer.cc)."""
    from paddle_tpu.serve.native_export import export_native

    cfg, model, spec, params, mstate = _init_model_from_config(args)
    export_native(model, params, mstate, spec, args.output)
    print(f"wrote native artifact {args.output}")
    return 0


def cmd_infer(args) -> int:
    import numpy as np

    _enable_compile_cache(args)
    from paddle_tpu.serve import load_compiled_model

    m = load_compiled_model(args.artifact)
    inputs = [np.load(p) for p in args.inputs]
    out = m.predict(*inputs)
    import jax

    for i, o in enumerate(jax.tree_util.tree_leaves(out)):
        o = np.asarray(o)
        if args.output_prefix:
            np.save(f"{args.output_prefix}{i}.npy", o)
        print(f"output[{i}] shape={o.shape} dtype={o.dtype} "
              f"mean={float(o.mean()):.6f}")
    return 0


def cmd_serve(args) -> int:
    """Continuous-batching LM serving from the command line: a config
    script supplies the model (cfg + params), prompts come one
    whitespace-separated token-id sequence per line, completions leave
    the same way (the framework is tokenizer-agnostic, like the
    reference's id-based SequenceGenerator)."""
    import numpy as np

    _enable_compile_cache(args)
    from paddle_tpu.serve import DecodeEngine

    ns = runpy.run_path(args.config)
    if "get_serve_config" not in ns:
        raise SystemExit(
            f"{args.config} must define get_serve_config() -> dict "
            "with keys: cfg (TransformerConfig), params; optional: "
            "eos_id, slots, max_len")
    if args.fleet_procs is not None and args.replicas is not None:
        raise SystemExit(
            "--fleet-procs and --replicas are mutually exclusive: "
            "one fleet of threads OR one fleet of processes")
    # One process per chip: a --fleet-procs parent that built the
    # model here would initialise the backend and hold the chip its
    # replica children need. The children run the config themselves
    # (serve.fleet.build_server_from_config); the parent only checked
    # above that it defines the entry point, and stays off jax.
    sc = None
    if not args.fleet_procs:
        sc = ns["get_serve_config"]()
        missing = {"cfg", "params"} - set(sc)
        if missing:
            raise SystemExit(
                f"get_serve_config() is missing {sorted(missing)}")

    def make_engine():
        return DecodeEngine(
            sc["params"], sc["cfg"],
            slots=(sc.get("slots", 8) if args.slots is None
                   else args.slots),
            max_len=(sc.get("max_len", 2048) if args.max_len is None
                     else args.max_len),
            eos_id=sc.get("eos_id"), seed=args.seed)

    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else None)
    if args.http is not None:
        # network mode: the HTTP front door replaces the prompts
        # batch — clients drive the fleet over sockets until SIGTERM
        # (or --http-max-requests) drains it
        with _transfer_guard(args.transfer_guard):
            return _serve_http(args, make_engine, buckets)
    if args.prompts is None:
        raise SystemExit("--prompts is required (unless --http PORT "
                         "serves over the network instead)")
    eng = None if args.fleet_procs else make_engine()

    with open(args.prompts) as f:
        prompts = [np.asarray([int(t) for t in line.split()], np.int32)
                   for line in f if line.strip()]
    # `is not None`, not truthiness: explicit zeros must REACH the
    # engine's sampler validation and fail loudly, not vanish
    one = {k: v for k, v in (("temperature", args.temperature),
                             ("top_k", args.top_k),
                             ("top_p", args.top_p)) if v is not None}
    sampling = [dict(one) for _ in prompts] if one else None
    # open the sink BEFORE the (possibly long) serve run: an
    # unwritable --output must fail fast, not discard the decode work
    sink = open(args.output, "w") if args.output else sys.stdout
    # any of these flags needs the ServingServer wrapper: the queue /
    # deadline knobs obviously, but also --engine-artifact (bundle
    # adoption happens at server boot) and the obs flags (counters and
    # flight events hang off the server) — silently ignoring them on
    # the bare eng.serve() path would look like a no-op to the user
    reliable = (args.max_queue is not None
                or args.default_deadline_ms is not None
                or args.engine_artifact is not None
                or args.metrics_out is not None
                or args.flight_dir is not None)
    try:
        if args.fleet_procs:
            # N replica PROCESSES behind the fleet supervisor
            # (docs/SERVING.md "Elastic autoscaling & rolling
            # upgrades"): SIGKILL-safe failover, elastic scale
            with _transfer_guard(args.transfer_guard):
                return _serve_fleet_procs(args, prompts, sampling,
                                          buckets, sink)
        if args.replicas is not None and args.replicas > 1:
            # N single-box replicas behind the prefix-affinity router
            # (docs/SERVING.md "Multi-replica routing"): one engine
            # (and so one paged pool + prefix cache) per replica,
            # weights shared host-side
            engines = [eng] + [make_engine()
                               for _ in range(args.replicas - 1)]
            with _transfer_guard(args.transfer_guard):
                return _serve_fleet(args, engines, prompts, sampling,
                                    buckets, sink)
        if reliable:
            with _transfer_guard(args.transfer_guard):
                return _serve_reliable(args, eng, prompts, sampling,
                                       buckets, sink)
        with _transfer_guard(args.transfer_guard):
            out = eng.serve(prompts, max_new=args.max_new,
                            buckets=buckets, sampling=sampling,
                            return_logprobs=args.logprobs)
        toks, lps = out if args.logprobs else (out, None)
        for i, g in enumerate(toks):
            print(" ".join(str(t) for t in g), file=sink)
            if lps is not None:
                print("# logprobs " +
                      " ".join(f"{x:.4f}" for x in lps[i]), file=sink)
    finally:
        if sink is not sys.stdout:
            sink.close()
    return 0


def _serve_http(args, make_engine, buckets):
    """`serve --http PORT`: the streaming HTTP front door
    (docs/SERVING.md "HTTP front door"). Composes with the fleet
    flags — bare = one reliability server behind a 1-replica router,
    `--replicas N` = the thread fleet, `--fleet-procs N` = the
    process fleet with elastic autoscaling — and serves until SIGTERM
    (edge drain → fleet drain → drain report) or until
    `--http-max-requests` requests have finished (the deterministic
    test/CI stop). `--http-addr-file` publishes the bound address
    (written atomically AFTER the listener is up), so port 0 works
    for parallel test runs."""
    from paddle_tpu.serve.http_edge import HttpEdge
    from paddle_tpu.serve.router import ServingRouter
    from paddle_tpu.serve.server import ServingServer

    registry, tracer, flight = _obs_stack(args.metrics_out,
                                          args.flight_dir)
    if registry is None:
        # the edge serves GET /metrics: a live scrape target must not
        # depend on --metrics-out (that flag means "snapshot a file at
        # exit"). The no-registry fast path exists for uninstrumented
        # in-process serving; a network edge IS the instrumented mode.
        from paddle_tpu.obs import MetricsRegistry

        registry = MetricsRegistry()
    max_queue = args.max_queue if args.max_queue is not None else 64
    sup = None
    if args.fleet_procs:
        from paddle_tpu.serve.fleet import FleetSupervisor, ReplicaSpec

        env = {k: v for k, v in ((n, os.environ.get(n))
                                 for n in ("JAX_PLATFORMS",
                                           "XLA_FLAGS"))
               if v is not None}
        spec = ReplicaSpec(
            builder="paddle_tpu.serve.fleet:build_server_from_config",
            kwargs=dict(
                config=os.path.abspath(args.config),
                slots=args.slots, max_len=args.max_len,
                seed=args.seed, max_queue=max_queue,
                default_deadline_ms=args.default_deadline_ms,
                max_retries=args.max_retries, buckets=buckets,
                drain_grace_s=args.drain_grace,
                artifact=args.engine_artifact),
            env=env)
        sup = FleetSupervisor(
            spec, min_replicas=args.fleet_procs,
            max_replicas=max(args.fleet_procs,
                             args.fleet_max or args.fleet_procs),
            registry=registry, flight=flight,
            flight_dir=args.flight_dir)
        sup.start()
        # the supervisor's sweep drives autoscale/reap on the edge's
        # drive thread; its submit routes through admission control
        edge = HttpEdge(sup.router, host=args.http_host,
                        port=args.http,
                        sweep_fn=sup.sweep, submit_fn=sup.submit,
                        drain_fn=lambda why: sup.drain(reason=why),
                        registry=registry,
                        drain_report_path=args.drain_report)
    else:
        n = args.replicas or 1
        engines = [make_engine() for _ in range(n)]
        servers = [
            ServingServer(
                e, max_queue=max_queue,
                default_deadline_ms=args.default_deadline_ms,
                max_retries=args.max_retries, buckets=buckets,
                drain_grace_s=args.drain_grace,
                tracer=tracer, flight=flight,
                artifact_path=args.engine_artifact)
            for e in engines]
        router = ServingRouter(servers, tracer=tracer, flight=flight,
                               flight_dir=args.flight_dir)
        if registry is not None:
            router.bind_metrics(registry)
        edge = HttpEdge(router, host=args.http_host, port=args.http,
                        registry=registry, tracer=tracer,
                        drain_report_path=args.drain_report)
    edge.start()
    edge.install_signals()
    if args.http_addr_file:
        tmp = f"{args.http_addr_file}.tmp"
        with open(tmp, "w") as f:
            f.write(f"{edge.addr[0]} {edge.addr[1]}\n")
        os.replace(tmp, args.http_addr_file)
    print(f"# serving HTTP on {edge.addr[0]}:{edge.addr[1]}",
          flush=True)
    limit = args.http_max_requests
    drained = False
    try:
        while not edge.draining:
            if limit is not None:
                c = edge.counters()
                if (c["requests"] >= limit
                        and c["active_streams"] == 0):
                    edge.drain(reason=f"served {limit} requests "
                                      "(--http-max-requests)")
                    break
            time.sleep(0.05)
        drained = edge.wait_drained(timeout_s=args.drain_grace)
    finally:
        edge.close()
        if sup is not None:
            sup.shutdown(drain=False)
    c = edge.counters()
    print("# outcomes " + " ".join(f"{k}={v}" for k, v in c.items()),
          flush=True)
    _write_metrics(registry, args.metrics_out)
    return 0 if drained else 1


def _serve_reliable(args, eng, prompts, sampling, buckets, sink):
    """`serve` with the reliability layer (docs/RELIABILITY.md
    "Serving fault model"): bounded admission queue + load shedding,
    per-request deadlines, slot retry, SIGTERM graceful drain. One
    output line per request IN ORDER — completed requests print their
    token ids, everything else a `# req <i> <outcome>: <reason>`
    comment — plus one `# outcomes ...` counters trailer, so a caller
    can reconcile the whole run from the transcript alone."""
    from paddle_tpu.serve.server import QueueFullError, ServingServer

    registry, tracer, flight = _obs_stack(args.metrics_out,
                                          args.flight_dir)
    server = ServingServer(
        eng,
        max_queue=(args.max_queue if args.max_queue is not None
                   else 64),
        default_deadline_ms=args.default_deadline_ms,
        max_retries=args.max_retries,
        buckets=buckets,
        drain_grace_s=args.drain_grace,
        drain_report_path=args.drain_report,
        install_signal_handlers=True,
        tracer=tracer, flight=flight,
        artifact_path=args.engine_artifact)
    if registry is not None:
        server.bind_metrics(registry)
    # feed the batch AS THE QUEUE DRAINS, like a well-behaved client:
    # submitting everything up-front would force the shed path on any
    # batch larger than max_queue even though the pool is idle and the
    # work is known (the queue bound is for live overload, not a cap
    # on how much a batch run may serve)
    ids = {}
    cursor = [0]

    def feed(_srv=None, _step=None):
        while (cursor[0] < len(prompts) and server.queue_space > 0
               and not server.draining):
            i = cursor[0]
            cursor[0] += 1
            try:
                ids[i] = server.submit(
                    prompts[i], max_new=args.max_new,
                    sampling=(sampling[i] if sampling else None))
            except (ValueError, QueueFullError) as e:
                # recorded in server.results under its assigned id
                ids[i] = e.req_id

    server.on_step.append(feed)
    feed()
    results = server.run()
    while cursor[0] < len(prompts) and not server.draining:
        # the pool drained before the feeder saw a step (e.g. every
        # queued request expired at admission) — feed the rest
        feed()
        results = server.run()
    _render_serve_results(args, sink, prompts, ids, results,
                          server.counters())
    _write_metrics(registry, args.metrics_out)
    return 0


def _render_serve_results(args, sink, prompts, ids, results, counters):
    """THE ordered per-request output convention, shared by the
    single-server reliable path and the fleet path so the transcript
    format cannot drift between them: completed requests print their
    token ids (plus optional logprobs), everything else a
    `# req <i> <outcome>: <reason>` comment, then one `# outcomes`
    counters trailer a caller can reconcile the whole run from."""
    for i in range(len(prompts)):
        if i not in ids:
            print(f"# req {i} shed: not submitted (draining)",
                  file=sink)
            continue
        res = results[ids[i]]
        if res.outcome == "completed":
            print(" ".join(str(t) for t in res.tokens), file=sink)
            if args.logprobs:
                print("# logprobs " + " ".join(
                    f"{x:.4f}" for x in res.logprobs), file=sink)
        else:
            print(f"# req {i} {res.outcome}: {res.error}", file=sink)
    print("# outcomes " + " ".join(f"{k}={v}"
                                   for k, v in counters.items()),
          file=sink)
    return 0


def _serve_fleet(args, engines, prompts, sampling, buckets, sink):
    """`serve --replicas N`: the multi-replica fleet (serve.router).
    Each replica is a full reliability server; the router fronts them
    with prefix-affinity routing, health-checked failover, and
    replica-loss redistribution. Like _serve_reliable, the batch FEEDS
    the fleet as queues drain (submitting everything up-front would
    shed any batch larger than the fleet's queue capacity while the
    pools sit idle), SIGTERM/SIGINT drains the whole fleet gracefully,
    and the output is one line per request IN ORDER plus the fleet
    `# outcomes` trailer."""
    import json
    import signal

    from paddle_tpu.serve.router import QueueFullError, ServingRouter
    from paddle_tpu.serve.server import ServingServer

    registry, tracer, flight = _obs_stack(args.metrics_out,
                                          args.flight_dir)
    servers = [
        ServingServer(
            e,
            max_queue=(args.max_queue if args.max_queue is not None
                       else 64),
            default_deadline_ms=args.default_deadline_ms,
            max_retries=args.max_retries,
            buckets=buckets,
            drain_grace_s=args.drain_grace,
            # replicas SHARE the fleet tracer: the router mints the
            # rr<N> span, the replica's _finish ends it
            tracer=tracer, flight=flight,
            # every replica boots from the same bundle (manifest
            # verified per replica — a mismatch degrades just that
            # replica to the jit path, counted in its counters)
            artifact_path=args.engine_artifact)
        for e in engines]
    router = ServingRouter(servers, tracer=tracer, flight=flight,
                           flight_dir=args.flight_dir)
    if registry is not None:
        router.bind_metrics(registry)

    def handler(signum, frame):
        router.drain(reason=f"signal {signum}")

    prev = {s: signal.signal(s, handler)
            for s in (signal.SIGTERM, signal.SIGINT)}
    ids = {}
    cursor = [0]

    def feed():
        while cursor[0] < len(prompts) and not router.draining:
            if (router.queue_space() <= 0
                    and any(r.routable() for r in router.replicas)):
                # queues full but the fleet is healthy: run() drains
                # them and the next feed() continues
                break
            # NO routable replica: submit anyway — it raises the
            # ledgered no-routable QueueFullError per prompt, so the
            # batch terminates with explicit sheds instead of
            # busy-spinning on a dead fleet
            i = cursor[0]
            cursor[0] += 1
            try:
                ids[i] = router.submit(
                    prompts[i], max_new=args.max_new,
                    sampling=(sampling[i] if sampling else None))
            except (ValueError, QueueFullError) as e:
                ids[i] = e.rr_id   # ledgered under its assigned id

    # feed AS QUEUES DRAIN, like the single-server reliable path:
    # every replica's step refills the fleet, so a batch larger than
    # the fleet's queue capacity streams through instead of being
    # served in drain-refill waves
    for srv in servers:
        srv.on_step.append(lambda _s, _step: feed())
    try:
        feed()
        results = router.run()
        while cursor[0] < len(prompts) and not router.draining:
            feed()
            results = router.run()
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
    router.reconcile()
    counters = router.counters()
    _render_serve_results(args, sink, prompts, ids, results, counters)
    _write_metrics(registry, args.metrics_out)
    if args.drain_report and router.draining:
        tmp = f"{args.drain_report}.tmp"
        with open(tmp, "w") as f:
            json.dump({"reason": "fleet drain", "counters": counters,
                       "per_replica": router.per_replica()}, f,
                      indent=1)
        import os

        os.replace(tmp, args.drain_report)
    return 0


def _serve_fleet_procs(args, prompts, sampling, buckets, sink):
    """`serve --fleet-procs N`: the cross-process fleet
    (serve.fleet). Each replica runs its ServingServer in its own OS
    process over the socket transport; the supervisor owns spawn /
    reap / autoscale (up to --fleet-max) and the router owns
    exactly-once failover, so a replica SIGKILL mid-batch
    redistributes its ledger instead of losing requests. The batch
    feeds the fleet between sweeps (child queues drain as we submit),
    SIGTERM/SIGINT drains the whole fleet, and the transcript is the
    shared ordered format plus the fleet `# outcomes` trailer."""
    import os
    import signal

    from paddle_tpu.serve.fleet import FleetSupervisor, ReplicaSpec
    from paddle_tpu.serve.router import QueueFullError

    # the parent-side tracer has no replica to hand spans to across
    # the process boundary; children run their own obs stacks
    registry, _tracer, flight = _obs_stack(args.metrics_out,
                                           args.flight_dir)
    # children land on the parent's platform: they inherit its
    # JAX_PLATFORMS; the spec env restates it with XLA_FLAGS
    env = {k: v for k, v in ((n, os.environ.get(n))
                             for n in ("JAX_PLATFORMS", "XLA_FLAGS"))
           if v is not None}
    spec = ReplicaSpec(
        builder="paddle_tpu.serve.fleet:build_server_from_config",
        kwargs=dict(
            config=os.path.abspath(args.config),
            slots=args.slots, max_len=args.max_len, seed=args.seed,
            max_queue=(args.max_queue if args.max_queue is not None
                       else 64),
            default_deadline_ms=args.default_deadline_ms,
            max_retries=args.max_retries, buckets=buckets,
            drain_grace_s=args.drain_grace,
            artifact=args.engine_artifact),
        env=env)
    sup = FleetSupervisor(
        spec, min_replicas=args.fleet_procs,
        max_replicas=max(args.fleet_procs,
                         args.fleet_max or args.fleet_procs),
        registry=registry, flight=flight,
        flight_dir=args.flight_dir)
    sup.start()

    def handler(signum, frame):
        sup.drain(reason=f"signal {signum}")

    prev = {s: signal.signal(s, handler)
            for s in (signal.SIGTERM, signal.SIGINT)}
    ids = {}
    try:
        cursor = 0
        while cursor < len(prompts) and not sup.router.draining:
            if (sup.router.queue_space() <= 0
                    and any(r.routable()
                            for r in sup.router.replicas)):
                # queues full but the fleet is healthy: a sweep
                # drains them (and may scale out), then keep feeding
                sup.sweep()
                continue
            try:
                ids[cursor] = sup.submit(
                    prompts[cursor], max_new=args.max_new,
                    sampling=(sampling[cursor] if sampling else None))
            except (ValueError, QueueFullError) as e:
                ids[cursor] = e.rr_id   # ledgered under its id
            cursor += 1
            sup.sweep()
        results = sup.run()
        sup.reconcile()
        counters = sup.router.counters()
        counters.update(sup.counters())
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
        sup.shutdown(drain=False)
    _render_serve_results(args, sink, prompts, ids, results, counters)
    _write_metrics(registry, args.metrics_out)
    return 0


def cmd_obs(args) -> int:
    """Observability utilities (docs/OBSERVABILITY.md):

      obs dump FILE   — pretty-print a flight-recorder dump
      obs schema      — self-check the metrics-export schema (build a
                        registry with one of each metric kind, snapshot
                        + export it, validate the invariants the
                        scrape/ingest side relies on); exit 1 on drift
    """
    if args.obs_cmd == "dump":
        with open(args.file) as f:
            payload = json.load(f)
        if payload.get("kind") != "flight_dump":
            print(f"{args.file}: not a flight dump "
                  f"(kind={payload.get('kind')!r})", file=sys.stderr)
            return 1
        print(f"flight dump: reason={payload['reason']} "
              f"pid={payload.get('pid')} "
              f"events={payload.get('n_events')}")
        for k, v in (payload.get("extra") or {}).items():
            print(f"  extra.{k} = {json.dumps(v, default=str)}")
        tail = payload.get("events", [])[-args.last:]
        for e in tail:
            t = e.get("t")
            head = (f"  [{t:.3f}] {e.get('kind')}/{e.get('name')}"
                    if isinstance(t, float)
                    else f"  {e.get('kind')}/{e.get('name')}")
            rest = {k: v for k, v in e.items()
                    if k not in ("t", "kind", "name")}
            print(head + (f" {json.dumps(rest, default=str)}"
                          if rest else ""))
        return 0
    if args.obs_cmd == "schema":
        from paddle_tpu.obs import MetricsRegistry

        reg = MetricsRegistry(clock=lambda: 0.0)
        reg.counter("demo_total", "demo counter").inc(
            2, labels={"outcome": "completed"})
        reg.gauge("demo_gauge", "demo gauge").set(1.5)
        reg.histogram("demo_seconds", "demo histogram").observe(0.01)
        snap = reg.snapshot()
        errs = []
        for key in ("ts", "series", "dropped_series", "source_errors"):
            if key not in snap:
                errs.append(f"snapshot missing key {key!r}")
        kinds = {s["name"]: s["kind"] for s in snap["series"]}
        for name, kind in (("demo_total", "counter"),
                           ("demo_gauge", "gauge")):
            if kinds.get(name) != kind:
                errs.append(f"{name}: kind {kinds.get(name)!r} != "
                            f"{kind!r}")
        for s in snap["series"]:
            if not isinstance(s.get("value"), (int, float)):
                errs.append(f"{s['name']}: non-numeric value")
        prom = reg.to_prometheus()
        for needle in ("# TYPE demo_total counter",
                       'demo_total{outcome="completed"} 2',
                       "# TYPE demo_seconds histogram",
                       'le="+Inf"', "demo_seconds_count",
                       "demo_seconds_sum"):
            if needle not in prom:
                errs.append(f"prometheus text missing {needle!r}")
        for line in reg.to_jsonl().splitlines():
            json.loads(line)   # every line must parse standalone
        if errs:
            for e in errs:
                print(f"schema drift: {e}", file=sys.stderr)
            return 1
        print(f"obs schema ok: {len(snap['series'])} series, "
              f"{len(prom.splitlines())} prometheus lines")
        return 0
    raise SystemExit(f"unknown obs subcommand {args.obs_cmd!r}")


def cmd_master(args) -> int:
    from paddle_tpu.native import MasterServer, TaskQueue

    q = TaskQueue(timeout_ms=args.task_timeout_ms,
                  max_retries=args.max_retries)
    if args.snapshot and _exists(args.snapshot):
        q.restore(args.snapshot)
        print(f"recovered master state from {args.snapshot}")
    else:
        for path in args.dataset:
            n = q.add_file_chunks(path, chunks_per_task=args.chunks_per_task)
            print(f"{path}: {n} tasks")
    q.start()
    srv = MasterServer(q, port=args.port)
    print(f"master serving on 127.0.0.1:{srv.port}")
    try:
        while True:
            time.sleep(args.snapshot_period)
            if args.snapshot:
                q.snapshot(args.snapshot)
    except KeyboardInterrupt:
        pass
    finally:
        if args.snapshot:
            q.snapshot(args.snapshot)
        srv.stop()
    return 0


def _exists(p: str) -> bool:
    import os

    return os.path.exists(p)


def cmd_make_diagram(args) -> int:
    from paddle_tpu.utils.diagram import model_to_dot

    cfg = _load_config(args.config)
    dot = model_to_dot(cfg["model"], name=cfg.get("name", "model"))
    if args.output:
        with open(args.output, "w") as f:
            f.write(dot + "\n")
        print(f"wrote {args.output} (render: dot -Tpng {args.output})")
    else:
        print(dot)
    return 0


def cmd_launch(args) -> int:
    from paddle_tpu.parallel import launch as launch_mod

    command = list(args.command)
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        raise SystemExit("launch needs a command, e.g. "
                         "`launch --hosts a,b -- train --config cfg.py`")
    if args.emit_jobset:
        sys.stdout.write(launch_mod.emit_jobset(
            args.emit_jobset, image=args.image, command=command,
            num_hosts=args.num_hosts, tpu_topology=args.tpu_topology))
        return 0
    if not args.hosts:
        raise SystemExit("launch needs --hosts or --emit-jobset")
    hosts = [h for h in args.hosts.split(",") if h]
    return launch_mod.launch_ssh(
        hosts, command, coordinator_port=args.coordinator_port,
        workdir=args.workdir, python=args.python, dry_run=args.dry_run)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="paddle_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sub.add_parser("version").set_defaults(fn=cmd_version)

    t = sub.add_parser("train")
    t.add_argument("--config", required=True)
    t.add_argument("--batch-size", type=int, default=32)
    t.add_argument("--num-passes", type=int, default=None,
                   help="overrides the config's num_passes (default 1)")
    t.add_argument("--learning-rate", type=float, default=0.01)
    t.add_argument("--log-period", type=int, default=10)
    t.add_argument("--save-dir", default=None)
    t.add_argument("--checkpoint-dir", default=None,
                   help="enable the fault-tolerant runtime: orbax "
                        "checkpoints here, auto-resume, SIGTERM drain, "
                        "divergence guard (docs/RELIABILITY.md)")
    t.add_argument("--checkpoint-every", type=int, default=None,
                   help="save every N batches (plus every pass end)")
    t.add_argument("--bad-step-policy", choices=("skip", "rollback"),
                   default="rollback")
    t.add_argument("--max-bad-steps", type=int, default=3)
    t.add_argument("--lr-backoff", type=float, default=None,
                   help="multiply the effective LR by this on each "
                        "rollback (0 < x < 1)")
    t.add_argument("--watchdog-timeout", type=float, default=None,
                   help="abort (exit 75) if no step completes for this "
                        "many seconds — bounds wedged-collective hangs")
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--transfer-guard", action="store_true",
                   help="enforce jax.transfer_guard('disallow') "
                        "around the train loop: implicit host<->device"
                        " transfers raise; batches are device_put "
                        "explicitly (docs/ANALYSIS.md)")
    t.add_argument("--metrics-out", default=None,
                   help="write an obs metrics snapshot here at exit "
                        "(.json/.jsonl -> JSON lines, else Prometheus "
                        "text), the training timeline's spans and "
                        "counters among it (train_timeline_*); with "
                        "--checkpoint-dir also enables step tracing + "
                        "the flight recorder (docs/OBSERVABILITY.md)")
    t.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent XLA compile-cache directory "
                        "(default <checkout>/.jax_cache; "
                        "JAX_COMPILATION_CACHE_DIR, where set, wins)")
    t.add_argument("--no-compile-cache", action="store_true",
                   help="disable the persistent compile cache")
    t.add_argument("--coordinator", default=None,
                   help="host:port of process 0 for multi-host jobs")
    t.add_argument("--num-processes", type=int, default=None)
    t.add_argument("--process-id", type=int, default=None)
    t.add_argument("--zero", action="store_true",
                   help="ZeRO-shard the optimizer state over all local "
                        "devices (parallel.make_zero_train_step): "
                        "bit-identical updates at ~1/N optimizer bytes "
                        "per replica; batch size must divide the "
                        "device count (docs/RELIABILITY.md)")
    t.add_argument("--elastic", type=int, default=None, metavar="N",
                   help="run an N-process elastic gang (parallel."
                        "GangSupervisor): dead/wedged members are "
                        "detected via heartbeats + the watchdog, the "
                        "gang reforms at the surviving count and "
                        "resumes from the durable ZeRO checkpoint — "
                        "requires --checkpoint-dir and a "
                        "deterministic reader (docs/RELIABILITY.md "
                        "'Elastic training fault model')")
    t.add_argument("--total-steps", type=int, default=100,
                   help="with --elastic: total optimizer steps for "
                        "the gang (the elastic path is step-, not "
                        "pass-, oriented)")
    t.add_argument("--min-procs", type=int, default=1,
                   help="with --elastic: fail the run rather than "
                        "reform below this many members")
    t.add_argument("--gang-deadline", type=float, default=3600.0,
                   help="with --elastic: wall-clock bound on the "
                        "whole gang run")
    t.set_defaults(fn=cmd_train)

    d = sub.add_parser("dump-config")
    d.add_argument("--config", required=True)
    d.set_defaults(fn=cmd_dump_config)

    en = sub.add_parser(
        "export-native",
        help=".ptni artifact for the Python-free CPU serving engine")
    en.add_argument("--config", required=True)
    en.add_argument("--params", default=None)
    en.add_argument("--output", required=True)
    en.set_defaults(fn=cmd_export_native)

    m = sub.add_parser("merge-model")
    m.add_argument("--config", required=True)
    m.add_argument("--params", default=None,
                   help="params.tar from `train --save-dir`")
    m.add_argument("--output", required=True)
    m.set_defaults(fn=cmd_merge_model)

    i = sub.add_parser("infer")
    i.add_argument("--artifact", required=True)
    i.add_argument("--output-prefix", default=None)
    i.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent XLA compile-cache directory "
                        "(default <checkout>/.jax_cache; "
                        "JAX_COMPILATION_CACHE_DIR, where set, wins)")
    i.add_argument("--no-compile-cache", action="store_true",
                   help="disable the persistent compile cache")
    i.add_argument("inputs", nargs="+", help=".npy input files")
    i.set_defaults(fn=cmd_infer)

    sv = sub.add_parser(
        "serve", help="continuous-batching LM serving (token ids in, "
        "token ids out; see cmd_serve)")
    sv.add_argument("--config", required=True,
                    help="script defining get_serve_config()")
    sv.add_argument("--prompts", default=None,
                    help="file: one whitespace-separated id sequence "
                    "per line (required unless --http)")
    sv.add_argument("--max-new", type=int, default=128)
    sv.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the streaming HTTP front door on this "
                         "port instead of a prompts batch (0 = "
                         "ephemeral; docs/SERVING.md \"HTTP front "
                         "door\"): POST /v1/generate streams tokens "
                         "via chunked transfer, client disconnect "
                         "cancels the request, overload sheds 429 at "
                         "the edge, SIGTERM drains edge then fleet")
    sv.add_argument("--http-host", default="127.0.0.1",
                    help="bind address for --http")
    sv.add_argument("--http-addr-file", default=None, metavar="PATH",
                    help="write 'host port' here once the --http "
                         "listener is bound (atomic; pairs with "
                         "--http 0 for test runs)")
    sv.add_argument("--http-max-requests", type=int, default=None,
                    metavar="N",
                    help="drain and exit after N HTTP requests have "
                         "finished (deterministic stop for tests/CI; "
                         "default: serve until SIGTERM)")
    sv.add_argument("--replicas", type=int, default=None,
                    help="serve through an N-replica fleet behind the "
                         "prefix-affinity router (serve.router): one "
                         "engine pool per replica, health-checked "
                         "failover, replica-loss redistribution")
    sv.add_argument("--fleet-procs", type=int, default=None,
                    help="serve through N replica PROCESSES behind "
                         "the fleet supervisor (serve.fleet): each "
                         "replica runs its ServingServer in its own "
                         "OS process over the socket transport, with "
                         "SIGKILL-safe exactly-once failover and "
                         "elastic autoscaling up to --fleet-max")
    sv.add_argument("--fleet-max", type=int, default=None,
                    help="autoscale ceiling for --fleet-procs "
                         "(default: the floor — no elastic headroom)")
    sv.add_argument("--slots", type=int, default=None)
    sv.add_argument("--max-len", type=int, default=None)
    sv.add_argument("--buckets", default=None,
                    help="comma-separated prompt-length buckets")
    sv.add_argument("--temperature", type=float, default=None)
    sv.add_argument("--top-k", type=int, default=None)
    sv.add_argument("--top-p", type=float, default=None)
    sv.add_argument("--seed", type=int, default=0)
    sv.add_argument("--logprobs", action="store_true")
    sv.add_argument("--output", default=None)
    # reliability layer (serve.server): any of --max-queue /
    # --default-deadline-ms routes through the admission-controlled
    # scheduler with load shedding, deadlines, retry, SIGTERM drain
    sv.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue; overflow sheds "
                         "the cheapest-to-retry request (enables the "
                         "reliability layer)")
    sv.add_argument("--default-deadline-ms", type=float, default=None,
                    help="per-request deadline: expired requests free "
                         "their slot mid-generation (enables the "
                         "reliability layer)")
    sv.add_argument("--drain-grace", type=float, default=30.0,
                    help="seconds SIGTERM drain waits for in-flight "
                         "requests before expiring them")
    sv.add_argument("--max-retries", type=int, default=1,
                    help="transient-fault requeue budget per request")
    sv.add_argument("--drain-report", default=None,
                    help="write the drain report JSON here on "
                         "graceful shutdown")
    sv.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent XLA compile-cache directory "
                         "(default <checkout>/.jax_cache; "
                         "JAX_COMPILATION_CACHE_DIR, where set, wins; "
                         "a warm-dir restart skips XLA compilation — "
                         "docs/SERVING.md)")
    sv.add_argument("--no-compile-cache", action="store_true",
                    help="disable the persistent compile cache")
    sv.add_argument("--engine-artifact", default=None, metavar="TAR",
                    help="AOT engine bundle "
                         "(serve.artifact.save_engine_artifact): "
                         "replicas verify its manifest at boot and "
                         "serve from pre-exported programs; any "
                         "mismatch falls back to the jit path with "
                         "an artifact_fallbacks counter")
    sv.add_argument("--transfer-guard", action="store_true",
                    help="enforce jax.transfer_guard('disallow') "
                         "around the decode loop: implicit "
                         "host<->device transfers raise "
                         "(docs/ANALYSIS.md)")
    sv.add_argument("--metrics-out", default=None,
                    help="write an obs metrics snapshot here at exit "
                         "(.json/.jsonl -> JSON lines, else "
                         "Prometheus text); enables request tracing "
                         "(docs/OBSERVABILITY.md)")
    sv.add_argument("--flight-dir", default=None,
                    help="flight-recorder dump directory: replica "
                         "death / breaker-open / SIGTERM dump the "
                         "recent-event ring here")
    sv.set_defaults(fn=cmd_serve)

    ms = sub.add_parser("master")
    ms.add_argument("--port", type=int, default=0)
    ms.add_argument("--dataset", nargs="*", default=[],
                    help="recordio files to partition into tasks")
    ms.add_argument("--chunks-per-task", type=int, default=1)
    ms.add_argument("--task-timeout-ms", type=int, default=60000)
    ms.add_argument("--max-retries", type=int, default=3)
    ms.add_argument("--snapshot", default=None)
    ms.add_argument("--snapshot-period", type=float, default=30.0)
    ms.set_defaults(fn=cmd_master)

    ob = sub.add_parser(
        "obs", help="observability utilities: pretty-print flight "
        "dumps, self-check the metrics schema (docs/OBSERVABILITY.md)")
    obs_sub = ob.add_subparsers(dest="obs_cmd", required=True)
    od = obs_sub.add_parser("dump",
                            help="pretty-print a flight-recorder dump")
    od.add_argument("file")
    od.add_argument("--last", type=int, default=20,
                    help="show only the last N ring events")
    obs_sub.add_parser(
        "schema",
        help="validate the metrics-export schema (exit 1 on drift)")
    ob.set_defaults(fn=cmd_obs)

    md = sub.add_parser(
        "make-diagram",
        help="emit a graphviz dot topology diagram (reference: "
             "make_model_diagram.py)")
    md.add_argument("--config", required=True)
    md.add_argument("--output", default=None)
    md.set_defaults(fn=cmd_make_diagram)

    l = sub.add_parser(
        "launch",
        help="fan a paddle_tpu command out to N hosts (reference: "
             "scripts/cluster_train/paddle.py) or emit a JobSet manifest")
    l.add_argument("--hosts", default=None,
                   help="comma-separated ssh destinations; first is the "
                        "coordinator")
    l.add_argument("--coordinator-port", type=int, default=1234)
    l.add_argument("--workdir", default=None)
    l.add_argument("--python", default="python")
    l.add_argument("--dry-run", action="store_true",
                   help="print the ssh commands without running them")
    l.add_argument("--emit-jobset", default=None, metavar="NAME",
                   help="print a k8s JobSet manifest instead of ssh")
    l.add_argument("--image", default="paddle-tpu:latest")
    l.add_argument("--num-hosts", type=int, default=4)
    l.add_argument("--tpu-topology", default="4x4")
    l.add_argument("command", nargs=argparse.REMAINDER,
                   help="command after `python -m paddle_tpu`, e.g. "
                        "`train --config cfg.py`")
    l.set_defaults(fn=cmd_launch)
    return p


def main(argv: Optional[list] = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # stdout consumer (e.g. `| head`) closed early — exit quietly
        try:
            sys.stdout.close()
        except Exception:
            pass
        return 0


if __name__ == "__main__":
    sys.exit(main())
