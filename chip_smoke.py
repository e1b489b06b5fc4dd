#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, end to end, through the entry points a
user calls, at the full width of models the repo benchmarks (random
weights from a seed), in ONE process:

  train_resnet50     ResNet-50, 224x224x3, batch 256, bf16 compute,
                     momentum, through `Trainer` fed by `DataFeeder`
                     batches from a seeded synthetic reader: the
                     compiling step and 5 more, loss finite on each.
                     With more than one device the step is the sharded
                     one over all of them.
  train_transformer  one `T.loss` + grad step, seq 2048, dim 512 x 8
                     layers x 8 heads, vocab 32000, attn_impl="auto":
                     flash forward and its backward inside a real step,
                     checked against the dense path on the same batch.
  train_layer_kinds  one `T.loss_and_aux` + grad step on a block whose
                     layers differ in kind (three sliding-window layers,
                     one full layer with YaRN) over a dropless expert
                     layer that holds 4 of its 16 experts, seq 2048,
                     window 512: the band's kernels beside the full
                     layer's in one program, checked against the dense
                     path, and the expert layer's counts.
  train_hybrid       the same step on a block whose layers differ in
                     token mixer (three Gated DeltaNet layers, one gated
                     full layer with partial rotary) over the dropless
                     layer with a gated shared expert: the gated delta
                     rule's kernels and flash beside each other, checked
                     against the `jnp` chunked rule and the dense path.
  train_afmoe        the same step on Trinity's block: a leading dense
                     gated-SiLU layer, then three sliding-window layers
                     and a full layer without rotary (NoPE), gated
                     attention and sandwich norms, over the dropless
                     layer with a sigmoid router choosing by an expert
                     bias and an ungated shared expert; the router's
                     counts over all its experts.
  serve_http         the same transformer behind HttpEdge ->
                     ServingRouter -> ServingServer -> DecodeEngine as
                     `cli serve --http` wires them (slots 8, max_len
                     2048, page 16, prefill_chunk 256, attn_impl="auto",
                     ragged_impl=None): 8 streamed requests, prompts of
                     16..1024 tokens, 32 new tokens each, sent by
                     `testing.traffic.stream_generate` from threads that
                     never touch jax. All complete, the page pool
                     reconciles with no slot holding a page, and for two
                     prompts the engine's first token agrees with a
                     float32 `default_matmul_precision("highest")`
                     forward computed on the chip.
  multichip_dryrun   only with more than one device (and not under
                     --tiny: it has one size, which the driver's own
                     multichip check already runs on the CPU mesh):
                     `__graft_entry__.dryrun_multichip(n)`.

Exit code 0 and a last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}
mean every phase passed ON A TPU. No chip, a device that is not in the
peaks table (`core.hw.PEAKS`), or any failing phase ends the run
non-zero with the phase named; nothing here catches a phase's
exception. Times printed are smoke timings (wall clock split into
compile and run: the union of the compile recorder's rows in the phase),
not metrics; after the last phase, the recorder's seconds by phase and
function for the five functions that cost most.

`--tiny` runs the same phases at toy size on whatever backend is
present and prints `"chip": false`. It exists so tier-1 keeps this file
from rotting; it proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import importlib.metadata
import json
import sys
import threading

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import compilation_cache, data, models, optim, parallel
from paddle_tpu.core import dtypes
from paddle_tpu.core import mesh as mesh_lib
from paddle_tpu.core.devices import require_chip
from paddle_tpu.models import transformer as T
from paddle_tpu.native import build as native_build
from paddle_tpu.nn.module import ShapeSpec
from paddle_tpu.obs.trace import default_timeline
from paddle_tpu.ops import losses, pallas_util
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.http_edge import HttpEdge
from paddle_tpu.serve.router import ServingRouter
from paddle_tpu.serve.server import ServingServer
from paddle_tpu.testing.traffic import stream_generate
from paddle_tpu.train import Trainer, events as E

#: flash vs dense on one batch: |loss difference| and the relative
#: difference of the global gradient norm. bf16 operands round at 2^-9;
#: the kernels measured 0.2-0.4% against float32 on the v5e.
TRAIN_TOL = 2e-2
#: serve check, in log-probability units, against the float32 reference:
#: how far the engine's first token may sit below the reference argmax,
#: and how far its log-probability may be from the reference's. bf16
#: activations through 8 layers and a 32000-way head.
SERVE_TOL = 0.25


@dataclasses.dataclass(frozen=True)
class Sizes:
    image_model: object         # () -> nn.Layer
    image_hw: int
    image_batch: int
    classes: int
    train_steps: int            # after the compiling one
    lm: dict                    # TransformerConfig fields
    lm_seq: int
    lm_batch: int
    kinds_lm: dict              # the block of `train_layer_kinds`
    kinds_window: int
    hybrid: dict                # and what `train_hybrid` adds to it
    afmoe: dict                 # and what `train_afmoe` adds to it
    slots: int
    max_len: int
    prefill_chunk: int
    prompt_lens: tuple
    max_new: int


FULL = Sizes(
    image_model=lambda: models.resnet.resnet(50, num_classes=1000),
    image_hw=224, image_batch=256, classes=1000, train_steps=5,
    lm=dict(vocab=32000, dim=512, n_layers=8, n_heads=8),
    lm_seq=2048, lm_batch=2,
    kinds_lm=dict(vocab=32000, dim=512, n_heads=4, n_kv_heads=2,
                  head_size=128, moe_experts=16, moe_held=4, moe_k=2,
                  moe_dim=256), kinds_window=512,
    hybrid=dict(moe_shared_dim=256, gdn_key_heads=2, gdn_value_heads=4),
    afmoe=dict(moe_shared_dim=256, mlp_ratio=2),
    slots=8, max_len=2048, prefill_chunk=256,
    prompt_lens=(16, 48, 100, 200, 300, 500, 777, 1024), max_new=32)

TINY = Sizes(
    image_model=lambda: models.resnet.resnet_cifar(8, num_classes=10),
    image_hw=16, image_batch=8, classes=10, train_steps=1,
    lm=dict(vocab=97, dim=32, n_layers=1, n_heads=4),
    lm_seq=64, lm_batch=2,
    kinds_lm=dict(vocab=97, dim=32, n_heads=2, n_kv_heads=1, head_size=16,
                  moe_experts=4, moe_held=2, moe_k=2, moe_dim=16),
    kinds_window=16,
    hybrid=dict(moe_shared_dim=16, gdn_key_heads=1, gdn_value_heads=2,
                gdn_key_dim=16, gdn_value_dim=16),
    afmoe=dict(moe_shared_dim=16, mlp_ratio=2),
    slots=4, max_len=96, prefill_chunk=16,
    prompt_lens=(3, 5, 9, 14, 16, 20, 33, 48), max_new=6)


def run_phase(name: str, fn, sz: Sizes, devices) -> None:
    print(f"== phase {name}", flush=True)
    compilation_cache.reset_counters()
    traced0 = collections.Counter(pallas_util.traced())
    clock_ns = default_timeline().clock_ns
    t0_ns = clock_ns()
    try:
        facts = fn(sz, devices)
    except BaseException:
        print(f"PHASE FAILED: {name}", file=sys.stderr, flush=True)
        raise
    wall = (clock_ns() - t0_ns) / 1e9
    # jax tracing, lowering and compiling (or reading the compile
    # cache), from the recorder's rows: what lets a phase split its
    # wall time without a second, warm pass
    compile_s = compilation_cache.compile_seconds(t0_ns)
    traced = collections.Counter(pallas_util.traced()) - traced0
    print(json.dumps({
        "phase": name, "ok": True,
        "smoke_timing_s": {"wall": round(wall, 2),
                           "compile": round(compile_s, 2),
                           "run": round(wall - compile_s, 2)},
        "compile_cache": compilation_cache.counters(),
        # which implementation each dispatch site traced in this phase
        "traced": dict(traced), **facts}), flush=True)


def _compiled_fun(row: str) -> str:
    """`compile.lower:jit(step)` -> `step`: tracing names the function
    bare, lowering and the backend as jax wraps it."""
    fun = row.split(":", 1)[1]
    return fun[4:-1] if fun.startswith("jit(") and fun.endswith(")") else fun


def compile_table(top: int = 5) -> dict:
    """The `compile.*` lines of the timeline's summary for the `top`
    functions that cost most over their phases (trace, lowering,
    backend), beside the cache reads and the package's import."""
    table = {name: row for name, row in default_timeline().summary().items()
             if name.startswith(("compile.", "import."))}
    cost = collections.Counter()
    for name, row in table.items():
        if ":" in name:
            cost[_compiled_fun(name)] += row["total_s"]
    dearest = {fun for fun, _ in cost.most_common(top)}
    return {name: {k: round(v, 4) for k, v in row.items()}
            for name, row in table.items()
            if ":" not in name or _compiled_fun(name) in dearest}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- phases ---------------------------------------------------------------


def train_resnet50(sz: Sizes, devices) -> dict:
    model = sz.image_model()
    loss_fn = lambda lo, la: jnp.mean(losses.softmax_cross_entropy(lo, la))
    opt = optim.momentum(0.01, mu=0.9)
    trainer = Trainer(model, loss_fn, opt, seed=0)
    shape = (sz.image_batch, sz.image_hw, sz.image_hw, 3)
    state = trainer.init_state(ShapeSpec(shape))
    sharding = None
    if len(devices) > 1:
        # README "Multi-chip": the same step, state and batch placed
        # over every device (the way cli.cmd_train swaps in its ZeRO
        # step)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(data=len(devices)))
        state = parallel.shard_train_state(state, mesh)
        trainer._train_step = parallel.make_sharded_train_step(
            model, loss_fn, opt, mesh)
        sharding = parallel.batch_sharding(mesh)
    n_batches = 1 + sz.train_steps

    def reader():
        rng = np.random.default_rng(0)
        for _ in range(n_batches * sz.image_batch):
            yield (rng.random(shape[1:], dtype=np.float32),
                   int(rng.integers(sz.classes)))

    feeder = data.DataFeeder(sharding=sharding)
    batches = lambda: feeder(data.batch_reader(reader, sz.image_batch))
    costs = []

    def handler(ev):
        if isinstance(ev, E.EndIteration):
            costs.append(ev.cost)       # stays on device until the end

    state = trainer.train(state, batches, num_passes=1,
                          event_handler=handler)
    costs = [float(c) for c in costs]
    check(len(costs) == n_batches, f"took {len(costs)} steps, "
                                   f"wanted {n_batches}")
    check(all(np.isfinite(c) for c in costs), f"loss not finite: {costs}")
    check(int(state.step) == n_batches, f"state.step {int(state.step)}")
    live = _live_bytes(devices)     # None where the backend keeps none
    check(all(b is None or b > 0 for b in live.values()),
          f"a device holds no live bytes: {live}")
    return {"steps": n_batches, "loss": [round(c, 4) for c in costs],
            "sharded_over": len(devices), "bytes_in_use": live}


def _grad_norm(grads) -> float:
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                              for x in jax.tree.leaves(grads))))


def train_transformer(sz: Sizes, devices) -> dict:
    cfg = T.TransformerConfig(**sz.lm, attn_impl="auto")
    dense_cfg = dataclasses.replace(cfg, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (sz.lm_batch, sz.lm_seq + 1)), jnp.int32)

    def step(c):
        return jax.jit(jax.value_and_grad(lambda p, t: T.loss(p, c, t)))

    loss, grads = step(cfg)(params, toks)
    loss_d, grads_d = step(dense_cfg)(params, toks)
    loss, loss_d = float(loss), float(loss_d)
    gn, gn_d = _grad_norm(grads), _grad_norm(grads_d)
    check(np.isfinite(loss) and np.isfinite(gn), f"loss {loss} |g| {gn}")
    # random weights: the loss sits near ln(vocab)
    check(abs(loss - np.log(cfg.vocab)) < 1.0,
          f"loss {loss} far from ln(vocab) {np.log(cfg.vocab):.3f}")
    check(abs(loss - loss_d) <= TRAIN_TOL, f"loss auto {loss} vs dense "
                                           f"{loss_d}")
    check(abs(gn - gn_d) <= TRAIN_TOL * gn_d, f"|grad| auto {gn} vs dense "
                                              f"{gn_d}")
    return {"seq": sz.lm_seq, "batch": sz.lm_batch, "loss_auto": loss,
            "loss_dense": loss_d, "grad_norm_auto": gn,
            "grad_norm_dense": gn_d, "tolerance": TRAIN_TOL}


def _auto_against_dense(sz: Sizes, cfg) -> dict:
    """One `T.loss_and_aux` + grad step of `cfg` (`attn_impl="auto"`)
    and of its dense twin on one batch, checked against each other: the
    losses, the gradients' norms and the expert layer's counts as a
    training loop adds them to the timeline."""
    from paddle_tpu.parallel import moe

    dense_cfg = dataclasses.replace(cfg, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (sz.lm_batch, sz.lm_seq + 1)), jnp.int32)
    bias = None
    if cfg.moe_expert_bias:     # a step's bias: it chooses, it has no grad
        bias = 0.01 * jax.random.normal(jax.random.key(2),
                                        T.init_expert_bias(cfg).shape)

    def step(c):
        return jax.jit(jax.value_and_grad(
            lambda p, t: T.loss_and_aux(p, c, t, expert_bias=bias),
            has_aux=True))

    (loss, stats), grads = step(cfg)(params, toks)
    (loss_d, _), grads_d = step(dense_cfg)(params, toks)
    loss, loss_d = float(loss), float(loss_d)
    gn, gn_d = _grad_norm(grads), _grad_norm(grads_d)
    check(np.isfinite(loss) and np.isfinite(gn), f"loss {loss} |g| {gn}")
    check(abs(loss - loss_d) <= TRAIN_TOL, f"loss auto {loss} vs dense "
                                           f"{loss_d}")
    check(abs(gn - gn_d) <= TRAIN_TOL * gn_d, f"|grad| auto {gn} vs dense "
                                              f"{gn_d}")
    before = dict(default_timeline().counters())
    moe.count_dropless_stats(stats, positions=sz.lm_batch * sz.lm_seq)
    counters = {k: v - before.get(k, 0)
                for k, v in default_timeline().counters().items()
                if k.startswith("moe.")}
    check(0 < counters["moe.rows_held"] <= cfg.moe_k
          * counters["moe.positions"], f"rows held {counters}")
    if cfg.moe_expert_bias:
        check(counters["moe.route_rows"] == cfg.moe_k
              * counters["moe.positions"], f"rows routed {counters}")
    return {"seq": sz.lm_seq, "loss_auto": loss, "loss_dense": loss_d,
            "grad_norm_auto": gn, "grad_norm_dense": gn_d,
            "counters": counters}


def train_layer_kinds(sz: Sizes, devices) -> dict:
    """Attention kind by layer: three sliding-window layers and one
    full layer with YaRN in the one block body, over a dropless expert
    layer that holds a share of its experts; `T.loss_and_aux` and its
    gradient, `auto` against dense, and the layer's counts added to the
    timeline as a training loop would."""
    kinds = (("sliding", T.AttentionKind(window=sz.kinds_window)),
             ("full", T.AttentionKind(rope_scaling="yarn", rope_factor=16.0,
                                      rope_original=sz.lm_seq // 2)))
    cfg = T.TransformerConfig(
        **sz.kinds_lm, n_layers=4, norm="rms", bias=False, qk_norm=True,
        layer_types=("sliding",) * 3 + ("full",), attention_kinds=kinds,
        moe_router="dropless", moe_every=1, attn_impl="auto", remat=True)
    return {"window": sz.kinds_window, **_auto_against_dense(sz, cfg)}


def train_hybrid(sz: Sizes, devices) -> dict:
    """Token mixer by layer: three Gated DeltaNet layers and one full
    layer with a gated output and a rotary over a quarter of each
    head's lanes, over the dropless layer with a gated shared expert;
    `auto` against dense as `train_layer_kinds`."""
    kinds = (("linear", T.AttentionKind(mixer="gated_delta")),
             ("full", T.AttentionKind(
                 output_gate=True,
                 rotary_dim=sz.kinds_lm["head_size"] // 4)))
    cfg = T.TransformerConfig(
        **sz.kinds_lm, **sz.hybrid, n_layers=4, norm="rms", bias=False,
        qk_norm=True, layer_types=("linear",) * 3 + ("full",),
        attention_kinds=kinds, moe_router="dropless", moe_every=1,
        attn_impl="auto", remat=True)
    return _auto_against_dense(sz, cfg)


def train_afmoe(sz: Sizes, devices) -> dict:
    """Trinity's block (AfMoE): a leading dense gated-SiLU layer, then
    three sliding-window layers with rotary and a full layer without
    (NoPE), every attention gated, sandwich norms, over the dropless
    layer with a sigmoid router choosing by an expert bias and an
    ungated shared expert; `auto` against dense as `train_layer_kinds`."""
    kinds = (("sliding", T.AttentionKind(window=sz.kinds_window,
                                         output_gate=True)),
             ("full", T.AttentionKind(output_gate=True, rotary_dim=0)))
    cfg = T.TransformerConfig(
        **sz.kinds_lm, **sz.afmoe, n_layers=5, norm="rms", bias=False,
        qk_norm=True, rms_eps=1e-5, sandwich_norm=True,
        embed_scale=float(sz.kinds_lm["dim"]) ** 0.5,
        layer_types=("sliding",) * 4 + ("full",), attention_kinds=kinds,
        mlp="swiglu", moe_dense_layers=1, moe_router="dropless",
        moe_every=1, moe_shared_gate=False, moe_score="sigmoid",
        moe_route_scale=2.826, moe_expert_bias=True, attn_impl="auto",
        remat=True)
    return _auto_against_dense(sz, cfg)


def serve_http(sz: Sizes, devices) -> dict:
    cfg = T.TransformerConfig(**sz.lm, attn_impl="auto")
    params = T.init_params(jax.random.key(0), cfg)
    # the objects cli._serve_http builds, in its order, in this process
    engine = DecodeEngine(params, cfg, slots=sz.slots, max_len=sz.max_len,
                          page_size=16, prefill_chunk=sz.prefill_chunk,
                          ragged_impl=None, seed=0)
    server = ServingServer(engine, max_queue=64)
    router = ServingRouter([server])
    edge = HttpEdge(router, host="127.0.0.1", port=0).start()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in sz.prompt_lens]
    results = [None] * len(prompts)

    def client(i):      # sockets only: this thread never touches jax
        results[i] = stream_generate(edge.addr, prompts[i], sz.max_new,
                                     timeout_s=900.0)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(prompts))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(1000.0)
        check(not any(t.is_alive() for t in threads), "a client hung")
        edge.drain(reason="chip_smoke: requests served")
        check(edge.wait_drained(timeout_s=60.0), "edge did not drain")
    finally:
        edge.close()
    for n, r in zip(sz.prompt_lens, results):
        check(r is not None and r.status == 200
              and r.outcome == "completed", f"prompt {n}: {r}")
        check(len(r.tokens) == sz.max_new
              and all(0 <= t < cfg.vocab for t in r.tokens),
              f"prompt {n}: tokens {r.tokens}")
    pool = engine.pool
    pool.reconcile()
    held = sum(len(p) for p in pool.slot_pages)
    check(held == 0, f"slots still hold {held} pages")
    check(pool.pages_in_use == pool.evictable(),
          f"{pool.pages_in_use} pages in use, {pool.evictable()} of them "
          "cache-only")

    # first-token agreement with a float32 reference, for the shortest
    # prompt (one prefill chunk: the flash body) and the longest (later
    # chunks and every decode step read through the page table)
    picks = (0, len(prompts) - 1)
    toks, lps = engine.serve([prompts[i] for i in picks], max_new=1,
                             return_logprobs=True)
    ref_cfg = dataclasses.replace(cfg, attn_impl="dense")
    bf16 = dtypes.default_policy()
    dtypes.set_default_policy(dtypes.Policy())
    try:
        with jax.default_matmul_precision("highest"):
            ref = [jax.nn.log_softmax(jax.jit(
                lambda p, t: T.apply(p, ref_cfg, t))(
                    params, jnp.asarray(prompts[i])[None])[0, -1])
                   for i in picks]
    finally:
        dtypes.set_default_policy(bf16)
    first = []
    for i, tok, lp, ref_lp in zip(picks, toks, lps, ref):
        tok, lp = int(tok[0]), float(lp[0])
        check(tok == results[i].tokens[0],
              f"engine.serve token {tok} != streamed {results[i].tokens[0]}")
        below_argmax = float(jnp.max(ref_lp) - ref_lp[tok])
        lp_err = abs(lp - float(ref_lp[tok]))
        check(below_argmax <= SERVE_TOL and lp_err <= SERVE_TOL,
              f"prompt {sz.prompt_lens[i]}: token {tok} sits "
              f"{below_argmax:.4f} below the reference argmax, logprob "
              f"off by {lp_err:.4f} (tolerance {SERVE_TOL})")
        first.append({"prompt_len": sz.prompt_lens[i], "token": tok,
                      "is_ref_argmax": tok == int(jnp.argmax(ref_lp)),
                      "below_ref_argmax": round(below_argmax, 4),
                      "logprob_err": round(lp_err, 4)})
    return {"requests": len(prompts), "new_tokens": sz.max_new,
            "first_token_check": first, "tolerance": SERVE_TOL,
            "pool": pool.counters(), "edge": edge.counters(),
            "bytes_in_use": _live_bytes(devices)}


def multichip_dryrun(sz: Sizes, devices) -> dict:
    import __graft_entry__

    __graft_entry__.dryrun_multichip(len(devices))
    return {"devices": len(devices)}


def _live_bytes(devices) -> dict:
    # memory_stats() is None on backends that keep none (the CPU)
    return {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
            for d in devices}


# -- entry ----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on whatever backend is present; "
                         "prints \"chip\": false (tier-1's rot check)")
    args = ap.parse_args(argv)

    cache_dir = compilation_cache.enable()
    if args.tiny:
        sz, devices = TINY, jax.devices()
    else:
        sz = FULL
        devices, _ = require_chip()   # no TPU, or no peaks row: raises
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({
        "chip_smoke": "tiny" if args.tiny else "full", "device": dev,
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "native_libraries_loaded": native_build.ensured()}), flush=True)

    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    phases = [("train_resnet50", train_resnet50),
              ("train_transformer", train_transformer),
              ("train_layer_kinds", train_layer_kinds),
              ("train_hybrid", train_hybrid),
              ("train_afmoe", train_afmoe),
              ("serve_http", serve_http)]
    if len(devices) > 1 and not args.tiny:
        phases.append(("multichip_dryrun", multichip_dryrun))
    for name, fn in phases:
        run_phase(name, fn, sz, devices)
    print(json.dumps({"compile_seconds_by_phase_and_function":
                      compile_table()}), flush=True)

    result = {"ok": True, "device": dev}
    if args.tiny:
        result["chip"] = False
    print(json.dumps({"native_libraries_loaded": native_build.ensured()}),
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
