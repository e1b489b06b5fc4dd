"""Continuous-batching decode engine: slot-based serving over a
block-paged KV pool.

The reference's serving surface decodes one fixed batch to completion
(reference: api/PaddleAPI.h:1025 SequenceGenerator;
gserver/gradientmachines/RecurrentGradientMachine.cpp:964 generates a
whole batch in lockstep). Real serving traffic is a STREAM: requests
arrive and finish at different times, and a lockstep batch leaves the
chip idle on every finished row until the whole batch drains. This
engine keeps a fixed pool of S decode slots — static shapes, so the
jitted step never recompiles — and the host loop admits a queued
request into a slot the moment one finishes (continuous batching).

TPU-first choices:
- ONE jitted `decode_step` advances every active slot a token. The KV
  state is a BLOCK-PAGED pool ("Ragged Paged Attention", PAPERS.md):
  per layer one `[num_pages, page_size, Hkv, Dh]` arena plus a static
  `[S, max_pages_per_slot]` page table; rows scatter-write this step's
  K/V through the table at their own position (slots are NOT in
  lockstep — that is the point) and gather their mapped pages for the
  masked read (ops.paged_attention). Pages are allocated/freed on the
  HOST (serve.paged.PagePool) at admit / page-boundary / retire, so
  pool memory follows actual sequence lengths instead of
  slots x max_len — the capacity win `ServingServer` admits against.
  Sliding-window configs instead hold [S, window] RING pools (per-row
  slot = pos mod window — O(window) memory, no paging needed).
- Copy-free SHARED-PREFIX reuse: a prefix cache keyed by chained
  prompt-block hash maps common leading blocks (system prompts) to
  refcounted read-only pages; a hit maps them into the new slot's
  table and prefill starts at the first divergent block (the
  copy-on-write split — shared pages are never written, because
  decode writes land past the prompt).
- Prefill runs in CHUNKS through one jitted body compiled per
  (chunk_width, first?, last?): a prefix hit skips straight to its
  first private position, and `prefill_chunk=N` slices long prompts
  into fixed N-token chunks the host interleaves with decode steps —
  no per-prompt-length compile explosion, no head-of-line stall while
  a long prompt prefills.
- Inactive slots still compute (static shapes) but their writes are
  dropped (scatter mode="drop" via sentinel page ids / out-of-range
  positions) and their reads masked.

Consistency contract, tested in tests/test_serve_engine.py +
tests/test_paged_pool.py: a GREEDY (default select_fn) request served
through the engine yields EXACTLY the tokens of
`transformer.generate()` on the same prompt — regardless of which
other requests share the pool, when it was admitted, whether its
prefix came from the cache, and whether its prefill was chunked.
(One boundary, inherent to lossy caches: kv_cache_dtype="int8" under
a prefix hit or chunked prefill reads QUANTIZED prefix K/V where the
one-shot prefill read exact values — same class of boundary as int8
decode itself.) SAMPLED serving — per request via
`serve(sampling=[...])` (per-slot temperature/top_k/top_p arrays
through one compiled step) or pool-wide via select_fn — runs ONE rng
stream PER SLOT, seeded at admission from the request's own identity:
with an explicit `"seed"` a request's draws are fully deterministic
and co-tenancy/admission-order INVARIANT (tested); the default
identity is this engine's admission counter (reproducible per engine
seed + admission order). Tokens are the engine's own stream (not
`transformer.sample()`'s); temperature 0 (the default) keeps the
exact greedy contract beside sampled co-tenants.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.dtypes import default_policy
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import sampling as sampling_ops
from paddle_tpu.serve.paged import (PagePool, PoolExhaustedError,
                                    blocks_for)
from paddle_tpu.serve.policy import SchedulerPolicy
from paddle_tpu.serve.speculative import NGramProposer


@lru_cache(maxsize=8192)
def _staged(val, dtype):
    """Committed device scalar for a host value, cached by value.

    The host-side bookkeeping around the jitted bodies (page-map
    updates, slot retires, per-chunk prefill scalars) used to hand
    eager ops bare Python scalars — each one an IMPLICIT host->device
    transfer, re-staged every step (`analysis.guards`' transfer guard
    flags exactly this). Explicit `device_put` staging cached by value
    makes the steady-state loop transfer-free and reuses the committed
    buffer across steps: slots, block indices, page ids, bucket
    lengths and sampler params all draw from small repeating sets."""
    return jax.device_put(np.asarray(val, dtype))


def _staged_once(val, dtype):
    """Explicit staging WITHOUT the cache — for per-request-unique
    values (request seeds, admission-counter tags) that would only
    pollute the `_staged` LRU and evict its genuinely hot entries."""
    return jax.device_put(np.asarray(val, dtype))


class EngineState(NamedTuple):
    """Device-resident pool state. caches: per layer (k_buf, v_buf) —
    paged ARENAS [num_pages, page_size, Hkv, Dh] addressed through
    `page_table` for full-attention configs, [S, window, ...] rings
    under attn_window, (s8 data, scale) pairs under
    kv_cache_dtype="int8". page_table [S, max_pages_per_slot] int32
    maps each slot's logical blocks to physical pages (sentinel =
    num_pages on unmapped entries, so writes there drop). pos[s] = the
    next absolute position row s writes; out-of-range sentinels on
    inactive rows make their scatter writes drop. rng is a PER-SLOT
    key vector: each request's stream is seeded at its own admission
    and advances one split per step, so a sampled request's draws
    depend only on its seed and its own step index — co-tenants
    cannot perturb them."""

    caches: tuple
    page_table: jnp.ndarray  # [S, max_pages] int32 (paged mode)
    pos: jnp.ndarray        # [S] int32
    active: jnp.ndarray     # [S] bool
    last_tok: jnp.ndarray   # [S] int32
    rng: jnp.ndarray        # [S] keys — ONE stream per slot
    # per-REQUEST sampler params, set at admission (temp 0 = greedy)
    temp: jnp.ndarray       # [S] f32
    top_k: jnp.ndarray      # [S] int32
    top_p: jnp.ndarray      # [S] f32
    # log p(last_tok | its prefix) under the FULL softmax (the
    # rescoring convention, = transformer.score()), captured when the
    # token was selected
    last_lp: jnp.ndarray    # [S] f32


@dataclass
class PoolStats:
    """Host-side accounting for one serve() run (PARITY §5
    observability): steps = jitted decode_step invocations (each a
    fixed [S]-wide batch of device work); tokens = emitted real
    tokens; utilization = tokens / (steps * slots) — the fraction of
    issued row-steps that produced a kept token (lockstep batching's
    idle finished rows show up here directly).

    The outcome counters are the serving reliability layer's
    per-request ledger (serve.server, docs/RELIABILITY.md "Serving
    fault model"): every submitted request lands in EXACTLY ONE of
    completed/expired/shed/failed; `admitted` counts requests that won
    a slot (prefilled at least once) and `retried` counts requeue
    events (not requests). The plain engine.serve() loop — which never
    sheds or expires, but DOES requeue pool-exhaustion preemption
    victims — fills admitted/completed/retried so the ledger
    reconciles on either path.

    The page-pool block (docs/SERVING.md "Paged KV cache"):
    pages_in_use/pages_free are end-of-run gauges (peak_pages_in_use
    the high-water mark), prefix_hits/prefix_misses count admissions
    that did/didn't reuse cached prefix blocks, prefill_chunks counts
    jitted prefill-chunk invocations (1 per admission unless
    `prefill_chunk` slices longer prompts)."""

    steps: int = 0
    tokens: int = 0
    prefills: int = 0
    requests: int = 0
    # per-request outcome ledger (serve.server's counters)
    admitted: int = 0
    completed: int = 0
    expired: int = 0
    shed: int = 0
    failed: int = 0
    retried: int = 0
    # paged KV pool observability
    pages_in_use: int = 0
    pages_free: int = 0
    peak_pages_in_use: int = 0
    prefix_hits: int = 0
    prefix_misses: int = 0
    prefill_chunks: int = 0
    # speculative decoding (serve(speculative=True) verify rounds):
    # draft_proposed/draft_accepted count DRAFT tokens (the carry
    # token of each round is not a draft — a 0-draft round is a plain
    # decode step), spec_reserved/spec_rolled_back are the pool's
    # page-granular reserve/rollback ledger
    spec_rounds: int = 0
    draft_proposed: int = 0
    draft_accepted: int = 0
    spec_reserved: int = 0
    spec_rolled_back: int = 0

    def utilization(self, slots: int) -> float:
        return self.tokens / max(self.steps * slots, 1)

    def acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens — the speculative health
        gauge (mean bonus tokens per round = rate x mean draft len;
        a low rate means the proposer's traffic match is poor and the
        verify rounds are mostly paying plain-step work)."""
        return self.draft_accepted / max(self.draft_proposed, 1)


def pad_to_bucket(prompt, buckets):
    """(padded_prompt, true_len) for the smallest bucket >= the real
    length — THE bucket-padding convention shared by engine.serve()
    and the reliability server (serve.server), so prefill compile
    keying cannot drift between the two schedulers. Raises ValueError
    when no bucket fits; buckets=None passes through unpadded."""
    import numpy as np

    t0 = int(prompt.shape[-1])
    if buckets is None:
        return prompt, t0
    fits = [b for b in sorted(buckets) if b >= t0]
    if not fits:
        raise ValueError(
            f"prompt len {t0} exceeds largest bucket {max(buckets)}")
    return np.pad(np.asarray(prompt), (0, fits[0] - t0)), t0


@dataclass
class PrefillTicket:
    """Host-side handle for one in-progress (possibly chunked)
    prefill: `prefill_begin` maps the slot's pages and returns one,
    each `prefill_advance` runs one jitted chunk. The reliability
    server keeps tickets per slot so long prompts prefill interleaved
    with live decodes instead of stalling them."""

    slot: int
    prompt: np.ndarray          # bucket-padded prompt, int32
    true_len: int
    chunk: Optional[int]        # None = the rest in one chunk
    next_start: int
    temp: float
    top_k: int
    top_p: float
    req_tag: int
    req_seed: int
    windowed: bool = False      # ring pool: one-shot legacy prefill


@dataclass
class DecodeSeed:
    """Host-side snapshot of one slot's per-row decode state, taken by
    `pause_slot` at the prefill-complete seam and replayed by
    `resume_slot` on the migration destination. Everything the jitted
    step reads per row EXCEPT the KV blocks (those ride the page
    export): carrying last_tok/last_lp means the destination's first
    decode step emits exactly the token the source's would have — the
    bit-exact handoff contract — and carrying the raw rng key data
    keeps a sampled request's stream identical across the move."""

    pos: int
    last_tok: int
    last_lp: float
    temp: float
    top_k: int
    top_p: float
    rng_key_data: np.ndarray     # raw per-slot key bits (wrap on import)


class DecodeEngine:
    """The EXECUTOR half of the serving stack (the policy half is
    `serve.policy.SchedulerPolicy` — see its docstring for the split):
    make once per (params, cfg, pool geometry); drive with
    `init_state` / `prefill` (or `prefill_begin`/`prefill_advance`) /
    `decode_step` / `ensure_decode_page` / `release_slot` — THE
    executor surface every scheduler (the batteries-included `serve()`
    host loop here, `ServingServer`, the fleet router's replicas)
    consumes — or just call `serve()`. Scheduling decisions inside
    `serve()` (admission order, preemption victim, prefill interleave)
    route through the `policy`; the jitted bodies and pool writes do
    not."""

    def __init__(self, params, cfg: T.TransformerConfig, *, slots: int,
                 max_len: int, eos_id: Optional[int] = None,
                 select_fn=None, seed: int = 0,
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefix_cache_blocks: int = 512,
                 policy: Optional[SchedulerPolicy] = None,
                 ragged_impl: Optional[str] = None):
        """Pool geometry: full-attention configs hold a block-paged KV
        pool of `num_pages` pages of `page_size` positions per layer
        (default num_pages = slots * ceil(max_len / page_size) — the
        dense layout's capacity exactly, so the pool can never refuse
        what the dense pool admitted; pass fewer pages to
        OVER-SUBSCRIBE slots against actual lengths and let
        ServingServer admit on headroom). `prefill_chunk` slices
        prompt prefill into fixed-width chunks the serve loops
        interleave with decode steps; `prefix_cache` enables
        copy-free shared-prefix reuse. Sliding-window configs keep
        their [S, window] ring pools (the paging knobs are inert).

        Sampling, two ways: per REQUEST via serve(sampling=[...])/
        prefill(sampling={...}) — temperature/top_k/top_p ride
        per-slot arrays through ONE compiled step (temp 0 = greedy,
        the default) — or a pool-wide select_fn(logits [B, V], rng)
        -> [B] override applied to every request (mutually exclusive
        with per-request sampling). Draws are reproducible per (seed,
        admission order).

        `ragged_impl` pins the paged read path every jitted body
        traces: None (default) lets ops.ragged_paged_attention
        auto-select (fused kernel on TPU where the walk fits VMEM —
        float and int8 arenas alike — jnp gather elsewhere);
        "pallas"/"jnp" force one side everywhere, which is how the
        serving-parity suites drive the kernel in interpret mode on
        CPU. Baked into every traced program, so it is an artifact
        manifest field."""
        if ragged_impl not in (None, "jnp", "pallas"):
            raise ValueError(
                f"ragged_impl must be None|jnp|pallas, got "
                f"{ragged_impl!r}")
        T.require_decodable(cfg)
        if cfg.kv_cache_dtype not in ("compute", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be compute|int8, got "
                f"{cfg.kv_cache_dtype!r}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        # MoE configs ride the shared _block_parts body like every
        # other decode path. One semantic boundary, inherent to
        # capacity-based routing: expert capacity is a function of the
        # step's token count (= slots here, batch in generate()), so a
        # pathologically imbalanced pool step can drop a token to
        # capacity where a solo decode would not — same boundary the
        # reference's capacity semantics impose on any batch. (A
        # chunked or prefix-hit prefill changes the per-call token
        # count the same way.)
        # weight-only int8 params (serve.quant) use the SAME split as
        # generate(): prefill reads the hoisted dequant (one-shot,
        # compute-bound), the per-token step re-traces the dequant
        # in-body keyed on the loop-varying tokens so the decode
        # streams s8 weights. Identity (zero cost) for fp params.
        self.params, self._step_params = T._int8_step_params(params)
        self.cfg = cfg
        self.policy = policy if policy is not None else SchedulerPolicy()
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.select_fn = select_fn
        self.seed = seed
        self.ragged_impl = ragged_impl
        self.paged = cfg.attn_window is None
        self.page_size = page_size
        self.max_pages_per_slot = -(-max_len // page_size)
        self.num_pages = (num_pages if num_pages is not None
                          else slots * self.max_pages_per_slot)
        if self.num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got "
                             f"{self.num_pages}")
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        self.prefix_cache_blocks = prefix_cache_blocks
        # the retired-slot page-table row, staged ONCE (an eager
        # jnp.full per retire would re-transfer the sentinel row)
        self._empty_row = jax.device_put(np.full(
            (self.max_pages_per_slot,), self.num_pages, np.int32))
        self.pool: Optional[PagePool] = None  # built by init_state()
        self._admissions = 0   # default per-request stream identity
        self._prefill_jit = jax.jit(self._prefill_impl,
                                    static_argnames=("t0",))
        self._chunk_jit = jax.jit(
            self._chunk_impl,
            static_argnames=("chunk_w", "from_zero", "final"))
        self._step_jit = jax.jit(self._step_impl)
        self._spec_jit = jax.jit(self._spec_step_impl)
        # jitted micro-updates for the HOST-side bookkeeping (page
        # map, slot retire): eager .at[] ops hand XLA implicit scalar
        # transfers per call (their negative-index fixup runs with
        # python constants); a jitted body compiles once (warmed in
        # init_state) and takes only staged device scalars
        self._pagemap_jit = jax.jit(
            lambda tbl, slot, blk, page: tbl.at[slot, blk].set(page))
        self._rowset_jit = jax.jit(
            lambda tbl, slot, row: tbl.at[slot].set(row))
        self._retire_jit = jax.jit(
            lambda active, pos, slot, fill: (
                active.at[slot].set(False), pos.at[slot].set(fill)))
        # KV-block migration bodies (disaggregated prefill/decode).
        # Static [max_pages_per_slot] page-id vectors keep each body at
        # ONE compile regardless of how many blocks a request maps:
        # export gathers with mode="clip" (host slices the real count),
        # import scatters with mode="drop" (sentinel ids — padding and
        # shared blocks alike — vanish). Compiled lazily at the first
        # migration; every later transfer reuses them, which is what
        # the RecompileGuard chaos test pins down.
        self._pause_jit = jax.jit(self._pause_impl)
        self._kvread_jit = jax.jit(self._kvread_impl)
        self._kvwrite_jit = jax.jit(self._kvwrite_impl)
        self._resume_jit = jax.jit(self._resume_impl)
        # AOT artifact surface (serve.artifact): `bind_artifact`
        # installs pre-exported programs that replace the jitted
        # bodies call-for-call — a fleet restart then skips
        # retrace+compile entirely. None = the pure jit path. Any
        # runtime failure of a bound program falls back to the jit
        # body for that member FOREVER (the member is dropped), bumps
        # `artifact_fallbacks` and notifies `_artifact_hook` — never
        # a wrong answer, never a crash.
        self._artifact: Optional[dict] = None
        self.artifact_loads = 0
        self.artifact_fallbacks = 0
        self._artifact_hook = None

    def ping(self) -> None:
        """The health-probe surface: a cheap host-side liveness touch
        (no device work, no state). The real engine always answers;
        a dead-replica proxy (testing.faults) raises here exactly
        like a lost device would on its first RPC — which is what
        makes the fleet router's health checks honest."""
        return None

    # -- AOT artifact surface (serve.artifact) ----------------------------

    def state_spec(self) -> EngineState:
        """ShapeDtypeStruct template of init_state()'s pytree, built
        from config arithmetic alone — no tracing, no allocation.
        serve.artifact uses it to flatten/unflatten EngineState across
        the exported flat-argument programs. Paged engines only (the
        artifact surface; ring configs keep the plain jit path)."""
        if not self.paged:
            raise ValueError(
                "state_spec/engine artifacts support paged engines "
                "only (attn_window configs keep the jit path)")
        cfg, s = self.cfg, self.slots
        policy = default_policy()
        shape = (self.num_pages, self.page_size, cfg.kv_heads,
                 cfg.head_dim)
        if cfg.kv_cache_dtype == "int8":
            buf = (jax.ShapeDtypeStruct(shape, jnp.int8),
                   jax.ShapeDtypeStruct(shape[:-1], jnp.float32))
        else:
            buf = jax.ShapeDtypeStruct(shape, policy.compute_dtype)
        sds = jax.ShapeDtypeStruct
        return EngineState(
            caches=tuple((buf, buf) for _ in self.params["blocks"]),
            page_table=sds((s, self.max_pages_per_slot), jnp.int32),
            pos=sds((s,), jnp.int32),
            active=sds((s,), jnp.bool_),
            last_tok=sds((s,), jnp.int32),
            rng=sds((s,), jax.random.key(0).dtype),
            temp=sds((s,), jnp.float32),
            top_k=sds((s,), jnp.int32),
            top_p=sds((s,), jnp.float32),
            last_lp=sds((s,), jnp.float32))

    def artifact_manifest(self) -> dict:
        """Everything an artifact's correctness depends on, as JSON
        primitives: the exported programs BAKE IN the weights, the
        config, this engine's rng seed and the pool geometry, so a
        loader must refuse a bundle whose manifest differs in ANY
        field (serve.artifact.load_engine_artifact compares every
        entry and falls back to the jit path on mismatch)."""
        import hashlib

        if self.select_fn is not None:
            raise ValueError(
                "engine artifacts need select_fn=None: a pool-wide "
                "select_fn is a baked-in Python closure no manifest "
                "can verify (per-request sampling rides traced "
                "arrays and is fully supported)")
        if not self.paged:
            raise ValueError(
                "engine artifacts support paged engines only")
        h = hashlib.sha256()
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.params)[0]:
            arr = np.asarray(jax.device_get(leaf))
            h.update(str(path).encode())
            h.update(str(arr.shape).encode())
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
        policy = default_policy()
        return {
            "kind": "engine",
            "jax_version": jax.__version__,
            "x64": bool(jax.config.jax_enable_x64),
            "compute_dtype": str(policy.compute_dtype),
            "kv_cache_dtype": self.cfg.kv_cache_dtype,
            "cfg_hash": hashlib.sha256(
                repr(self.cfg).encode()).hexdigest()[:16],
            "params_hash": h.hexdigest(),
            "slots": int(self.slots),
            "max_len": int(self.max_len),
            "page_size": int(self.page_size),
            "num_pages": int(self.num_pages),
            "max_pages_per_slot": int(self.max_pages_per_slot),
            "eos_id": None if self.eos_id is None else int(self.eos_id),
            "seed": int(self.seed),
            "spec_draft_max": int(self.policy.spec_draft_max),
            # the traced read path: a bundle exported with the kernel
            # must not be trusted by a jnp-pinned engine (or vice
            # versa) — same program-identity rule as the dtypes
            "ragged_impl": self.ragged_impl or "auto",
        }

    def bind_artifact(self, programs: dict, manifest: dict) -> None:
        """Install loaded artifact programs (serve.artifact builds
        the dict — ALREADY manifest-verified against this engine).
        Subsequent decode/spec/prefill-chunk/micro-setter calls route
        through them instead of the jit bodies."""
        self._artifact = dict(programs)
        self._artifact_manifest = dict(manifest)
        self.artifact_loads += 1

    def artifact_fallback(self, member: str, error: str) -> None:
        """Record one artifact->jit fallback (load-time mismatch or a
        bound program failing at run time): bump the counter the
        server/router export and notify the observability hook
        (ServingServer points it at its flight recorder)."""
        self.artifact_fallbacks += 1
        if self._artifact_hook is not None:
            self._artifact_hook(member, error)

    def _art(self, name: str):
        art = self._artifact
        return None if art is None else art.get(name)

    def _art_drop(self, name: str, exc: Exception) -> None:
        # a program that failed once would fail every call — drop the
        # member so the steady loop doesn't pay an exception per step
        if self._artifact is not None:
            self._artifact.pop(name, None)
        self.artifact_fallback(name, repr(exc))

    # the host-bookkeeping micro-bodies route through the same
    # dispatch: tiny programs, but they are exactly what init_state
    # warms — an artifact boot should compile NOTHING

    def _set_pagemap(self, tbl, slot, blk, page):
        fn = self._art("pagemap")
        if fn is not None:
            try:
                return fn(tbl, slot, blk, page)
            except Exception as e:
                self._art_drop("pagemap", e)
        return self._pagemap_jit(tbl, slot, blk, page)

    def _set_row(self, tbl, slot, row):
        fn = self._art("rowset")
        if fn is not None:
            try:
                return fn(tbl, slot, row)
            except Exception as e:
                self._art_drop("rowset", e)
        return self._rowset_jit(tbl, slot, row)

    def _retire(self, active, pos, slot, fill):
        fn = self._art("retire")
        if fn is not None:
            try:
                return fn(active, pos, slot, fill)
            except Exception as e:
                self._art_drop("retire", e)
        return self._retire_jit(active, pos, slot, fill)

    # -- state ------------------------------------------------------------

    def init_state(self) -> EngineState:
        # every buffer is built host-side and staged EXPLICITLY
        # (device_put): pool construction is the one sanctioned bulk
        # transfer, so `serve --transfer-guard` holds end-to-end, and
        # initialization compiles no throwaway fill programs
        cfg, s = self.cfg, self.slots
        policy = default_policy()
        hkv, dh = cfg.kv_heads, cfg.head_dim
        dput = jax.device_put
        if self.paged:
            # block-paged arenas: one [P, page, Hkv, Dh] pool per
            # layer, addressed through the per-slot page table
            L = self.max_len
            shape = (self.num_pages, self.page_size, hkv, dh)

            def buf():
                if cfg.kv_cache_dtype == "int8":
                    return (dput(np.zeros(shape, np.int8)),
                            dput(np.full(shape[:-1], 1e-8 / 127.0,
                                         np.float32)))
                return dput(np.zeros(shape, policy.compute_dtype))

            page_table = dput(np.full((s, self.max_pages_per_slot),
                                      self.num_pages, np.int32))
            self.pool = PagePool(
                num_pages=self.num_pages, page_size=self.page_size,
                slots=s, max_pages_per_slot=self.max_pages_per_slot,
                prefix_cache=self.prefix_cache,
                prefix_cache_blocks=self.prefix_cache_blocks)
        else:
            # sliding-window configs hold a RING pool: window slots
            # per row (generate()'s rolling cache, per-row)
            L = cfg.attn_window

            def buf():
                if cfg.kv_cache_dtype == "int8":
                    # (s8 data, per-vector scale) — the SAME
                    # quantized-pair format _cached_attention streams
                    # in generate(); constructed directly (zeros
                    # quantize to data=0 with the eps-floor scale)
                    return (dput(np.zeros((s, L, hkv, dh), np.int8)),
                            dput(np.full((s, L, hkv), 1e-8 / 127.0,
                                         np.float32)))
                return dput(np.zeros((s, L, hkv, dh),
                                     policy.compute_dtype))

            page_table = dput(np.zeros((s, 1), np.int32))  # inert
            self.pool = None

        caches = tuple((buf(), buf()) for _ in self.params["blocks"])
        # default stream identities restart with the pool: two serve()
        # calls on one engine replay identically (the counter is host
        # state, NOT part of EngineState — a restored state needs its
        # engine's counter AND page pool to continue; explicit
        # per-request seeds sidestep the former entirely)
        self._admissions = 0
        active = dput(np.zeros((s,), bool))
        pos = dput(np.full((s,), self.max_len, np.int32))
        # pre-warm the host-bookkeeping micro-jits with value-no-op
        # calls on the fresh state, so a first page-boundary crossing
        # or retire mid-serve never compiles inside the steady loop
        z = _staged(0, np.int32)
        self._retire(active, pos, z,
                     _staged(self.max_len, np.int32))
        if self.paged:
            self._set_pagemap(page_table, z, z,
                              _staged(self.num_pages, np.int32))
            self._set_row(page_table, z, self._empty_row)
        return EngineState(
            caches=caches,
            page_table=page_table,
            pos=pos,                        # sentinel: writes drop
            active=active,
            last_tok=dput(np.zeros((s,), np.int32)),
            rng=jax.random.split(
                jax.random.key(dput(np.int64(self.seed))),
                self.slots),
            temp=dput(np.zeros((s,), np.float32)),
            top_k=dput(np.full((s,), cfg.vocab, np.int32)),
            top_p=dput(np.ones((s,), np.float32)),
            last_lp=dput(np.zeros((s,), np.float32)))

    # -- shared first-token selection --------------------------------------

    def _select_first(self, params, x_last, temp, top_k, top_p,
                      req_tag, req_seed):
        """The request's first generated token + its full-softmax
        logprob, from the last real prompt position's activation —
        one definition for the ring prefill and every paged chunk."""
        # this request's OWN stream, seeded at admission: draws depend
        # only on (engine seed, request seed) and step index
        req_key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(self.seed), req_tag), req_seed)
        req_key, sub = jax.random.split(req_key)
        logits = T._head(params, x_last[None])
        if self.select_fn is not None:
            first = self.select_fn(logits, sub)[0]
        else:
            first = T.per_row_sample(logits, temp[None], top_k[None],
                                     top_p[None], sub)[0]
        first_lp = jax.nn.log_softmax(
            T.at_least_f32(logits), axis=-1)[0, first]
        return first, first_lp, req_key

    # -- ring (sliding-window) prefill -------------------------------------

    def _prefill_impl(self, state: EngineState, slot, prompt, true_len,
                      temp, top_k, top_p, req_tag, req_seed, t0: int):
        """One-shot ring-pool prefill (attn_window configs): prompt
        [t0] int32 (real tokens in [:true_len], rest padding) -> state
        with the slot's ring holding the last min(true_len, W) real
        positions, pos=true_len, active, last_tok = the request's
        first token. true_len is TRACED, so one compile per padded
        bucket length serves every real length."""
        cfg, params = self.cfg, self.params
        policy = default_policy()
        toks = prompt[None, :]                       # [1, t0]
        x = jnp.take(params["embed"]["table"], toks, axis=0)
        x = x.astype(policy.compute_dtype)
        pos = jnp.arange(t0, dtype=jnp.int32)[None, :]
        # pad keys masked out exactly like generate(prompt_lens=...)
        attn = lambda q, k, v: T._attention(
            cfg, q, k, v, causal=True, key_lens=true_len[None])
        # bucket-pad tokens must not claim MoE expert capacity either —
        # the same key_ok mask generate()/loss()/score() pass through
        # to the router (transformer.py _forward token_mask)
        tok_mask = (jnp.arange(t0, dtype=jnp.int32) < true_len)[None, :]
        z = jnp.int32(0)

        def write_slot(buf, new):
            """Write this request's [1, W, ...] K/V rows into its
            slot — quantizing first when the pool holds (s8, scale)
            pairs."""
            if isinstance(buf, tuple):
                d, sc = buf
                nd, nsc = T._kv_quantize(new)
                d = jax.lax.dynamic_update_slice(
                    d, nd, (slot, z, z, z))
                sc = jax.lax.dynamic_update_slice(
                    sc, nsc.astype(sc.dtype), (slot, z, z))
                return (d, sc)
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (slot, z, z, z))

        # ring pool: keep only the last min(true_len, W) REAL
        # positions, each in its slot p mod W — ring slot s holds
        # p(s) = (true_len-1) - ((true_len-1 - s) mod W); negative
        # p(s) (short prompts) gathers a clipped row the decode
        # mask keeps invalid until overwritten. Padded-bucket rows
        # never enter the ring: p(s) indexes real positions only.
        w_ = cfg.attn_window
        p_slot = (true_len - 1) - jnp.mod(
            (true_len - 1) - jnp.arange(w_, dtype=jnp.int32), w_)
        ring_idx = jnp.clip(p_slot, 0, t0 - 1)
        ring = lambda kv: jnp.take(kv, ring_idx, axis=1)

        caches = []
        for p, (k_buf, v_buf) in zip(params["blocks"], state.caches):
            x, k, v, _ = T._block_parts(cfg, p, x, pos, attn, tok_mask)
            caches.append((write_slot(k_buf, ring(k)),
                           write_slot(v_buf, ring(v))))
        # first token reads the LAST REAL position's logits
        x_last = jax.lax.dynamic_index_in_dim(
            x[0], true_len - 1, axis=0, keepdims=False)
        first, first_lp, req_key = self._select_first(
            params, x_last, temp, top_k, top_p, req_tag, req_seed)
        return EngineState(
            caches=tuple(caches),
            page_table=state.page_table,
            pos=state.pos.at[slot].set(true_len),
            active=state.active.at[slot].set(True),
            last_tok=state.last_tok.at[slot].set(
                first.astype(jnp.int32)),
            rng=state.rng.at[slot].set(req_key),
            temp=state.temp.at[slot].set(temp),
            top_k=state.top_k.at[slot].set(top_k),
            top_p=state.top_p.at[slot].set(top_p),
            last_lp=state.last_lp.at[slot].set(
                first_lp.astype(jnp.float32)))

    # -- paged prefill (chunked, prefix-aware) -----------------------------

    def _chunk_impl(self, state: EngineState, slot, toks, start,
                    true_len, temp, top_k, top_p, req_tag, req_seed,
                    *, chunk_w: int, from_zero: bool, final: bool):
        """One prefill CHUNK for one slot: toks [chunk_w] at absolute
        positions start..start+chunk_w-1. Compiles per (chunk_w,
        from_zero, final) — a fixed `prefill_chunk` gives O(1)
        compiles across all prompt lengths. from_zero chunks (start ==
        0) need no cache reads and run THE SAME within-chunk
        `_attention` call the one-shot prefill always ran (so the
        default single-chunk path is numerically identical to it);
        later chunks attend through the page table over everything
        cached so far — shared-prefix pages included, which is what
        makes a prefix hit copy-free. `final` chunks (the one holding
        position true_len-1) also select the request's first token and
        activate the slot; padded tail positions (>= true_len) write
        garbage the decode mask never reads (each cell is overwritten
        the step before it first becomes readable)."""
        cfg, params = self.cfg, self.params
        policy = default_policy()
        x = jnp.take(params["embed"]["table"], toks[None, :], axis=0)
        x = x.astype(policy.compute_dtype)
        ap = start + jnp.arange(
            chunk_w, dtype=jnp.int32)            # absolute positions
        pos = ap[None, :]
        # pad/garbage positions must not claim MoE expert capacity
        tok_mask = (ap < true_len)[None, :]
        pages_row = state.page_table[slot]
        new_caches = []

        if from_zero:
            # within-chunk causal attention, masked exactly like
            # generate(prompt_lens=...) — no cache read needed
            attn_fn = lambda q, k, v: T._attention(
                cfg, q, k, v, causal=True, key_lens=true_len[None])

        for p, (k_buf, v_buf) in zip(params["blocks"], state.caches):
            if from_zero:
                x, k, v, _ = T._block_parts(cfg, p, x, pos, attn_fn,
                                            tok_mask)
                pg, off = pa.page_addresses(pages_row, ap,
                                            page_size=self.page_size)
                new_caches.append((pa.write_kv(k_buf, k[0], pg, off),
                                   pa.write_kv(v_buf, v[0], pg, off)))
            else:
                def attn_fn(q, k, v, k_buf=k_buf, v_buf=v_buf):
                    out, k2, v2 = pa.paged_chunk_attention(
                        q, k, v, k_buf, v_buf, pages_row, start,
                        page_size=self.page_size, max_len=self.max_len,
                        impl=self.ragged_impl)
                    new_caches.append((k2, v2))
                    return out

                x, _, _, _ = T._block_parts(cfg, p, x, pos, attn_fn,
                                            tok_mask)
        state = state._replace(caches=tuple(new_caches))
        if not final:
            return state
        # first token reads the LAST REAL position's logits
        x_last = jax.lax.dynamic_index_in_dim(
            x[0], true_len - 1 - start, axis=0, keepdims=False)
        first, first_lp, req_key = self._select_first(
            params, x_last, temp, top_k, top_p, req_tag, req_seed)
        return state._replace(
            pos=state.pos.at[slot].set(true_len),
            active=state.active.at[slot].set(True),
            last_tok=state.last_tok.at[slot].set(
                first.astype(jnp.int32)),
            rng=state.rng.at[slot].set(req_key),
            temp=state.temp.at[slot].set(temp),
            top_k=state.top_k.at[slot].set(top_k),
            top_p=state.top_p.at[slot].set(top_p),
            last_lp=state.last_lp.at[slot].set(
                first_lp.astype(jnp.float32)))

    # -- admission (begin/advance; prefill() drives both) ------------------

    def _validate_admission(self, prompt, true_len, sampling):
        t0 = int(prompt.shape[-1])
        if true_len is None:
            true_len = t0
        elif not (1 <= true_len <= t0):
            raise ValueError(f"true_len {true_len} not in [1, {t0}]")
        if self.cfg.attn_window is None:
            # physical bounds of the full-length pool only — the
            # windowed ring holds any prompt (it keeps the last W).
            # The REAL length is what must leave room for >= 1
            # generated token; padded bucket length merely has to fit
            # the cache rows (a short prompt in a max_len-sized bucket
            # is fine — its pad tail is never read).
            if t0 > self.max_len:
                raise ValueError(
                    f"padded prompt len {t0} exceeds cache max_len "
                    f"{self.max_len}")
            if true_len >= self.max_len:
                raise ValueError(
                    f"prompt true_len {true_len} >= max_len "
                    f"{self.max_len}: no room for a generated token")
            # page-granular capacity: a prompt whose own blocks exceed
            # the WHOLE pool can never be served — reject up front,
            # not from a mid-run PoolExhaustedError
            need = blocks_for(true_len, self.page_size)
            if need > self.num_pages:
                raise ValueError(
                    f"prompt true_len {true_len} needs {need} pages "
                    f"> page pool num_pages {self.num_pages}")
        sampling = sampling or {}
        if sampling and self.select_fn is not None:
            raise ValueError(
                "per-request sampling and a pool-wide select_fn are "
                "mutually exclusive — drop one")
        unknown = set(sampling) - {"temperature", "top_k", "top_p",
                                   "seed"}
        if unknown:
            raise ValueError(f"unknown sampling keys {sorted(unknown)}")
        temp = sampling.get("temperature", 0.0)
        top_k = sampling.get("top_k")        # None-vs-0 must not blur:
        top_p = sampling.get("top_p")        # 0 values are ERRORS below
        T._validate_sampler_args(temp, top_k, top_p)
        return true_len, temp, top_k, top_p, sampling.get("seed")

    def prefill_begin(self, state: EngineState, slot: int, prompt,
                      true_len: Optional[int] = None,
                      sampling: Optional[dict] = None):
        """Admit a request into `slot`: validate, consult the prefix
        cache, map the slot's pages (PoolExhaustedError when the
        private blocks cannot be allocated — the pool is left
        untouched), and return (state, PrefillTicket). Run the actual
        forward with `prefill_advance` — once per chunk, interleaved
        with decode steps however the caller schedules them.

        sampling: THIS request's sampler params — a dict with any of
        temperature/top_k/top_p (missing = greedy/no-filter) and an
        optional "seed": the request's own rng stream identity, making
        its draws independent of pool co-tenants and admission order
        (default: this engine's admission counter). All values are
        traced, so requests with different sampling share compiled
        bodies. Incompatible with a pool-wide select_fn override."""
        true_len, temp, top_k, top_p, req_seed = \
            self._validate_admission(prompt, true_len, sampling)
        # the request's OWN stream identity: an explicit seed makes its
        # draws fully request-deterministic (pool/admission invariant);
        # default = this engine's admission counter. The two live in
        # DISJOINT domains (tag bit) so an explicit seed can never
        # collide with a counter value and correlate two streams.
        if req_seed is None:
            req_tag, req_seed = 0, self._admissions
        else:
            req_tag = 1
        prompt_np = np.asarray(prompt, np.int32)
        if not self.paged:
            self._admissions += 1
            return state, PrefillTicket(
                slot=slot, prompt=prompt_np, true_len=true_len,
                chunk=None, next_start=0, temp=float(temp),
                top_k=int(self.cfg.vocab if top_k is None else top_k),
                top_p=float(1.0 if top_p is None else top_p),
                req_tag=req_tag, req_seed=int(req_seed),
                windowed=True)
        if self.pool is None:
            raise RuntimeError(
                "no page pool — call init_state() before prefill")
        pages, shared_len = self.pool.admit(slot, prompt_np, true_len)
        self._admissions += 1
        row = np.full((self.max_pages_per_slot,), self.num_pages,
                      np.int32)
        row[:len(pages)] = pages
        state = state._replace(
            page_table=self._set_row(
                state.page_table, _staged(slot, np.int32),
                jnp.asarray(row)))
        return state, PrefillTicket(
            slot=slot, prompt=prompt_np, true_len=true_len,
            chunk=self.prefill_chunk, next_start=shared_len,
            temp=float(temp),
            top_k=int(self.cfg.vocab if top_k is None else top_k),
            top_p=float(1.0 if top_p is None else top_p),
            req_tag=req_tag, req_seed=int(req_seed))

    def prefill_advance(self, state: EngineState,
                        ticket: PrefillTicket):
        """Run ONE prefill chunk for the ticket; returns (state,
        done). The final chunk (the one holding position true_len-1)
        activates the slot and registers the prompt's full blocks in
        the prefix cache; chunks never run past the last real
        position, so bucket padding costs no chunk invocations."""
        # every scalar argument is staged explicitly (cached by
        # value): bucket lengths, sampler params and slot ids repeat
        # across requests, so admission costs no implicit transfers
        # and no per-call re-staging
        if ticket.windowed:
            state = self._prefill_jit(
                state, _staged(ticket.slot, np.int32),
                jnp.asarray(ticket.prompt, jnp.int32),
                _staged(ticket.true_len, np.int32),
                _staged(ticket.temp, np.float32),
                _staged(ticket.top_k, np.int32),
                _staged(ticket.top_p, np.float32),
                _staged_once(ticket.req_tag, np.int32),
                _staged_once(ticket.req_seed, np.int32),
                t0=int(ticket.prompt.shape[-1]))
            return state, True
        start = ticket.next_start
        t0 = int(ticket.prompt.shape[-1])
        width = ticket.chunk if ticket.chunk else (t0 - start)
        final = start + width >= ticket.true_len
        toks = ticket.prompt[start:start + width]
        if toks.shape[0] < width:
            toks = np.pad(toks, (0, width - toks.shape[0]))
        from_zero = (start == 0)
        args = (_staged(ticket.slot, np.int32),
                jnp.asarray(toks, jnp.int32), _staged(start, np.int32),
                _staged(ticket.true_len, np.int32),
                _staged(ticket.temp, np.float32),
                _staged(ticket.top_k, np.int32),
                _staged(ticket.top_p, np.float32),
                _staged_once(ticket.req_tag, np.int32),
                _staged_once(ticket.req_seed, np.int32))
        # artifact bundles carry one program per (chunk_w, from_zero,
        # final) combo actually saved; a width the bundle doesn't
        # cover (e.g. a prefix-hit remainder) is an EXPECTED miss and
        # takes the jit body silently — only a bound program FAILING
        # is a fallback event
        key = f"chunk_w{width}_z{int(from_zero)}_f{int(final)}"
        fn = self._art(key)
        if fn is not None:
            try:
                state = fn(state, *args)
            except Exception as e:
                self._art_drop(key, e)
                fn = None
        if fn is None:
            state = self._chunk_jit(
                state, *args,
                chunk_w=width, from_zero=from_zero, final=final)
        self.pool.prefill_chunks += 1
        ticket.next_start = start + width
        if final:
            self.pool.register(ticket.slot, ticket.prompt,
                               ticket.true_len)
        return state, final

    def prefill(self, state: EngineState, slot: int, prompt,
                true_len: Optional[int] = None,
                sampling: Optional[dict] = None) -> EngineState:
        """Admit a request and run its whole prefill: fill `slot` from
        `prompt` [t0]. Chunk widths are STATIC (one compile per
        distinct width) — pad prompts host-side to a few bucket
        lengths and pass the real length as `true_len` (traced: no
        recompile across real lengths within a bucket; decode matches
        generate() on the unpadded prompt). The slot's first generated
        token is in .last_tok[slot]. Equivalent to `prefill_begin` +
        `prefill_advance` until done — use those directly to
        interleave long prefills with decode steps."""
        state, ticket = self.prefill_begin(state, slot, prompt,
                                           true_len=true_len,
                                           sampling=sampling)
        done = False
        while not done:
            state, done = self.prefill_advance(state, ticket)
        return state

    # -- the batched decode step ------------------------------------------

    def _step_impl(self, state: EngineState):
        cfg = self.cfg
        params = self._step_params(state.last_tok)
        s, L = self.slots, self.max_len
        policy = default_policy()
        tok = state.last_tok
        x = jnp.take(params["embed"]["table"], tok[:, None], axis=0)
        x = x.astype(policy.compute_dtype)
        pos = state.pos[:, None]                      # [S, 1] per-row rope
        new_caches = []
        if not self.paged:
            # rolling ring pool: generate()'s rolling cache per-row —
            # the slot/validity arithmetic is THE shared convention
            # (T._ring_slot_valid); softmax is permutation-invariant
            # over key slots and rope rode in with K.
            w = cfg.attn_window
            slots_raw, ring_ok = T._ring_slot_valid(state.pos, w)
            write_slots = jnp.where(state.active, slots_raw,
                                    jnp.int32(w))   # sentinel: drop
            valid = ring_ok & state.active[:, None]
            valid4 = valid[:, None, None, :]

            def make_attn(k_buf, v_buf):
                def attn(q, k, v):
                    # THE shared decode attention (_cached_attention)
                    # with a per-row slot VECTOR: each row writes its
                    # own slot (out-of-range sentinel on inactive rows
                    # -> drop)
                    out, k2, v2 = T._cached_attention(
                        q, k, v, k_buf, v_buf, write_slots, valid4)
                    new_caches.append((k2, v2))
                    return out

                return attn
        else:

            def make_attn(k_buf, v_buf):
                def attn(q, k, v):
                    # the paged counterpart: scatter this step's K/V
                    # through the page table, gather the mapped pages
                    # (position order, sliced to max_len — the exact
                    # dense key axis) for the masked read
                    out, k2, v2 = pa.paged_decode_attention(
                        q, k, v, k_buf, v_buf, state.page_table,
                        state.pos, state.active,
                        page_size=self.page_size, max_len=L,
                        impl=self.ragged_impl)
                    new_caches.append((k2, v2))
                    return out

                return attn

        for p, (k_buf, v_buf) in zip(params["blocks"], state.caches):
            # inactive slots must not claim MoE expert capacity: their
            # compute is dead (writes drop, reads masked) but without a
            # token_mask the router would still count them against the
            # per-expert budget and could evict REAL tokens under a
            # tight capacity_factor
            x, _, _, _ = T._block_parts(cfg, p, x, pos,
                                        make_attn(k_buf, v_buf),
                                        state.active[:, None])
        keys = jax.vmap(jax.random.split)(state.rng)   # [S, 2] keys
        rng, sub = keys[:, 0], keys[:, 1]
        logits = T._head(params, x[:, -1])
        if self.select_fn is not None:
            # pool-wide select_fn keeps its scalar-key contract; it
            # consumes slot 0's stream (every slot's stream advances
            # each step regardless)
            nxt = self.select_fn(logits, sub[0]).astype(jnp.int32)
        else:
            # all-greedy pools (the default) must not pay the sampled
            # branch's O(S*V log V) sort per token: cond executes only
            # the taken branch, and temp is loop state, so a pool that
            # never admits a sampled request runs pure argmax
            nxt = jax.lax.cond(
                jnp.any(state.temp > 0.0),
                lambda lg, r: T.per_row_sample(
                    lg, state.temp, state.top_k, state.top_p, r),
                lambda lg, r: jnp.argmax(
                    T.at_least_f32(lg), axis=-1),
                logits, sub).astype(jnp.int32)
        nxt_lp = jnp.take_along_axis(
            jax.nn.log_softmax(T.at_least_f32(logits), axis=-1),
            nxt[:, None], axis=-1)[:, 0].astype(jnp.float32)
        # emitted token per row = the token CONSUMED this step (matches
        # generate(): its scan emits the carry token). A row finishes
        # when the token it just EMITTED is eos (so eos is part of its
        # output, like generate), or when it consumed its last cache
        # slot (nxt could never be processed).
        emitted = state.last_tok
        emitted_lp = state.last_lp
        fin = jnp.zeros_like(state.active)
        if self.eos_id is not None:
            fin = state.active & (emitted == self.eos_id)
        if cfg.attn_window is None:
            # capacity retirement is a PHYSICAL bound of the
            # full-length pool only; the ring reuses slots, so
            # windowed requests are bounded by eos and the caller's
            # max_new alone
            fin = fin | (state.active & (state.pos + 1 >= L))
        cont = state.active & ~fin
        new_state = EngineState(
            caches=tuple(new_caches),
            page_table=state.page_table,
            pos=jnp.where(cont, state.pos + 1, jnp.int32(L)),
            active=cont,
            last_tok=nxt,
            rng=rng,
            temp=state.temp,
            top_k=state.top_k,
            top_p=state.top_p,
            last_lp=nxt_lp)
        return new_state, emitted, emitted_lp, state.active, fin

    def decode_step(self, state: EngineState):
        """Advance every active slot one token. Returns (state,
        emitted [S] int32, emitted_lp [S] f32, was_active [S] bool,
        finished [S] bool): emitted[r]/emitted_lp[r] are meaningful
        where was_active[r] (emitted_lp is log p(token | prefix) under
        the full softmax — transformer.score()'s convention, whatever
        the sampler); finished rows have just emitted their final
        token (eos or cache-full) and their slot is free for the next
        prefill — paged callers must still `release_slot` it so the
        HOST pool frees its pages."""
        fn = self._art("step")
        if fn is not None:
            try:
                return fn(state)
            except Exception as e:
                self._art_drop("step", e)
        return self._step_jit(state)

    # -- the speculative verify round --------------------------------------

    def _spec_step_impl(self, state: EngineState, drafts, draft_len):
        cfg = self.cfg
        params = self._step_params(state.last_tok)
        s, L = self.slots, self.max_len
        policy = default_policy()
        k = drafts.shape[1]
        # the verify WINDOW: the carry token plus the k drafts — one
        # forward over [S, K+1] scores every draft against the target
        # in a single launch (the plain step is exactly the k=0 case)
        window = jnp.concatenate(
            [state.last_tok[:, None], drafts.astype(jnp.int32)],
            axis=1)
        x = jnp.take(params["embed"]["table"], window, axis=0)
        x = x.astype(policy.compute_dtype)
        pos = (state.pos[:, None]
               + jnp.arange(k + 1, dtype=jnp.int32)[None, :])
        new_caches = []

        def make_attn(k_buf, v_buf):
            def attn(q, kk, vv):
                # scatter the whole window's K/V through the page
                # table (the caller reserved pages through pos+k),
                # then the ragged masked read at per-row offsets —
                # rejected positions are rolled back on the HOST
                # (pool.commit) and rewritten before any later read
                # (paged_verify_attention's rewrite-soundness note)
                out, k2, v2 = pa.paged_verify_attention(
                    q, kk, vv, k_buf, v_buf, state.page_table,
                    state.pos, state.active,
                    page_size=self.page_size, max_len=L,
                    impl=self.ragged_impl)
                new_caches.append((k2, v2))
                return out

            return attn

        # positions past a row's draft_len are PADDING (every slot
        # pads its drafts to policy.spec_draft_max so this body
        # compiles ONCE): their compute is dead — writes land beyond
        # the accepted frontier and are rewritten before exposure, the
        # verify rule caps acceptance at draft_len — but they must not
        # claim MoE expert capacity, same rule as inactive rows in the
        # plain step
        tok_mask = state.active[:, None] & (
            jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            <= draft_len[:, None])
        for p, (k_buf, v_buf) in zip(params["blocks"], state.caches):
            x, _, _, _ = T._block_parts(cfg, p, x, pos,
                                        make_attn(k_buf, v_buf),
                                        tok_mask)
        keys = jax.vmap(jax.random.split)(state.rng)
        rng, sub = keys[:, 0], keys[:, 1]
        logits = T._head(params, x)                    # [S, K+1, V]
        # all-greedy pools take the sort-free argmax verify, exactly
        # like the plain step's per_row_sample/argmax cond; sampled
        # pools run the distribution-preserving acceptance rule. One
        # rng split per ROUND (not per token): a sampled row's draws
        # stay deterministic per (seed, round index) but differ from
        # the baseline's per-token stream — greedy rows ignore rng
        # entirely, so the bit-exact greedy contract is untouched.
        nxt, n_acc, lp_draft, lp_next = jax.lax.cond(
            jnp.any(state.temp > 0.0),
            lambda lg, r: sampling_ops.ngram_spec_verify(
                lg, window, draft_len, state.temp, state.top_k,
                state.top_p, r),
            lambda lg, r: sampling_ops.greedy_spec_verify(
                lg, window, draft_len),
            logits, sub)
        # a round CONSUMES window[:n_acc+1] (accepted prefix plus the
        # break position's own token) and each consumed token is
        # emitted — generate()'s emit-the-carry convention per token
        emitted = window
        emitted_lp = jnp.concatenate(
            [state.last_lp[:, None], lp_draft], axis=1)
        n_con = n_acc + 1
        fin = jnp.zeros_like(state.active)
        n_emit = n_con
        if self.eos_id is not None:
            # eos anywhere in the consumed prefix finishes the row at
            # that token (eos is emitted, like generate); later
            # accepted tokens are discarded with the row
            is_eos = (window == self.eos_id) & (
                jnp.arange(k + 1, dtype=jnp.int32)[None, :]
                < n_con[:, None])
            has_eos = jnp.any(is_eos, axis=1)
            n_emit = jnp.where(
                has_eos,
                jnp.argmax(is_eos.astype(jnp.int32), axis=1) + 1,
                n_con).astype(jnp.int32)
            fin = state.active & has_eos
        # capacity retirement: the round's true advance against the
        # plain step's pos+1 >= L (policy.draft_len clamps k so
        # pos + n_emit <= L always — equality IS the bound)
        fin = fin | (state.active & (state.pos + n_emit >= L))
        cont = state.active & ~fin
        new_state = EngineState(
            caches=tuple(new_caches),
            page_table=state.page_table,
            pos=jnp.where(cont, state.pos + n_emit, jnp.int32(L)),
            active=cont,
            last_tok=nxt,
            rng=rng,
            temp=state.temp,
            top_k=state.top_k,
            top_p=state.top_p,
            last_lp=lp_next)
        return (new_state, emitted, emitted_lp, n_emit, state.active,
                fin, n_acc)

    def spec_step(self, state: EngineState, drafts, draft_len):
        """One speculative verify round over the pool: score each
        slot's drafts against the target in a single forward, accept
        the distribution-preserving prefix, carry the redraw as the
        next round's token. drafts [S, K] int32 / draft_len [S] int32
        are HOST arrays (K = the policy's padded width; entries past
        draft_len[r] arbitrary), staged explicitly here — they change
        every round, so the `_staged` value-cache would not help.

        Returns (state, emitted [S, K+1] int32, emitted_lp [S, K+1]
        f32, n_emit [S] int32, was_active [S] bool, finished [S] bool,
        n_accepted [S] int32): row r emitted emitted[r, :n_emit[r]]
        this round (lps full-softmax, score()'s convention), finished
        rows just emitted their final token. The caller must have
        reserved pages covering positions pos..pos+draft_len[r]
        (pool.reserve) BEFORE the call, and must settle continuing
        rows with pool.commit(slot, n_emit) after — commit maps the
        next write block and rolls the rejected tail's pages back."""
        d = jax.device_put(np.asarray(drafts, np.int32))
        dl = jax.device_put(np.asarray(draft_len, np.int32))
        fn = self._art("spec")
        if fn is not None:
            try:
                return fn(state, d, dl)
            except Exception as e:
                self._art_drop("spec", e)
        return self._spec_jit(state, d, dl)

    def reserve_spec_pages(self, state: EngineState, slot: int,
                           k: int) -> EngineState:
        """Map the verify window's write blocks for one slot BEFORE a
        spec_step: pool.reserve (all-or-nothing, pos untouched) plus
        the device page-table pushes, staged scalars through the same
        jitted setter as every other mapping. Raises
        PoolExhaustedError with pool AND device table unchanged — the
        caller degrades the slot to a 0-draft round (never preempt a
        co-tenant for SPECULATIVE work)."""
        for blk, page in self.pool.reserve(slot, k):
            state = state._replace(
                page_table=self._set_pagemap(
                    state.page_table, _staged(slot, np.int32),
                    _staged(blk, np.int32), _staged(page, np.int32)))
        return state

    def settle_spec(self, state: EngineState, slot: int,
                    n_emit: int) -> EngineState:
        """Settle one CONTINUING slot's pool state after a spec_step
        consumed n_emit tokens: pool.commit advances pos, maps the
        next write block when full acceptance crossed a boundary (may
        raise PoolExhaustedError with pos NOT advanced — the caller
        frees a victim and retries, exactly like ensure_decode_page),
        and rolls the rejected tail's pages back; the dropped blocks'
        device rows return to the drop sentinel so stale mappings
        cannot resurface."""
        added, dropped = self.pool.commit(slot, n_emit)
        for blk, page in added:
            state = state._replace(
                page_table=self._set_pagemap(
                    state.page_table, _staged(slot, np.int32),
                    _staged(blk, np.int32), _staged(page, np.int32)))
        for blk in dropped:
            state = state._replace(
                page_table=self._set_pagemap(
                    state.page_table, _staged(slot, np.int32),
                    _staged(blk, np.int32),
                    _staged(self.num_pages, np.int32)))
        return state

    def ensure_decode_page(self, state: EngineState,
                           slot: int) -> EngineState:
        """Advance the HOST page bookkeeping for one slot that just
        consumed a token and continues: when its next write position
        crosses into an unmapped block, allocate that block's page and
        push the mapping to the device table. Call exactly once per
        continuing slot per decode step (both serve loops do). Raises
        PoolExhaustedError — with the position NOT advanced, so the
        caller can free a victim and retry — when no page is
        available."""
        if not self.paged:
            return state
        res = self.pool.extend(slot)
        if res is not None:
            blk, page = res
            # staged scalars through the jitted setter: the per-step
            # page-map update costs no implicit transfer and no
            # compile (transfer-guard regression, tests/test_analysis)
            state = state._replace(
                page_table=self._set_pagemap(
                    state.page_table, _staged(slot, np.int32),
                    _staged(blk, np.int32), _staged(page, np.int32)))
        return state

    def release_slot(self, state: EngineState, slot: int) -> EngineState:
        """Host-side retire of one slot: deactivate the row, park its
        pos on the out-of-range sentinel so the next step's writes
        drop and its reads stay masked, free its pages back to the
        pool (refcounted — shared prefix pages survive for their other
        holders), and reset its page-table row to the drop sentinel.
        THE one retire convention — serve()'s token-budget retire, its
        device-finished rows, and the reliability server's deadline/
        drain/exhaustion evictions (serve.server) all route here, so
        the sentinel arithmetic and the page accounting cannot drift
        between them."""
        if self.paged and self.pool is not None:
            self.pool.release(slot)
            state = state._replace(
                page_table=self._set_row(
                    state.page_table, _staged(slot, np.int32),
                    self._empty_row))
        active, pos = self._retire(
            state.active, state.pos, _staged(slot, np.int32),
            _staged(self.max_len, np.int32))
        return state._replace(active=active, pos=pos)

    # -- KV-block migration (disaggregated prefill/decode) -----------------

    def _pause_impl(self, state: EngineState, slot, fill):
        """Read one slot's per-row decode state and PARK the row in a
        single launch: active False + pos on the drop sentinel, so the
        pool's decode/spec steps skip it (writes drop, reads masked)
        while the host still owns its pages for the transfer window."""
        row = lambda a: a[slot]
        vals = (row(state.pos), row(state.last_tok), row(state.last_lp),
                row(state.temp), row(state.top_k), row(state.top_p),
                jax.random.key_data(state.rng)[slot])
        return (vals, state.active.at[slot].set(False),
                state.pos.at[slot].set(fill))

    def _kvread_impl(self, state: EngineState, pages):
        """Gather `pages` (padded [max_pages_per_slot] int32, clip on
        the pad tail) from every layer's arenas: per layer ((k, v)) —
        int8 arenas yield (data, scale) pairs, exported verbatim so the
        destination receives bit-identical quantized content."""
        def g(buf):
            if isinstance(buf, tuple):
                return tuple(jnp.take(b, pages, axis=0, mode="clip")
                             for b in buf)
            return jnp.take(buf, pages, axis=0, mode="clip")

        return tuple((g(k_buf), g(v_buf))
                     for k_buf, v_buf in state.caches)

    def _kvwrite_impl(self, state: EngineState, pages, data):
        """Scatter exported block contents into this pool's arenas at
        `pages` (padded [max_pages_per_slot] int32; sentinel entries —
        the pad tail AND blocks satisfied by the local prefix cache —
        drop, so shared pages are never written)."""
        def s(buf, new):
            if isinstance(buf, tuple):
                return tuple(b.at[pages].set(n, mode="drop")
                             for b, n in zip(buf, new))
            return buf.at[pages].set(new.astype(buf.dtype), mode="drop")

        caches = tuple((s(k_buf, dk), s(v_buf, dv))
                       for (k_buf, v_buf), (dk, dv)
                       in zip(state.caches, data))
        return state._replace(caches=caches)

    def _resume_impl(self, state: EngineState, slot, pos, tok, lp,
                     temp, top_k, top_p, key_data):
        """Install a migrated slot's decode state: the row goes live
        with exactly the source's pos/last_tok/last_lp/sampler params
        and rng stream (wrap_key_data of the exported key bits)."""
        return state._replace(
            pos=state.pos.at[slot].set(pos),
            active=state.active.at[slot].set(True),
            last_tok=state.last_tok.at[slot].set(tok),
            rng=state.rng.at[slot].set(
                jax.random.wrap_key_data(key_data)),
            temp=state.temp.at[slot].set(temp),
            top_k=state.top_k.at[slot].set(top_k),
            top_p=state.top_p.at[slot].set(top_p),
            last_lp=state.last_lp.at[slot].set(lp))

    def _padded_pages(self, pages, start_block: int = 0) -> np.ndarray:
        """[max_pages_per_slot] int32 page-id vector: `pages` in block
        order with entries before `start_block` and past len(pages)
        replaced by the drop/clip sentinel."""
        row = np.full((self.max_pages_per_slot,), self.num_pages,
                      np.int32)
        row[start_block:len(pages)] = pages[start_block:]
        return row

    def pause_slot(self, state: EngineState, slot: int):
        """Pause one ACTIVE slot at the prefill-complete seam (the
        disaggregation handoff point): snapshot its per-row decode
        state to the host and park the device row, leaving its pages
        mapped in the pool and the page table untouched. Returns
        (state, DecodeSeed). The slot decodes nothing while parked;
        `resume_slot` (here after a cancelled handoff, or on the
        migration destination) continues bit-exactly where the row
        stopped. Paged engines only."""
        fn, out = self._art("pause"), None
        args = (_staged(slot, np.int32),
                _staged(self.max_len, np.int32))
        if fn is not None:
            try:
                out = fn(state, *args)
            except Exception as e:
                self._art_drop("pause", e)
        if out is None:
            out = self._pause_jit(state, *args)
        vals, active, pos = out
        vals = jax.device_get(vals)
        seed = DecodeSeed(
            pos=int(vals[0]), last_tok=int(vals[1]),
            last_lp=float(vals[2]), temp=float(vals[3]),
            top_k=int(vals[4]), top_p=float(vals[5]),
            rng_key_data=np.asarray(vals[6]))
        return state._replace(active=active, pos=pos), seed

    def export_slot_kv(self, state: EngineState, pages) -> list:
        """Read the arena contents of `pages` (one slot's mapped
        blocks, in block order) to the host: per layer (k, v), each an
        ndarray [n_pages, page_size, Hkv, Dh] — or an (int8 data,
        scale) pair under kv_cache_dtype="int8", exported verbatim.
        The caller holds the pages (slot mapping or a pool export pin)
        for the duration, so the ids cannot be recycled under us."""
        padded = jnp.asarray(self._padded_pages(pages))
        fn, out = self._art("kvread"), None
        if fn is not None:
            try:
                out = fn(state, padded)
            except Exception as e:
                self._art_drop("kvread", e)
        if out is None:
            out = self._kvread_jit(state, padded)
        n = len(pages)
        sl = lambda a: np.asarray(a)[:n]

        def host(buf):
            if isinstance(buf, tuple):
                return tuple(sl(b) for b in buf)
            return sl(buf)

        out = jax.device_get(out)
        return [(host(k), host(v)) for k, v in out]

    def import_slot_kv(self, state: EngineState, slot: int, pages,
                       start_block: int, kv) -> EngineState:
        """Write exported block contents into this pool's arenas for a
        freshly `import_blocks`-mapped slot, and push the slot's full
        page-table row. Blocks before `start_block` were satisfied by
        the LOCAL prefix cache (their pages are shared, read-only —
        the inbound copy is redundant) and are skipped via the scatter
        sentinel. `kv` is `export_slot_kv`'s output from the source;
        geometry must match this engine (asserted)."""
        if len(kv) != len(state.caches):
            raise ValueError(
                f"migrated KV has {len(kv)} layers, engine has "
                f"{len(state.caches)}")
        pad_rows = self._padded_pages(pages, start_block)
        arena_shape = (self.max_pages_per_slot, self.page_size,
                       self.cfg.kv_heads, self.cfg.head_dim)

        def pad(buf):
            if isinstance(buf, tuple):
                return tuple(self._pad_blocks(b) for b in buf)
            return self._pad_blocks(buf)

        data = []
        for k, v in kv:
            first = k[0] if isinstance(k, tuple) else k
            if tuple(first.shape[1:]) != arena_shape[1:]:
                raise ValueError(
                    f"migrated KV block shape {first.shape[1:]} does "
                    f"not match arena {arena_shape[1:]}")
            data.append((pad(k), pad(v)))
        data = jax.device_put(tuple(data))
        padded = jnp.asarray(pad_rows)
        fn, out = self._art("kvwrite"), None
        if fn is not None:
            try:
                out = fn(state, padded, data)
            except Exception as e:
                self._art_drop("kvwrite", e)
        if out is None:
            out = self._kvwrite_jit(state, padded, data)
        state = out
        row = np.full((self.max_pages_per_slot,), self.num_pages,
                      np.int32)
        row[:len(pages)] = pages
        return state._replace(
            page_table=self._set_row(
                state.page_table, _staged(slot, np.int32),
                jnp.asarray(row)))

    def _pad_blocks(self, b) -> np.ndarray:
        """Pad a [n, ...] host block stack to [max_pages_per_slot, ...]
        (zeros — the scatter drops the tail anyway, the pad just keeps
        the jitted write body's shapes static)."""
        b = np.asarray(b)
        padn = self.max_pages_per_slot - b.shape[0]
        return np.pad(b, [(0, padn)] + [(0, 0)] * (b.ndim - 1))

    def resume_slot(self, state: EngineState, slot: int,
                    seed: DecodeSeed) -> EngineState:
        """Bring a slot live from a DecodeSeed: on the migration
        destination after `import_slot_kv`, or locally after a
        cancelled handoff. The row's next decode step emits exactly
        the token the paused source row would have."""
        args = (_staged(slot, np.int32),
                _staged(seed.pos, np.int32),
                _staged(seed.last_tok, np.int32),
                _staged(seed.last_lp, np.float32),
                _staged(seed.temp, np.float32),
                _staged(seed.top_k, np.int32),
                _staged(seed.top_p, np.float32),
                _staged_once(seed.rng_key_data,
                             seed.rng_key_data.dtype))
        fn = self._art("resume")
        if fn is not None:
            try:
                return fn(state, *args)
            except Exception as e:
                self._art_drop("resume", e)
        return self._resume_jit(state, *args)

    def kv_geometry(self) -> dict:
        """The fields two engines must agree on for a KV-block
        migration between them to be meaningful (the server's import
        gate; the fleet builds same-model replicas by construction,
        this catches mis-wiring): arena geometry + cache dtype +
        paging convention."""
        return {
            "page_size": int(self.page_size),
            "max_pages_per_slot": int(self.max_pages_per_slot),
            "kv_heads": int(self.cfg.kv_heads),
            "head_dim": int(self.cfg.head_dim),
            "kv_cache_dtype": self.cfg.kv_cache_dtype,
            "vocab": int(self.cfg.vocab),
            "max_len": int(self.max_len),
        }

    # -- batteries-included host scheduler --------------------------------

    def serve(self, prompts, *, max_new: int, buckets=None,
              sampling=None, return_logprobs: bool = False,
              speculative: bool = False, proposer=None):
        """Serve a list of 1-D int32 prompts through the S-slot pool:
        admit while slots AND pages are free, step, collect, refill —
        the continuous part. Returns per-request generated-token lists
        (eos included, like generate()); each equals the generate()
        tokens for that prompt (engine consistency test). max_new
        bounds every request (cache capacity bounds it too).

        With `prefill_chunk` set, long prompts prefill one chunk per
        loop iteration while admitted co-tenants keep decoding — no
        head-of-line stall. On page-pool exhaustion mid-decode (only
        possible when num_pages over-subscribes the slots) the loop
        preempts the cheapest co-tenant back onto the queue
        (stats.retried — its decode restarts from a fresh prefill,
        tokens identical) or, with no co-tenant to evict, retires the
        needy request at pool capacity exactly like the max_len bound.

        buckets: optional ascending prompt-length buckets (e.g.
        (32, 128, 512)): each prompt is padded to the smallest bucket
        >= its length, so prefill compiles once PER BUCKET instead of
        per distinct length; the real length rides through `true_len`,
        so the decode is still exactly the unpadded generate().

        sampling: optional per-request sampler params — one dict per
        prompt (see prefill()); None = greedy for every request.

        return_logprobs: also return per-request per-token
        log p(token | prefix) lists (full-softmax convention — the
        reference's SequenceGenerator returns sequence scores the
        same way, api/PaddleAPI.h:1025).

        speculative: decode via draft/verify rounds instead of
        one-token steps — each round scores up to
        policy.spec_draft_max host-proposed drafts per slot in ONE
        forward and consumes the accepted prefix plus the verify's
        own token (docs/SERVING.md "Speculative decoding"). Greedy
        requests keep the exact generate() parity contract; sampled
        requests keep the output DISTRIBUTION (rejection-sampling
        acceptance) but draw from a per-round stream, so individual
        draws differ from the plain loop's per-token stream. Paged
        engines only. `proposer` (default NGramProposer()) supplies
        propose(history, k) -> drafts; 0-draft rounds degrade to
        plain decode steps."""
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if sampling is not None and len(sampling) != len(prompts):
            raise ValueError(
                f"sampling has {len(sampling)} entries for "
                f"{len(prompts)} prompts")
        if buckets is not None and self.cfg.attn_window is None:
            # fail BEFORE any decode work: a bucket the cache cannot
            # hold would otherwise surface as a mid-run ValueError from
            # admit() after earlier requests already burned chip time
            too_big = [b for b in buckets if b > self.max_len]
            if too_big:
                raise ValueError(
                    f"buckets {too_big} exceed max_len {self.max_len}: "
                    f"padded prefills cannot fit the cache")
        # per-prompt bounds, ALSO at entry: an unservable prompt must
        # reject before any other request burns chip time, not from
        # deep inside a mid-run prefill
        for i, p in enumerate(prompts):
            t0 = int(p.shape[-1])
            if t0 < 1:
                raise ValueError(
                    f"prompt {i} is empty (need >= 1 token)")
            if buckets is not None and t0 > max(buckets):
                raise ValueError(
                    f"prompt {i} len {t0} exceeds largest bucket "
                    f"{max(buckets)}")
            if self.cfg.attn_window is None:
                if t0 >= self.max_len:
                    raise ValueError(
                        f"prompt {i} true_len {t0} >= max_len "
                        f"{self.max_len}: no room for a generated "
                        f"token")
                # page-granular capacity (same rule as prefill_begin):
                # a prompt that fits max_len but not the whole page
                # pool is rejected up front, not mid-run
                need = blocks_for(t0, self.page_size)
                if need > self.num_pages:
                    raise ValueError(
                        f"prompt {i} needs {need} pages > page pool "
                        f"num_pages {self.num_pages}")

        prompt_hist: list = []
        if speculative:
            if not self.paged:
                raise ValueError(
                    "speculative serving needs the paged engine "
                    "(sliding-window configs decode plain)")
            if self.select_fn is not None:
                raise ValueError(
                    "speculative serving composes with per-request "
                    "sampling only: a pool-wide select_fn overrides "
                    "the distribution the acceptance rule preserves")
            if int(self.policy.spec_draft_max) < 1:
                raise ValueError(
                    f"policy.spec_draft_max must be >= 1, got "
                    f"{self.policy.spec_draft_max}")
            if proposer is None:
                proposer = NGramProposer()
            # the proposer's history view: the TRUE prompt (unpadded)
            # plus everything emitted so far — host ints only
            prompt_hist = [
                [int(x) for x in
                 np.asarray(jax.device_get(p)).reshape(-1)]
                for p in prompts]

        state = self.init_state()
        stats = PoolStats(requests=len(prompts))
        queue = list(range(len(prompts)))
        slot_req = [-1] * self.slots          # which request owns a slot
        pending: dict[int, PrefillTicket] = {}  # mid-prefill slots
        emitted: dict[int, list] = {i: [] for i in range(len(prompts))}
        lps: dict[int, list] = {i: [] for i in range(len(prompts))}
        remaining = [max_new] * len(prompts)

        def admit():
            nonlocal state
            for slot in range(self.slots):
                if slot_req[slot] != -1 or not queue:
                    continue
                idx = self.policy.next_index(queue)
                req = queue[idx]
                padded, true_len = pad_to_bucket(prompts[req],
                                                 buckets)
                if not self.policy.can_admit(self.pool, padded,
                                             true_len):
                    # no pages for the policy's pick right now:
                    # in-flight requests will free some — keep it
                    # queued in place
                    break
                try:
                    state, ticket = self.prefill_begin(
                        state, slot, padded, true_len=true_len,
                        sampling=(sampling[req] if sampling else None))
                except PoolExhaustedError:
                    # the gate passed but admit still raised (an
                    # injected alloc fault) — same answer: wait
                    break
                queue.pop(idx)
                slot_req[slot] = req
                stats.prefills += 1
                stats.admitted += 1
                if ticket.chunk is None:
                    # one-shot prefill (the classic schedule): finish
                    # it here so this wave's LATER admissions can hit
                    # the prefix blocks it just registered
                    done = False
                    while not done:
                        state, done = self.prefill_advance(state,
                                                           ticket)
                else:
                    # chunked: defer to the loop, interleaved with
                    # decode steps (same-wave identical prompts miss
                    # the cache until the first one's final chunk
                    # registers — the interleaving trade)
                    pending[slot] = ticket

        def preempt_or_retire(slot: int) -> bool:
            """Pool exhausted extending `slot`: evict the victim the
            policy picks (default: LOWEST priority = latest submission
            order) back onto the queue — possibly `slot` itself, which
            then yields to its seniors. The default priority is a
            TOTAL order, so the most senior active request is never
            preempted and always progresses: no two slots can preempt
            each other forever (the recompute-preemption livelock).
            Returns True to retry the page grab, False when `slot` is
            gone (yielded or — alone in the pool — retired at pool
            capacity, the paged analog of the max_len bound). Mirrors
            the server's shed/requeue semantics for the plain loop."""
            nonlocal state
            holders = [s_ for s_ in range(self.slots)
                       if slot_req[s_] != -1]
            s_v = self.policy.preemption_victim(
                [(s_, slot_req[s_]) for s_ in holders])
            if s_v == slot and len(holders) == 1:
                # nobody to yield to: pool capacity IS this request's
                # bound — retire it with the tokens it has
                state = self.release_slot(state, slot)
                slot_req[slot] = -1
                stats.completed += 1
                return False
            req_v = slot_req[s_v]
            state = self.release_slot(state, s_v)
            pending.pop(s_v, None)
            slot_req[s_v] = -1
            emitted[req_v] = []
            lps[req_v] = []
            remaining[req_v] = max_new
            queue.insert(0, req_v)
            stats.retried += 1
            return s_v != slot

        admit()
        while any(r != -1 for r in slot_req):
            # one prefill chunk per mid-prefill slot, interleaved with
            # the decode steps below (chunked prefill's whole point);
            # which slots advance (and in what order) is the policy's
            for slot in self.policy.prefill_slots(list(pending)):
                ticket = pending.get(slot)
                if ticket is None:
                    continue
                state, done = self.prefill_advance(state, ticket)
                if done:
                    del pending[slot]
            decoding = sum(slot_req[s_] != -1 and s_ not in pending
                           for s_ in range(self.slots))
            if not self.policy.should_decode(decoding, len(pending)):
                continue        # only prefills in flight — no step
            if not speculative:
                state, toks, tok_lps, was_active, fin = \
                    self.decode_step(state)
                stats.steps += 1
                # ONE host sync per step (the admission decision
                # needs it)
                toks, tok_lps, was_active_h, fin_h = jax.device_get(
                    (toks, tok_lps, was_active, fin))
                freed = False
                for slot in range(self.slots):
                    req = slot_req[slot]
                    if req == -1 or slot in pending \
                            or not was_active_h[slot]:
                        continue
                    emitted[req].append(int(toks[slot]))
                    lps[req].append(float(tok_lps[slot]))
                    stats.tokens += 1
                    remaining[req] -= 1
                    if fin_h[slot] or remaining[req] <= 0:
                        # ONE retire path for device-finished and
                        # budget-finished rows alike: the pool must
                        # free the pages either way
                        state = self.release_slot(state, slot)
                        slot_req[slot] = -1
                        stats.completed += 1
                        freed = True
                        continue
                    # continuing row: map the next write position's
                    # page
                    while True:
                        try:
                            state = self.ensure_decode_page(state,
                                                            slot)
                            break
                        except PoolExhaustedError:
                            if not preempt_or_retire(slot):
                                freed = True
                                break  # retired at pool capacity
            else:
                # -- speculative verify round: propose -> reserve ->
                # verify-in-one-step -> commit/rollback -------------
                kmax = int(self.policy.spec_draft_max)
                drafts_np = np.zeros((self.slots, kmax), np.int32)
                dlen_np = np.zeros((self.slots,), np.int32)
                for slot in range(self.slots):
                    req = slot_req[slot]
                    if req == -1 or slot in pending:
                        continue
                    budget = self.policy.draft_len(
                        pos=self.pool.slot_pos[slot],
                        max_len=self.max_len,
                        remaining=remaining[req])
                    prop = []
                    if budget > 0:
                        # draft() self-extends through looped output;
                        # custom proposers may only define propose()
                        draft_fn = getattr(proposer, "draft",
                                           proposer.propose)
                        prop = draft_fn(
                            prompt_hist[req] + emitted[req],
                            budget)[:budget]
                    if prop:
                        try:
                            state = self.reserve_spec_pages(
                                state, slot, len(prop))
                        except PoolExhaustedError:
                            # no pages for drafts: degrade this slot
                            # to a plain decode round — never preempt
                            # for SPECULATIVE work
                            prop = []
                    drafts_np[slot, :len(prop)] = prop
                    dlen_np[slot] = len(prop)
                    stats.draft_proposed += len(prop)
                state, em, em_lp, n_emit, was_active, fin, n_acc = \
                    self.spec_step(state, drafts_np, dlen_np)
                stats.steps += 1
                stats.spec_rounds += 1
                # ONE host sync per round, same as the plain step
                em, em_lp, n_emit_h, was_active_h, fin_h, n_acc_h = \
                    jax.device_get((em, em_lp, n_emit, was_active,
                                    fin, n_acc))
                freed = False
                for slot in range(self.slots):
                    req = slot_req[slot]
                    if req == -1 or slot in pending \
                            or not was_active_h[slot]:
                        continue
                    ne = int(n_emit_h[slot])
                    stats.draft_accepted += int(n_acc_h[slot])
                    for j in range(ne):
                        emitted[req].append(int(em[slot, j]))
                        lps[req].append(float(em_lp[slot, j]))
                    stats.tokens += ne
                    remaining[req] -= ne
                    if fin_h[slot] or remaining[req] <= 0:
                        # release frees reserved-but-rejected pages
                        # with the rest of the row — no commit needed
                        state = self.release_slot(state, slot)
                        slot_req[slot] = -1
                        stats.completed += 1
                        freed = True
                        continue
                    # settle the pool at the accepted length: commit
                    # maps the next write block (full acceptance may
                    # cross a boundary) and unmaps the rejected
                    # tail's blocks (device rows -> drop sentinel)
                    while True:
                        try:
                            state = self.settle_spec(state, slot, ne)
                            break
                        except PoolExhaustedError:
                            if not preempt_or_retire(slot):
                                freed = True
                                break  # retired at pool capacity
            if freed or queue:
                admit()
        toks_out = [emitted[i] for i in range(len(prompts))]
        if self.pool is not None:
            pc = self.pool.counters()
            for k in ("pages_in_use", "pages_free",
                      "peak_pages_in_use", "prefix_hits",
                      "prefix_misses", "prefill_chunks",
                      "spec_reserved", "spec_rolled_back"):
                setattr(stats, k, pc[k])
        self.last_stats = stats
        if return_logprobs:
            return toks_out, [lps[i] for i in range(len(prompts))]
        return toks_out
