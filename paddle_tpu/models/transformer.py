"""Decoder-only transformer LM — the framework's modern long-context
flagship.

No reference counterpart: the reference predates transformers (SURVEY
§2.8 notes PP/TP/CP/ring have no analog there), but a TPU-native
framework needs one. TPU-first choices:

- pre-LN blocks, fused QKV projection (one [B*T,D]x[D,3D] matmul for
  the MXU instead of three),
- rotary positions (no learned position table to shard or resize),
- Pallas flash attention (`ops.flash_attention`) when requested /
  on TPU, exact dense fallback elsewhere — O(T·block) memory makes
  32k+ contexts feasible on one chip,
- optional `jax.checkpoint` over each block (`remat` trades FLOPs for
  HBM on long sequences). A checkpointed block keeps its input, 2 x
  dim bytes a position, and where its attention ran the flash kernel
  also the kernel's output and row log-sum-exp, by the names the kernel
  gives them (`flash_attention.REMAT_SAVED`): 2 H Dh + 4 H bytes a
  position a layer in bf16, 8.3 KB at 32 heads of 128, about twice what
  the block kept before. The backward pass recomputes the block around
  the kernel (norms, projections, rotary, transposes, the feed-forward
  or expert layer) and runs the attention forward once a layer a step;
  a plain checkpoint ran the kernel twice. The price is memory: a
  `remat` that was set to fit a long context fits a somewhat shorter
  one and trains faster (at a stage of 4-6 layers a chip the kept
  arrays are under 5% of a 16 GB chip). Dense and external (ring /
  Ulysses) attention name nothing, and such a block keeps its input
  alone,
- parameter names line up with `parallel.sharding.MEGATRON_RULES`
  (qkv/fc1 shard output features, proj/fc2 shard input features) so
  the same pytree drives dp x tp through
  `parallel.train_step.make_sharded_train_step`; `TP_RULES` below adds
  the vocab-sharded LM head.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtypes import at_least_f32, default_policy
from paddle_tpu.nn import initializers
from paddle_tpu.ops import linalg
from paddle_tpu.ops import losses as losses_ops
from paddle_tpu.ops import norm as norm_ops
from paddle_tpu.ops import pallas_util
from paddle_tpu.ops import sampling as sampling_ops
from paddle_tpu.ops.flash_attention import REMAT_SAVED, flash_attention
from paddle_tpu.ops.gated_delta import gated_delta_rule
from paddle_tpu.parallel.sharding import MEGATRON_RULES, MODEL_AXIS

from jax.sharding import PartitionSpec as P

# tensor-parallel rules for this family: megatron MLP/attention splits
# plus the LM head sharded over the vocab dim
TP_RULES = list(MEGATRON_RULES) + [(r"lm_head/kernel$", P(None, MODEL_AXIS))]

# MoE variant: stacked expert weights sharded over their expert dim
# (axis 0) on the model axis — pjit partitions the dispatch einsums;
# the shard_map EP path (parallel.moe.make_expert_parallel_ffn) is the
# hand-scheduled alternative for when the all-gather XLA inserts here
# costs more than the explicit all-to-all. The router rule must come
# FIRST: rules are first-match and MEGATRON's `out` alternation would
# otherwise catch the substring in "r-out-er" and shard the router's
# d_model dim (the router is replicated by design — the EP path's
# shard_map pspec pins it P()).
# The dropless layer's leaves (w_gate / w_up / w_down, stacked over the
# experts held) split the same way; the q/k norm weights are replicated.
TP_MOE_RULES = ([(r"moe/router/kernel$", P())] + TP_RULES +
                [(r"moe/(w1|b1|w2|b2|w_gate|w_up|w_down)$", P(MODEL_AXIS)),
                 (r"(q_norm|k_norm)/scale$", P())])


MIXERS = ("attention", "gated_delta")


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What a layer's token mixer may have of its own where a model
    mixes kinds of layer: the window (None = full causal) and the rotary
    scaling, fields as `TransformerConfig`'s of the same names (its
    `attn_window` is `window` here). Hashable: it rides `jax.checkpoint`
    as a static argument beside the config.

    mixer: "attention" (softmax attention, the fields here) or
    "gated_delta" (a Gated DeltaNet layer of the config's `gdn_*` sizes,
    `ops.gated_delta`; it reads none of the attention's fields).
    output_gate: the attention's output times sigmoid(gate), the gate a
    second half of the query projection, one value a lane of each head
    (Qwen3-Next). rotary_dim: the rotary embedding turns the first
    `rotary_dim` lanes of each head and passes the rest (None: all; 0:
    none, a NoPE layer whose positions only the causal mask gives)."""
    window: Optional[int] = None
    rope_scaling: str = "none"
    rope_factor: float = 1.0
    rope_original: Optional[int] = None
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: Optional[float] = None
    mixer: str = "attention"
    output_gate: bool = False
    rotary_dim: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 4
    mlp_ratio: int = 4
    rope_base: float = 10000.0
    # "flash" = Pallas kernel, "dense" = materialized scores,
    # "auto" = flash where the kernel compiles natively (TPU), dense
    # elsewhere (interpret-mode flash would be slower than dense)
    attn_impl: str = "auto"
    # grouped-query attention: n_kv_heads < n_heads shares each K/V
    # head across n_heads/n_kv_heads query heads. None = MHA. The win
    # is decode bandwidth: the KV cache (and its per-step HBM reads —
    # the decode bottleneck) shrink by that factor; the cached-attention
    # einsums read the compact cache directly, never expanding it.
    n_kv_heads: Optional[int] = None
    # rotary context extension beyond the training length, for every
    # layer: "none" | "linear" (positions / rope_factor — Chen et al.
    # 2023) | "ntk" (base * factor^(dh/(dh-2)) — frequency interpolation
    # that keeps high-frequency dims intact) | "yarn" (Peng et al. 2023:
    # by lane, a blend of the frequency and the frequency / rope_factor
    # over a ramp between the lanes that turn `rope_beta_fast` and
    # `rope_beta_slow` times in `rope_original` positions, and cos and
    # sin times `rope_attention_factor`, 0.1 ln(rope_factor) + 1 where
    # None; training only). factor 1.0 = off for linear and ntk.
    rope_scaling: str = "none"
    rope_factor: float = 1.0
    rope_original: Optional[int] = None
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: Optional[float] = None
    # sliding-window (local) attention in every layer: each position
    # attends the last `attn_window` positions only (None = full
    # causal). The flash path skips out-of-band blocks in BOTH
    # directions (O(T*window) training and prefill); generate() decodes
    # over a ROLLING `window`-slot cache (O(window) memory and per-step
    # HBM reads, r5); beam and speculative decode keep full-length
    # band-masked buffers.
    attn_window: Optional[int] = None
    # attention kind by layer, read at trace time by the one block body:
    # `layer_types` names each layer's kind (one entry a layer) and
    # `attention_kinds` is the table ((name, AttentionKind), ...) that
    # gives each kind its window and its rotary scaling. With them the
    # four fields above that describe every layer stay at their
    # defaults. None: every layer is the kind those fields describe.
    # Training only (loss, score, apply): the decode helpers refuse it.
    layer_types: Optional[tuple] = None
    attention_kinds: Optional[tuple] = None
    remat: bool = False
    # fused chunked cross-entropy: loss() folds the LM-head matmul into
    # a checkpointed scan over `fused_ce_chunk`-position slices so the
    # [B*T, vocab] logits tensor never exists (forward keeps only the
    # per-position nll; backward recomputes each chunk's logits on the
    # MXU). None = plain path. Affects loss() only — apply()/score()/
    # decode still materialize logits where callers consume them.
    fused_ce_chunk: Optional[int] = None
    # decode KV-cache precision: "compute" stores K/V in the compute
    # dtype; "int8" stores s8 data + one scale per (position, kv-head)
    # (absmax over head_dim, LOSSY), quantized at write and dequantized
    # fused into each step's attention reads — the cache is the decode
    # bandwidth bottleneck that GROWS with context (weights are
    # constant), and s8+scale is ~1/2 the bytes of a bf16 cache at
    # head_dim 64. Covers generate()/sample() and the serving engine's
    # slot pool (serve.DecodeEngine); beam and speculative decode raise
    # (their window-attention path reads fp buffers).
    kv_cache_dtype: str = "compute"
    # sparsely-activated FFN (GLaM-style): every `moe_every`-th block
    # swaps its dense MLP for `moe_experts` experts with top-`moe_k`
    # routing; 0 experts = all-dense
    moe_experts: int = 0
    moe_every: int = 2
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # "topk" (GShard token-choice, needs the aux loss),
    # "expert_choice" (experts pick tokens: perfect balance, no aux) or
    # "dropless" (softmax over all `moe_experts`, top-`moe_k`
    # renormalised, gated-SiLU experts of width `moe_dim`, no capacity
    # and no aux term: parallel.moe.dropless_ffn). A dropless layer may
    # hold a share of the experts, `moe_held` of them from
    # `moe_held_first` (an expert-parallel chip's): it routes over all
    # and computes the terms of the experts it holds.
    moe_router: str = "topk"
    moe_dim: Optional[int] = None
    moe_held: Optional[int] = None
    moe_held_first: int = 0
    # a dropless layer's shared expert: a gated-SiLU expert of this width
    # that every position runs, its output scaled by sigmoid(h . w)
    # (`moe_shared_gate`; else added as it is) and added to the routed
    # sum (None: none)
    moe_shared_dim: Optional[int] = None
    moe_shared_gate: bool = True
    # the dropless router's score: "softmax" over all experts or
    # "sigmoid" an expert (the chosen weights renormalised either way),
    # times `moe_route_scale`. moe_expert_bias: each expert layer chooses
    # by score + a bias that is not a parameter but the training step's
    # state (`init_expert_bias`; `moe.update_expert_bias` moves it after
    # each step, auxiliary-loss-free balancing), and weights by the
    # unbiased score
    moe_score: str = "softmax"
    moe_route_scale: float = 1.0
    moe_expert_bias: bool = False
    # FFN kind by layer: the first `moe_dense_layers` blocks keep the
    # dense MLP whatever `moe_every` says. mlp: the dense MLP of width
    # mlp_ratio * dim, "gelu" (fc2(gelu(fc1 x))) or "swiglu"
    # (down(silu(gate x) * up x))
    moe_dense_layers: int = 0
    mlp: str = "gelu"
    # the sizes of a Gated DeltaNet layer (a kind whose mixer is
    # "gated_delta"): key heads and value heads (the key heads divide
    # the value heads), their widths, the causal depthwise convolution's
    # kernel over q, k and v
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    # descriptors of the block, read at trace time. norm: "layer"
    # (biased LayerNorm, eps 1e-5) or "rms" (RMSNorm, weight only, eps
    # `rms_eps`);
    # bias: whether the projections and the MLP carry one; head_size:
    # the width of a head where it is not dim // n_heads; qk_norm: an
    # RMSNorm over each head's lanes of q and of k before the rotation
    # (one weight vector each, shared by the heads); sandwich_norm: the
    # attention's and the FFN's outputs are normalised again before
    # their residuals (`post_ln1`, `post_ln2`); embed_scale: the
    # embedding rows times this on the way in (None: as they are).
    norm: str = "layer"
    bias: bool = True
    head_size: Optional[int] = None
    qk_norm: bool = False
    rms_eps: float = 1e-6
    sandwich_norm: bool = False
    embed_scale: Optional[float] = None

    def __post_init__(self):
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm must be 'layer' or 'rms', got "
                             f"{self.norm!r}")
        if (self.layer_types is None) != (self.attention_kinds is None):
            raise ValueError("layer_types and attention_kinds go together")
        if self.layer_types is not None:
            kinds = dict(self.attention_kinds)
            if (len(self.layer_types) != self.n_layers
                    or not set(self.layer_types) <= set(kinds)
                    or not all(isinstance(k, AttentionKind)
                               for k in kinds.values())):
                raise ValueError(
                    f"layer_types needs one entry a layer ({self.n_layers}), "
                    f"each a name of attention_kinds {sorted(kinds)}, got "
                    f"{self.layer_types}")
            if self.attn_window is not None or self.rope_scaling != "none":
                raise ValueError(
                    "with layer_types the window and the rotary scaling "
                    "are the kinds': leave attn_window and rope_scaling "
                    "unset")
            for kind in kinds.values():
                if kind.mixer not in MIXERS:
                    raise ValueError(f"mixer must be one of {MIXERS}, got "
                                     f"{kind.mixer!r}")
                if kind.mixer == "gated_delta" and not (
                        0 < self.gdn_key_heads
                        and self.gdn_value_heads % self.gdn_key_heads == 0):
                    raise ValueError(
                        "a gated_delta layer needs gdn_key_heads > 0 "
                        "dividing gdn_value_heads")
                rd = kind.rotary_dim
                if rd is not None and not (0 <= rd <= self.head_dim
                                           and rd % 2 == 0):
                    raise ValueError(f"rotary_dim {rd} must be even and in "
                                     f"[0, head_dim {self.head_dim}]")
        if self.moe_shared_dim is not None and self.moe_router != "dropless":
            raise ValueError("moe_shared_dim is the dropless layer's shared "
                             "expert (moe_router='dropless')")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(f"mlp must be 'gelu' or 'swiglu', got "
                             f"{self.mlp!r}")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score must be 'softmax' or 'sigmoid', "
                             f"got {self.moe_score!r}")
        if self.moe_router != "dropless" and (
                self.moe_score != "softmax" or self.moe_route_scale != 1.0
                or self.moe_expert_bias or not self.moe_shared_gate):
            raise ValueError("moe_score, moe_route_scale, moe_expert_bias "
                             "and moe_shared_gate describe the dropless "
                             "layer (moe_router='dropless')")
        if self.moe_router == "dropless":
            if not (self.moe_dim and 0 < self.experts_held
                    and 0 <= self.moe_held_first
                    and self.moe_held_first + self.experts_held
                    <= self.moe_experts and self.moe_k <= self.moe_experts):
                raise ValueError(
                    "a dropless MoE needs moe_dim, moe_k <= moe_experts and "
                    "the experts held inside [0, moe_experts)")
        elif (self.moe_held is not None or self.moe_held_first
              or self.moe_dim is not None):
            raise ValueError("moe_dim / moe_held describe the dropless "
                             "layer (moe_router='dropless')")

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        return self.dim // self.n_heads

    @property
    def attn_dim(self) -> int:
        """Width of the concatenated heads: what the output projection
        reads (dim itself unless `head_size` says otherwise)."""
        return self.n_heads * self.head_dim

    @property
    def experts_held(self) -> int:
        return self.moe_experts if self.moe_held is None else self.moe_held

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        if self.n_heads % kv != 0:
            raise ValueError(
                f"n_kv_heads {kv} must divide n_heads {self.n_heads}")
        return kv

    def attention_kind(self, i: Optional[int] = None) -> AttentionKind:
        """Layer i's window and rotary scaling: its kind's where the
        config has kinds by layer, else (and for i None) what the
        config's own fields say of every layer."""
        if self.layer_types is not None and i is not None:
            return dict(self.attention_kinds)[self.layer_types[i]]
        return AttentionKind(
            self.attn_window, self.rope_scaling, self.rope_factor,
            self.rope_original, self.rope_beta_fast, self.rope_beta_slow,
            self.rope_attention_factor)

    def is_moe_block(self, i: int) -> bool:
        return (self.moe_experts > 0 and i >= self.moe_dense_layers
                and i % self.moe_every == self.moe_every - 1)

    @property
    def moe_layers(self) -> tuple:
        """The indices of the expert layers, in order."""
        return tuple(i for i in range(self.n_layers) if self.is_moe_block(i))


def init_expert_bias(cfg: TransformerConfig):
    """The expert bias of a config with `moe_expert_bias` at its start:
    zeros [expert layers, moe_experts] float32, the training step's state
    beside the parameters (`loss_and_aux(expert_bias=...)`)."""
    return jnp.zeros((len(cfg.moe_layers), cfg.moe_experts), jnp.float32)


def init_params(rng, cfg: TransformerConfig):
    smart = initializers.smart_uniform()
    d, h = cfg.dim, cfg.mlp_ratio * cfg.dim
    ks = iter(jax.random.split(rng, 4 + 4 * cfg.n_layers))

    # fused projection width: H query heads + 2 * KV heads (GQA keys/
    # values are narrower when n_kv_heads < n_heads; 3*d exactly for MHA)
    qkv_w = (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim

    def norm(width):
        if cfg.norm == "rms":
            return {"scale": jnp.ones((width,))}
        return {"scale": jnp.ones((width,)), "offset": jnp.zeros((width,))}

    def dense(key, shape):
        if cfg.bias:
            return {"kernel": smart(key, shape),
                    "bias": jnp.zeros((shape[1],))}
        return {"kernel": smart(key, shape)}

    def gated_delta_params(k1, k2):
        """Projections to [q | k | v | z] and to [b | a], the convolution
        over [q | k | v], A_log = log U(1, 16) and dt_bias (the inverse
        softplus of U(0.001, 0.1)) by value head, the gated norm's
        weight, the output projection."""
        nk, nv = cfg.gdn_key_heads, cfg.gdn_value_heads
        kd, vd = nk * cfg.gdn_key_dim, nv * cfg.gdn_value_dim
        k_qkvz, k_ba, k_conv, k_a, k_dt = jax.random.split(k1, 5)
        lim = 1.0 / math.sqrt(cfg.gdn_conv)
        dt = jax.random.uniform(k_dt, (nv,), minval=1e-3, maxval=0.1)
        return {
            "qkvz": dense(k_qkvz, (d, 2 * kd + 2 * vd)),
            "ba": dense(k_ba, (d, 2 * nv)),
            "conv": {"kernel": jax.random.uniform(
                k_conv, (cfg.gdn_conv, 2 * kd + vd), minval=-lim,
                maxval=lim)},
            "A_log": jnp.log(jax.random.uniform(k_a, (nv,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "o_norm": {"scale": jnp.ones((cfg.gdn_value_dim,))},
            "proj": dense(k2, (vd, d)),
        }

    def block_params(i, k1, k2, k3, k4):
        kind = cfg.attention_kind(i)
        if kind.mixer == "gated_delta":
            p = {"ln1": norm(d), **gated_delta_params(k1, k2),
                 "ln2": norm(d)}
        else:
            # a gated output takes a second query-wide block of columns
            gate_w = cfg.attn_dim if kind.output_gate else 0
            p = {
                "ln1": norm(d),
                "qkv": dense(k1, (d, qkv_w + gate_w)),
                "proj": dense(k2, (cfg.attn_dim, d)),
                "ln2": norm(d),
            }
            if cfg.qk_norm:
                p["q_norm"] = {"scale": jnp.ones((cfg.head_dim,))}
                p["k_norm"] = {"scale": jnp.ones((cfg.head_dim,))}
        if cfg.is_moe_block(i):
            from paddle_tpu.parallel import moe

            if cfg.moe_router == "dropless":
                p["moe"] = moe.init_dropless_params(
                    k3, cfg.moe_experts, cfg.experts_held, d, cfg.moe_dim,
                    d_shared=cfg.moe_shared_dim,
                    shared_gate=cfg.moe_shared_gate)
            else:
                p["moe"] = moe.init_moe_params(k3, cfg.moe_experts, d, h)
        elif cfg.mlp == "swiglu":
            k_up = jax.random.fold_in(k3, 1)
            p["mlp"] = {"gate_proj": dense(k3, (d, h)),
                        "up_proj": dense(k_up, (d, h)),
                        "down_proj": dense(k4, (h, d))}
        else:
            p["fc1"] = dense(k3, (d, h))
            p["fc2"] = dense(k4, (h, d))
        if cfg.sandwich_norm:
            p["post_ln1"] = norm(d)
            p["post_ln2"] = norm(d)
        return p

    return {
        "embed": {"table": initializers.normal(0.02)(next(ks),
                                                     (cfg.vocab, d))},
        "blocks": [block_params(i, next(ks), next(ks), next(ks), next(ks))
                   for i in range(cfg.n_layers)],
        "ln_f": norm(d),
        "lm_head": {"kernel": smart(next(ks), (d, cfg.vocab))},
    }


def _norm(cfg: TransformerConfig, p, x):
    """The block's normalisation over the last axis, in float32: biased
    LayerNorm, or RMSNorm x * rsqrt(mean(x^2) + eps) * scale."""
    if cfg.norm == "rms":
        return _rms_norm(x, p["scale"], cfg.rms_eps)
    return norm_ops.layer_norm(x, p["scale"], p["offset"])


def _rms_norm(x, scale, eps: float = 1e-6):
    x32 = at_least_f32(x)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def require_decodable(cfg: TransformerConfig) -> None:
    """The decode helpers (generate, beam, speculative, the serving
    engine) serve the biased-LayerNorm block with dim // n_heads heads,
    one window and one rotary scaling for every layer: their head and
    caches are written for it. A config they cannot serve yet is
    refused here rather than mis-shaped: among them a gated_delta mixer
    (its cache would be a recurrent state, which no decode path keeps),
    a gated attention output, partial rotary or none (NoPE), a sigmoid
    router, an expert bias, an ungated shared expert, leading dense
    layers, a gated-SiLU MLP, sandwich norms and an embedding
    multiplier."""
    kinds = dict(cfg.attention_kinds or ())
    block = {"sigmoid router": cfg.moe_score == "sigmoid",
             "expert bias": cfg.moe_expert_bias,
             "ungated shared expert": not cfg.moe_shared_gate,
             "leading dense layers": cfg.moe_dense_layers > 0,
             "gated-SiLU MLP": cfg.mlp == "swiglu",
             "sandwich norms": cfg.sandwich_norm,
             "embedding multiplier": cfg.embed_scale is not None}
    new = sorted({"gated_delta mixer" for k in kinds.values()
                  if k.mixer == "gated_delta"}
                 | {"output gate" for k in kinds.values() if k.output_gate}
                 | {"partial rotary" for k in kinds.values()
                    if k.rotary_dim}
                 | {"NoPE" for k in kinds.values() if k.rotary_dim == 0}
                 | {name for name, has in block.items() if has})
    if new:
        raise NotImplementedError(
            f"decoding is not implemented for a {', '.join(new)}: this "
            "model trains through loss() only")
    if (cfg.norm != "layer" or not cfg.bias or cfg.qk_norm
            or cfg.head_size is not None or cfg.moe_router == "dropless"
            or cfg.layer_types is not None or cfg.rope_scaling == "yarn"):
        raise NotImplementedError(
            "decoding is not implemented for this block (RMSNorm, "
            "bias-free projections, QK-norm, an explicit head size, a "
            "dropless / partly held MoE, attention kinds by layer "
            "(layer_types: caches of two sizes in one model) or YaRN "
            "rotary scaling): it trains through loss() and "
            "block_diffusion_loss() only")


def _rope(x, positions, base: float, scaling: str = "none",
          factor: float = 1.0, *, original: Optional[int] = None,
          beta_fast: float = 32.0, beta_slow: float = 1.0,
          attention_factor: Optional[float] = None,
          rotary_dim: Optional[int] = None):
    """Rotary embedding. x: [B,T,H,Dh] (Dh even), positions: [B,T].
    rotary_dim: the first `rotary_dim` lanes of each head turn, at the
    frequencies of a head that wide, and the rest pass (None: all).

    scaling extends usable context past the training length without new
    parameters: "linear" compresses positions by `factor` (every
    frequency slows uniformly); "ntk" rescales the BASE so low
    frequencies stretch while the highest stay near-intact (usually
    degrades short-context quality less); "yarn" (Peng et al. 2023)
    slows by `factor` only the lanes that turn fewer than `beta_slow`
    times in `original` positions, keeps those that turn more than
    `beta_fast` times, blends linearly by lane between the two, and
    multiplies cos and sin by `attention_factor` (0.1 ln(factor) + 1
    where None), so the scores carry its square. rotary_dim 0: x as it
    is (NoPE)."""
    if rotary_dim == 0:
        return x
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = _rope(x[..., :rotary_dim], positions, base, scaling, factor,
                       original=original, beta_fast=beta_fast,
                       beta_slow=beta_slow, attention_factor=attention_factor)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    dh = x.shape[-1]
    if scaling not in ("none", "linear", "ntk", "yarn"):
        raise ValueError(
            f"rope_scaling must be none|linear|ntk|yarn, got {scaling!r}")
    if factor <= 0:
        raise ValueError(f"rope_factor must be > 0, got {factor}")
    if scaling == "linear" and factor != 1.0:
        positions = positions / factor
    elif scaling == "ntk" and factor != 1.0:
        base = base * factor ** (dh / max(dh - 2, 1))
    freqs = base ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    if scaling == "yarn":
        if not original:
            raise ValueError("yarn needs rope_original, the context the "
                             "frequencies were trained at")

        def lane(turns):    # the lane that turns `turns` times in `original`
            return dh * math.log(original / (2 * math.pi * turns)) / (
                2 * math.log(base))

        low = max(math.floor(lane(beta_fast)), 0)
        high = min(math.ceil(lane(beta_slow)), dh - 1)
        ramp = jnp.clip((jnp.arange(dh // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
        if attention_factor is None:
            attention_factor = 0.1 * math.log(max(factor, 1.0)) + 1.0
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,Dh/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    dtype = x.dtype
    if scaling == "yarn":
        # float32 through the rotation: bfloat16 has no 1.2773, and on
        # every lane slow enough that cos is 1 at all positions the
        # factor would read 1.2734, q and k 0.3% short on half their
        # lanes in every full layer (PERF.md section 6, PR 36)
        cos, sin = cos * attention_factor, sin * attention_factor
        x = at_least_f32(x)
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dtype)


def block_diffusion_mask(length: int, bd: int):
    """[2L, 2L] bool, rows queries: the block-diffusion training mask
    over [noised copy ; clean copy] (ops.flash_attention._pair_mask
    has the table). For the dense path and small sizes."""
    pos = jnp.arange(2 * length, dtype=jnp.int32)
    noised, blk = pos < length, (pos % length) // bd
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(kn, qn & (kb == qb), jnp.where(qn, kb < qb, kb <= qb))


def _dense_attention(q, k, v, causal: bool, key_mask=None,
                     window=None, block_diffusion=None):
    """Exact reference attention; [B,T,H,Dh] in/out, f32 scores.
    key_mask: optional [B, Tk] bool, False keys are never attended.
    window: sliding-window band (causal only). block_diffusion: (L, Bd),
    the mask of `block_diffusion_mask` in place of the causal one."""
    if window is not None and not causal:
        # identical failure to ops.flash_attention's — the two backends
        # must not disagree for the same config (r4 advisor finding:
        # this path used to silently run FULL attention instead)
        raise ValueError("window requires causal=True")
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    scores = at_least_f32(scores)
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        if window is not None:
            qpos = jnp.arange(tq, dtype=jnp.int32)[:, None] + (tk - tq)
            mask = mask & (qpos - jnp.arange(
                tk, dtype=jnp.int32)[None, :] < window)
        scores = jnp.where(mask, scores, -1e30)
    if block_diffusion is not None:
        scores = jnp.where(block_diffusion_mask(*block_diffusion), scores,
                           -1e30)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _expand_kv(q, k, v):
    """Broadcast compact GQA K/V ([B,T,Hkv,Dh]) to q's full head count
    for attention impls that require matching heads (dense, flash,
    ring/Ulysses). One-shot paths only — the decode cache path never
    expands (that's GQA's whole win)."""
    h, hkv = q.shape[2], k.shape[2]
    if hkv == h:
        return k, v
    g = h // hkv
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


def _attention(cfg: TransformerConfig, q, k, v, causal: bool,
               key_mask=None, key_lens=None, block_diffusion=None,
               kind: Optional[AttentionKind] = None):
    """key_lens [B] describes RIGHT-padded rows (keys [0, lens[b]) are
    real) and rides the flash kernel's per-row bound; key_mask [B, Tk]
    is an arbitrary mask and forces the dense path. They are two
    encodings of a mask, not composable — pass exactly one.

    q, k, v are cast to the policy's compute dtype here, at the one
    door of both implementations: a biased `dense` returns float32
    under a bf16 policy (float32 bias), which no other matmul of the
    model is handed. Before the expansion, so the head repeat, the
    transposes, the pad and the kernel's k/v fetches move the narrow
    bytes too; the caches keep what `_block_parts` returns.

    block_diffusion (L, Bd): the training mask over [noised ; clean]
    copies in place of the causal one (`causal` is then not read).
    kind: the layer's own window where the config has kinds by layer
    (None: `cfg.attn_window`)."""
    q, k, v = default_policy().cast_to_compute(q, k, v)
    k, v = _expand_kv(q, k, v)
    if key_mask is not None and key_lens is not None:
        raise ValueError("pass key_mask or key_lens, not both — the "
                         "flash path would honor only key_lens and "
                         "silently diverge from dense for any mask "
                         "that isn't right-padding")
    impl = cfg.attn_impl
    if impl == "auto":
        # flash ONLY where the Pallas kernel compiles natively and the
        # program lowers for one device (pallas_util.auto_kernel);
        # anywhere else interpret-mode emulation would be far slower
        # than dense, and a partitioned jit cannot hold the kernel
        impl = "flash" if pallas_util.auto_kernel() else "dense"
    window = cfg.attn_window if kind is None else kind.window
    if impl == "flash" and key_mask is not None:
        impl = "dense"      # arbitrary masks: the ONE dense path below
    pallas_util.note_traced("transformer.attention", impl)
    pallas_util.note_traced("transformer.attention.operands", str(q.dtype))
    if block_diffusion is not None:
        if key_mask is not None or key_lens is not None or window is not None:
            raise ValueError("block_diffusion attention takes no key mask, "
                             "key lengths or window")
        if impl == "flash":
            return flash_attention(q, k, v, block_diffusion=block_diffusion)
        return _dense_attention(q, k, v, False,
                                block_diffusion=block_diffusion)
    if impl == "flash":
        if key_lens is not None:
            # right-padded variable-length rows ride the kernel's
            # per-row key-length bound — a long variable-length prefill
            # keeps O(T·block) memory instead of falling back to the
            # [B,H,Tq,Tk] dense score tensor
            return flash_attention(q, k, v, causal=causal,
                                   key_lens=key_lens, window=window)
        return flash_attention(q, k, v, causal=causal, window=window)
    # arbitrary key masks take the dense path — ONE dense
    # implementation decides both masked and unmasked prefills;
    # lens-only callers get the equivalent right-padding mask here
    if key_mask is None and key_lens is not None:
        key_mask = jnp.arange(
            k.shape[1], dtype=jnp.int32)[None, :] < key_lens[:, None]
    return _dense_attention(q, k, v, causal, key_mask, window)


def _ffn(cfg: TransformerConfig, p, y, token_mask=None):
    """The block's position-wise FFN: dense MLP (tanh-GELU, or gated
    SiLU where the block carries `mlp`) or MoE when the block carries
    expert params. Returns (out, aux_loss). token_mask [B, T] keeps
    padding from claiming expert capacity."""
    if "moe" in p:
        from paddle_tpu.parallel import moe

        b, t, d = y.shape
        flat_mask = None if token_mask is None else token_mask.reshape(b * t)
        if cfg.moe_router == "dropless":
            pallas_util.note_traced("transformer.ffn", "moe_dropless")
            out = moe.dropless_ffn(
                p["moe"], y.reshape(b * t, d), k=cfg.moe_k,
                first_held=cfg.moe_held_first, token_mask=flat_mask,
                score=cfg.moe_score, route_scale=cfg.moe_route_scale)
            return out.y.reshape(b, t, d), out.stats
        if cfg.moe_router == "expert_choice":
            out = moe.expert_choice_ffn(
                p["moe"], y.reshape(b * t, d),
                capacity_factor=cfg.moe_capacity_factor,
                token_mask=flat_mask)
        elif cfg.moe_router == "topk":
            out = moe.moe_ffn(p["moe"], y.reshape(b * t, d), k=cfg.moe_k,
                              capacity_factor=cfg.moe_capacity_factor,
                              token_mask=flat_mask)
        else:
            raise ValueError(
                f"moe_router must be 'topk', 'expert_choice' or "
                f"'dropless', got {cfg.moe_router!r}")
        return out.y.reshape(b, t, d), out.aux_loss
    if "mlp" in p:
        pallas_util.note_traced("transformer.ffn", "dense_swiglu")
        m = p["mlp"]
        dense = lambda a, w: linalg.dense(a, w["kernel"], w.get("bias"))
        hidden = (jax.nn.silu(at_least_f32(dense(y, m["gate_proj"])))
                  * at_least_f32(dense(y, m["up_proj"]))).astype(y.dtype)
        return (dense(hidden, m["down_proj"]), jnp.zeros((), jnp.float32))
    y = jax.nn.gelu(linalg.dense(y, p["fc1"]["kernel"], p["fc1"].get("bias")))
    return (linalg.dense(y, p["fc2"]["kernel"], p["fc2"].get("bias")),
            jnp.zeros((), jnp.float32))


def _causal_conv(x, kernel):
    """Causal depthwise convolution over time: x [B, T, C], kernel [K, C]
    -> out[t] = sum_i kernel[i] x[t - (K - 1) + i] (zeros before the
    start), in float32."""
    width = kernel.shape[0]
    xp = jnp.pad(at_least_f32(x), ((0, 0), (width - 1, 0), (0, 0)))
    t = x.shape[1]
    return sum(xp[:, i:i + t] * kernel[i].astype(jnp.float32)
               for i in range(width))


def _l2_normalize(x, eps=1e-6):
    x = at_least_f32(x)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


# the gated delta rule's implementation by the config's `attn_impl`, the
# one switch of the token mixers' kernels
_GATED_DELTA_IMPL = {"auto": "auto", "flash": "pallas", "dense": "jnp"}


def _gated_delta_mixer(cfg: TransformerConfig, p, y):
    """A Gated DeltaNet layer's token mixer (Qwen3-Next), y [B, T, D]
    normalised -> [B, T, D]: [q | k | v | z] and [b | a] from y; a causal
    depthwise convolution of [q | k | v] and SiLU; beta = sigmoid(b),
    the log decay g = -exp(A_log) softplus(a + dt_bias) by value head in
    float32; q and k L2-normalised over their lanes, q scaled by
    dk^-1/2; the gated delta rule (`ops.gated_delta`); RMSNorm of each
    head's output (one weight, shared) times silu(z); the output
    projection."""
    b, t, _ = y.shape
    nk, nv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    policy = default_policy()
    qkvz = linalg.dense(y, p["qkvz"]["kernel"], p["qkvz"].get("bias"))
    ba = at_least_f32(linalg.dense(y, p["ba"]["kernel"], p["ba"].get("bias")))
    mixed = jax.nn.silu(_causal_conv(qkvz[..., :2 * nk * dk + nv * dv],
                                     p["conv"]["kernel"]))
    q = _l2_normalize(mixed[..., :nk * dk].reshape(b, t, nk, dk)) * dk ** -0.5
    k = _l2_normalize(mixed[..., nk * dk:2 * nk * dk].reshape(b, t, nk, dk))
    v = mixed[..., 2 * nk * dk:].reshape(b, t, nv, dv)
    z = qkvz[..., 2 * nk * dk + nv * dv:].reshape(b, t, nv, dv)
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., nv:] + p["dt_bias"].astype(jnp.float32))
    q, k, v = policy.cast_to_compute(q, k, v)
    o = gated_delta_rule(q, k, v, g, beta,
                         impl=_GATED_DELTA_IMPL[cfg.attn_impl])
    o = _rms_norm(at_least_f32(o), p["o_norm"]["scale"],
                  cfg.rms_eps) * jax.nn.silu(
        at_least_f32(z))
    return linalg.dense(o.reshape(b, t, nv * dv).astype(policy.compute_dtype),
                        p["proj"]["kernel"], p["proj"].get("bias"))


def _block_parts(cfg: TransformerConfig, p, x, positions, attn_fn,
                 token_mask=None, kind: Optional[AttentionKind] = None):
    """One pre-LN block with a pluggable attention: attn_fn(q, k, v) ->
    [B,T,H,Dh]. The ONE definition of the block body — apply(), the
    decode prefill and the KV-cache step all run THIS code, so a model
    change cannot silently diverge between train and decode. Returns
    (x_out, k, v, aux) so cache builders can keep the rotated K/V and
    training can collect the MoE load-balance aux loss. Under GQA both
    attn_fn and the return see COMPACT K/V ([B,T,Hkv,Dh]): caches store
    that form and the cached attention reads it directly; full-H paths
    (_attention's dense/flash, external ring/Ulysses fns) expand at
    their own entry (`_expand_kv`). kind: the layer's rotary scaling
    where the config has kinds by layer (None: the config's own; the
    window is `attn_fn`'s business). A kind whose mixer is "gated_delta"
    runs `_gated_delta_mixer` in the attention's place and returns no
    k, v (None). Under `cfg.sandwich_norm` each branch's output is
    normalised again (`post_ln1`, `post_ln2`) before its residual."""
    b, t, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    kind = kind if kind is not None else cfg.attention_kind()
    post = lambda name, out: out
    if cfg.sandwich_norm:
        pallas_util.note_traced("transformer.post_norm", "sandwich")
        post = lambda name, out: _norm(cfg, p[name], out)
    y = _norm(cfg, p["ln1"], x)
    if kind.mixer == "gated_delta":
        k = v = None
        x = x + post("post_ln1", _gated_delta_mixer(cfg, p, y))
    else:
        rope = functools.partial(
            _rope, positions=positions, base=cfg.rope_base,
            scaling=kind.rope_scaling, factor=kind.rope_factor,
            original=kind.rope_original, beta_fast=kind.rope_beta_fast,
            beta_slow=kind.rope_beta_slow,
            attention_factor=kind.rope_attention_factor,
            rotary_dim=kind.rotary_dim)
        qkv = linalg.dense(y, p["qkv"]["kernel"], p["qkv"].get("bias"))
        q = qkv[..., :h * dh].reshape(b, t, h, dh)
        # [q | gate | k | v] where the kind gates its output
        kv0 = 2 * h * dh if kind.output_gate else h * dh
        k = qkv[..., kv0:kv0 + hkv * dh].reshape(b, t, hkv, dh)
        v = qkv[..., kv0 + hkv * dh:].reshape(b, t, hkv, dh)
        if cfg.qk_norm:
            q, k = _norm(cfg, p["q_norm"], q), _norm(cfg, p["k_norm"], k)
        q, k = rope(q), rope(k)
        a = attn_fn(q, k, v).reshape(b, t, cfg.attn_dim)
        if kind.output_gate:
            pallas_util.note_traced("transformer.attention.gate", "sigmoid")
            gate = jax.nn.sigmoid(at_least_f32(qkv[..., h * dh:kv0]))
            a = (at_least_f32(a) * gate).astype(a.dtype)
        x = x + post("post_ln1", linalg.dense(a, p["proj"]["kernel"],
                                              p["proj"].get("bias")))
    y = _norm(cfg, p["ln2"], x)
    out, aux = _ffn(cfg, p, y, token_mask)
    return x + post("post_ln2", out), k, v, aux


def _block(cfg: TransformerConfig, p, x, positions, token_mask=None,
           attn_fn=None, block_diffusion=None,
           kind: Optional[AttentionKind] = None):
    if attn_fn is None:
        attn_fn = lambda q, k, v: _attention(
            cfg, q, k, v, causal=True, block_diffusion=block_diffusion,
            kind=kind)
    elif block_diffusion is not None:
        raise ValueError("block_diffusion rides the config's own attention")
    else:
        # external impls (ring/Ulysses context parallelism) expect
        # matching head counts — expand compact GQA K/V at their door
        inner = attn_fn
        attn_fn = lambda q, k, v: inner(q, *_expand_kv(q, k, v))
    out, _, _, aux = _block_parts(cfg, p, x, positions, attn_fn,
                                  token_mask, kind)
    return out, aux


def _rope_label(kind: AttentionKind) -> str:
    """The rotary embedding of a kind, for the `transformer.rope` counter:
    its scaling, `partial_<lanes>` where it turns some lanes only, and
    `nope` where it turns none."""
    if kind.rotary_dim == 0:
        return "nope"
    if kind.rotary_dim is None:
        return kind.rope_scaling
    partial = f"partial_{kind.rotary_dim}"
    return partial if kind.rope_scaling == "none" else (
        f"{kind.rope_scaling}_{partial}")


def _remat_block(*args):
    """`_block` as `cfg.remat` checkpoints it. Notes, where the block
    is traced, what the checkpoint keeps of it beside its input: that
    follows from the attention the trace took (the flash kernel names
    its outputs or nobody does)."""
    flash = "transformer.attention=flash"
    before = pallas_util.traced().get(flash, 0)
    out = _block(*args)
    ran = pallas_util.traced().get(flash, 0) > before
    pallas_util.note_traced(
        "transformer.remat_saved", ",".join(REMAT_SAVED) if ran else "none")
    return out


def _forward(params, cfg: TransformerConfig, tokens, positions=None,
             token_mask=None, attn_fn=None, return_hidden=False,
             block_diffusion=None, expert_bias=None):
    """tokens [B,T] int32 -> (logits [B,T,V], summed MoE aux loss; for
    a dropless MoE its `DroplessStats`, stacked over the layers).
    token_mask [B,T] bool marks real (non-padding) positions for MoE
    capacity accounting. attn_fn overrides the config's attention (the
    context-parallel builder injects ring/Ulysses attention here).
    return_hidden=True skips the LM-head matmul and returns the final
    post-norm hidden [B,T,D] instead (the fused-CE loss path folds the
    head into its chunked scan). expert_bias [expert layers, E]: the
    step's expert bias where the config has `moe_expert_bias` (row j is
    the j-th expert layer's; `init_expert_bias`)."""
    if cfg.moe_expert_bias:
        if expert_bias is None:
            raise ValueError("moe_expert_bias: the experts are chosen by "
                             "the step's expert bias; pass expert_bias "
                             "(init_expert_bias(cfg) at the start)")
        # the bias rides each expert layer's params into its block: it
        # only chooses, so no gradient reaches it
        moe_at = {i: j for j, i in enumerate(cfg.moe_layers)}
        params = {**params, "blocks": [
            {**p, "moe": {**p["moe"], "expert_bias": jax.lax.stop_gradient(
                expert_bias[moe_at[i]])}} if i in moe_at else p
            for i, p in enumerate(params["blocks"])]}
    elif expert_bias is not None:
        raise ValueError("expert_bias needs a config with moe_expert_bias")
    policy = default_policy()
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    if cfg.embed_scale is not None:
        x = at_least_f32(x) * cfg.embed_scale
    x = x.astype(policy.compute_dtype)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
    blk = _block
    if cfg.remat:
        # the block keeps its input and what the flash kernel names (its
        # output and log-sum-exp): the backward pass recomputes the block
        # around the kernel, not the kernel. cfg, attn_fn, the mask and
        # the layer's kind are static (non-pytree) arguments
        blk = jax.checkpoint(
            _remat_block, static_argnums=(0, 5, 6, 7),
            policy=jax.checkpoint_policies.save_only_these_names(
                *REMAT_SAVED))
    kinds = [cfg.attention_kind(i) for i in range(cfg.n_layers)]
    if cfg.layer_types is not None:
        names = list(dict.fromkeys(cfg.layer_types))
        pallas_util.note_traced("transformer.layer_kinds", ",".join(
            f"{n}:{cfg.layer_types.count(n)}" for n in names))
        by_name = dict(cfg.attention_kinds)
        pallas_util.note_traced("transformer.mixer", ",".join(
            f"{n}:{by_name[n].mixer}" for n in names))
        pallas_util.note_traced("transformer.rope", ",".join(
            f"{n}:{_rope_label(by_name[n])}" for n in names
            if by_name[n].mixer == "attention"))
    auxes = []
    for p, kind in zip(params["blocks"], kinds):
        x, a = blk(cfg, p, x, positions, token_mask, attn_fn,
                   block_diffusion, kind)
        auxes.append(a)
    if cfg.moe_experts > 0 and cfg.moe_router == "dropless":
        moe_blocks = [a for i, a in enumerate(auxes) if cfg.is_moe_block(i)]
        aux = jax.tree.map(lambda *xs: jnp.stack(xs), *moe_blocks)
    else:
        aux = sum(auxes, jnp.zeros((), jnp.float32))
    x = _norm(cfg, params["ln_f"], x)
    if return_hidden:
        return x, aux
    return linalg.matmul(x, params["lm_head"]["kernel"]), aux


def apply(params, cfg: TransformerConfig, tokens, positions=None,
          expert_bias=None):
    """tokens [B,T] int32 -> logits [B,T,V]."""
    return _forward(params, cfg, tokens, positions,
                    expert_bias=expert_bias)[0]


def loss_and_aux(params, cfg: TransformerConfig, tokens, lengths=None,
                 attn_fn=None, expert_bias=None):
    """`loss()` and the forward's auxiliary output: for a dropless MoE
    its `DroplessStats`, stacked over the layers (what
    `moe.count_dropless_stats` adds to the timeline's `moe.*` counters,
    as `block_diffusion_loss` hands them back, and whose `route_counts`
    `moe.update_expert_bias` reads where the config has
    `moe_expert_bias`); for the other routers the summed load-balance
    term, already in the loss. expert_bias: the step's, as `_forward`
    takes it."""
    tmask = None
    if lengths is not None:
        tmask = jnp.arange(
            tokens.shape[1] - 1, dtype=jnp.int32)[None, :] < lengths[:, None]
    targets = tokens[:, 1:]
    if cfg.fused_ce_chunk:
        hid, aux = _forward(params, cfg, tokens[:, :-1], token_mask=tmask,
                            attn_fn=attn_fn, return_hidden=True,
                            expert_bias=expert_bias)
        nll = losses_ops.chunked_lm_head_nll(
            hid, params["lm_head"]["kernel"], targets,
            chunk=cfg.fused_ce_chunk)
    else:
        logits, aux = _forward(params, cfg, tokens[:, :-1],
                               token_mask=tmask, attn_fn=attn_fn,
                               expert_bias=expert_bias)
        lse = jax.nn.logsumexp(at_least_f32(logits), axis=-1)
        gold = jnp.take_along_axis(
            at_least_f32(logits), targets[..., None], axis=-1)[..., 0]
        nll = lse - gold
    if lengths is None:
        ce = jnp.mean(nll)
    else:
        mask = jnp.arange(
            1, tokens.shape[1], dtype=jnp.int32)[None, :] < lengths[:, None]
        ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    if cfg.moe_experts > 0 and cfg.moe_router != "dropless":
        ce = ce + cfg.moe_aux_weight * aux
    return ce, aux


def loss(params, cfg: TransformerConfig, tokens, lengths=None,
         attn_fn=None, expert_bias=None):
    """Next-token cross entropy over tokens [B, T+1] (+ the weighted
    load-balance term where the config has "topk" or "expert_choice"
    experts; a dropless layer has none); positions >= lengths are masked
    out of the CE term AND of the experts' routing. Every layer attends
    by its own kind where the config gives kinds by layer.
    `loss_and_aux` also returns the forward's auxiliary output."""
    return loss_and_aux(params, cfg, tokens, lengths, attn_fn,
                        expert_bias)[0]


def block_diffusion_noise(rng, tokens, block_length: int, *,
                          eps: float = 1e-3):
    """Draws the noise of `block_diffusion_loss` for tokens [B, L]: one
    t ~ U(0, 1) for each (sequence, block of `block_length` tokens),
    p = eps + (1 - eps) t (the linear schedule), and each token masked
    independently with its block's p. -> (masked [B, L] bool,
    p [B, L] float32)."""
    b, length = tokens.shape
    if length % block_length:
        raise ValueError(f"block_length {block_length} must divide the "
                         f"sequence length {length}")
    k_t, k_m = jax.random.split(rng)
    t = jax.random.uniform(k_t, (b, length // block_length), jnp.float32)
    p = jnp.repeat(eps + (1.0 - eps) * t, block_length, axis=1)
    return jax.random.uniform(k_m, (b, length), jnp.float32) < p, p


def block_diffusion_loss(params, cfg: TransformerConfig, tokens, masked, p,
                         *, block_length: int, mask_id: Optional[int] = None):
    """The vectorised block-diffusion objective (BD3-LM, Arriola et al.
    2025; what SDAR trains with). tokens [B, L] is the clean sequence
    x0, masked [B, L] bool which tokens are replaced by `mask_id`
    (default: the last id of the vocabulary) in the noised copy xt, and
    p [B, L] float the masking probability each token was drawn with
    (constant over a block). The model sees 2L positions, ids [xt ; x0]
    at position ids [0..L-1 ; 0..L-1], under the block-diffusion
    attention mask, and the loss reads the noised half only, at the
    masked positions, each weighted 1/p, with no shift:

        sum over masked (b, i) of nll(b, i) / p(b, i)  /  (B L)

    The noise is data: deterministic in its arguments
    (`block_diffusion_noise` draws it). -> (loss, aux); aux is the
    forward's: for a dropless MoE its per-layer `DroplessStats`."""
    b, length = tokens.shape
    if mask_id is None:
        mask_id = cfg.vocab - 1
    with jax.named_scope("block_diffusion_loss"):
        noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
        ids = jnp.concatenate([noised, tokens], axis=1)
        pos = jnp.broadcast_to(
            jnp.tile(jnp.arange(length, dtype=jnp.int32), 2), ids.shape)
        bd = (length, block_length)
        if cfg.fused_ce_chunk:
            hid, aux = _forward(params, cfg, ids, pos, return_hidden=True,
                                block_diffusion=bd)
            nll = losses_ops.chunked_lm_head_nll(
                hid[:, :length], params["lm_head"]["kernel"], tokens,
                chunk=cfg.fused_ce_chunk)
        else:
            logits, aux = _forward(params, cfg, ids, pos, block_diffusion=bd)
            logits = at_least_f32(logits[:, :length])
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, tokens[..., None], axis=-1)[..., 0]
        weight = masked.astype(jnp.float32) / p.astype(jnp.float32)
        return jnp.sum(nll * weight) / (b * length), aux


def score(params, cfg: TransformerConfig, tokens, lengths=None):
    """Per-token next-token log-probabilities [B, T-1] (0 past each
    row's length) and per-sequence mean NLL [B] — the perplexity /
    rescoring surface (reference analog: the v1 SequenceGenerator's
    sequence scores)."""
    tmask = None
    if lengths is not None:
        # pads must not claim MoE expert capacity (same as loss())
        tmask = jnp.arange(
            tokens.shape[1] - 1, dtype=jnp.int32)[None, :] < lengths[:, None]
    targets = tokens[:, 1:]
    if cfg.fused_ce_chunk:
        # gold log-prob is exactly -(nll): the chunked scan gives it
        # without materializing [B, T, V] log-probs (long-document
        # rescoring at 8k+ otherwise pays the same 4 GiB round-trip
        # the fused loss() avoids)
        hid, _ = _forward(params, cfg, tokens[:, :-1], token_mask=tmask,
                          return_hidden=True)
        gold = -losses_ops.chunked_lm_head_nll(
            hid, params["lm_head"]["kernel"], targets,
            chunk=cfg.fused_ce_chunk)
    else:
        logits, _ = _forward(params, cfg, tokens[:, :-1],
                             token_mask=tmask)
        logp = jax.nn.log_softmax(at_least_f32(logits), axis=-1)
        gold = jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0]
    if lengths is None:
        mask = jnp.ones_like(gold, bool)
    else:
        mask = jnp.arange(
            1, tokens.shape[1], dtype=jnp.int32)[None, :] < lengths[:, None]
    gold = jnp.where(mask, gold, 0.0)
    n = jnp.maximum(jnp.sum(mask, axis=1), 1)
    return gold, -jnp.sum(gold, axis=1) / n


def make_context_parallel_loss(cfg: TransformerConfig, mesh, *,
                               kind: str = "ring",
                               batch_axis: Optional[str] = None):
    """Context parallelism for the flagship LM: sequence-shard the
    tokens over the mesh `seq` axis and run every attention layer as
    ring (or Ulysses) attention — exact causal attention where no
    device ever holds the full sequence's K/V (parallel/ring_attention
    .py). Position-wise layers partition automatically under jit.

    Returns loss_fn(params, tokens, lengths=None). Feed tokens of
    length n*seq_shards + 1 (the loss slices one off for targets and
    the sharded attention needs T % seq_shards == 0).
    """
    from paddle_tpu import parallel as par

    if any(cfg.attention_kind(i).mixer != "attention"
           for i in range(cfg.n_layers)):
        raise ValueError(
            "a gated_delta mixer is not supported under context "
            "parallelism: its recurrent state crosses the sequence shards, "
            "and the ring/Ulysses attention stands in for softmax "
            "attention alone")
    if any(cfg.attention_kind(i).window is not None
           for i in range(cfg.n_layers)):
        raise ValueError(
            "attn_window (of the config or of a layer's kind) is not "
            "supported under context parallelism: "
            "the ring/Ulysses attention has no sliding-band plumbing, "
            "and silently training full-attention would diverge from "
            "every other (windowed) path")
    attn = par.make_sequence_parallel_attention(
        mesh, kind=kind, causal=True, batch_axis=batch_axis)

    def loss_fn(params, tokens, lengths=None):
        return loss(params, cfg, tokens, lengths, attn_fn=attn)

    return loss_fn


def _int8_step_params(params):
    """Weight-only int8 streaming hook shared by every decode path:
    returns (full_params, step_params) where full_params is the
    dequantized tree for one-shot prefills and step_params(vary)
    re-traces the dequant INSIDE a loop body. `vary` must be a
    loop-VARYING array (the current token(s)): the optimization_barrier
    keyed on it makes the dequant non-invariant, so XLA's while-loop
    LICM cannot hoist the size-inflating convert back out and the loop
    streams the s8 weights (1/4 the bytes — the decode bottleneck).
    Identity (zero-cost) for unquantized params."""
    from paddle_tpu.serve import quant as _quant

    if _quant.has_quantized(params):
        qp = params

        def step_params(vary):
            return _quant.dequantize_params(
                jax.lax.optimization_barrier((qp, vary))[0])

        return _quant.dequantize_params(qp), step_params
    return params, lambda vary: params


def _head(params, x_last):
    """Final LN + LM head over the last dim: [..., D] -> [..., V]
    (used on [B, D] last-position activations and [B, W, D] windows —
    ONE definition so a head change reaches every decode path)."""
    x_last = norm_ops.layer_norm(x_last, params["ln_f"]["scale"],
                                 params["ln_f"]["offset"])
    return linalg.matmul(x_last, params["lm_head"]["kernel"])


def _prefill_kv(params, cfg: TransformerConfig, toks, total: int):
    """Run `toks` [B, W] through the stack with plain causal attention
    and return per-block `total`-slot K/V buffers filled at [:, :W] —
    the shared prefill of the speculative and beam decoders (generate's
    prefill stays separate: it also threads prompt_lens/MoE masks)."""
    require_decodable(cfg)
    policy = default_policy()
    b, w = toks.shape
    x = jnp.take(params["embed"]["table"], toks, axis=0)
    x = x.astype(policy.compute_dtype)
    pos = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32), (b, w))
    caches = []
    for blk in params["blocks"]:
        x, k, v, _ = _block_parts(
            cfg, blk, x, pos,
            lambda q, k_, v_: _attention(cfg, q, k_, v_, causal=True))
        caches.append((
            jnp.zeros((b, total) + k.shape[2:], k.dtype)
            .at[:, :w].set(k),
            jnp.zeros((b, total) + v.shape[2:], v.dtype)
            .at[:, :w].set(v)))
    return caches


def _window_forward(p, c: TransformerConfig, caches, toks, start, total):
    """Process `toks` [1, W] at positions start..start+W-1 through the
    cached stack; returns (logits [1, W, V], new caches). Shared by the
    speculative decoders (greedy + sampling)."""
    policy = default_policy()
    w = toks.shape[1]
    x = jnp.take(p["embed"]["table"], toks, axis=0)
    x = x.astype(policy.compute_dtype)
    pos = start + jnp.arange(w, dtype=jnp.int32)[None, :]
    ar = jnp.arange(total, dtype=jnp.int32)[None, :]
    # window position j sees cache slots <= start + j (and within the
    # sliding-attention band when configured)
    qpos = (start + jnp.arange(w, dtype=jnp.int32))[None, :, None]
    if c.attn_window is not None:
        valid = _band_valid(ar[None, :, :], qpos, c.attn_window)
    else:
        valid = ar[None, :, :] <= qpos
    valid = valid[:, None]                   # [1, 1, W, total]
    new_caches = []
    for blk, (k_buf, v_buf) in zip(p["blocks"], caches):

        def cached_attn(q, k, v, k_buf=k_buf, v_buf=v_buf):
            out, k_buf, v_buf = _cached_attention(
                q, k, v, k_buf, v_buf, start, valid)
            new_caches.append((k_buf, v_buf))
            return out

        x, _, _, _ = _block_parts(c, blk, x, pos, cached_attn)
    return _head(p, x), new_caches


def _band_valid(slots, t, window):
    """The sliding-window band over cache SLOT indices: slot in
    (t - window, t]. ONE definition for every decode path (uniform
    prompts only — slot == position there)."""
    return (slots <= t) & (slots > t - window)


def _ring_slot_valid(pos, window: int):
    """THE ring-cache convention, shared by generate()'s rolling scan
    and the serving engine's per-row pool: position p lives at slot
    p mod window; after the write at `pos`, ring slot s holds absolute
    position pos - ((pos - s) mod window), valid iff it exists. pos may
    be a scalar (lockstep scan) or [S] (per-row pool). Returns
    (write_slot like pos, valid [..., window])."""
    p = jnp.asarray(pos)
    arw = jnp.arange(window, dtype=jnp.int32)
    held = p[..., None] - jnp.mod(p[..., None] - arw, window)
    return jnp.mod(p, window), held >= 0


# THE KV quantization convention — absmax symmetric per (position,
# kv-head), one scale per cached vector so dequant fuses into the
# attention einsum's operand read. The single definition lives in
# ops.paged_attention (the paged arena and the dense caches must
# quantize identically, and ops cannot import models); these are the
# models-side names every decode path in this file uses.
from paddle_tpu.ops.paged_attention import (  # noqa: E402
    kv_dequantize as _kv_dequantize,
    kv_quantize as _kv_quantize,
)


def _cached_attention(q, k, v, k_buf, v_buf, t, valid):
    """THE single-position decode attention: write this step's K/V at
    cache slot t, attend the 1-position q over `valid` cache keys
    ([..., total] bool, broadcastable over [B, H, 1, total]). Returns
    (out, k_buf, v_buf). Every decode path (greedy/sampled/beam/the
    serving engine) runs THIS math so a scoring change cannot diverge
    between them.

    t may be a SCALAR (all rows write the same slot — generate/beam's
    lockstep scan) or a [B] VECTOR of per-row slots (serve.engine's
    continuous batching, where slots are deliberately NOT in lockstep);
    vector writes use scatter mode="drop", so an out-of-range sentinel
    slot (the engine's inactive-row convention) skips the write.

    Under GQA the buffers hold COMPACT [B, total, Hkv, Dh] K/V; the
    grouped einsums read them directly (q reshaped to [.., Hkv, G, ..])
    so the per-step HBM read — the decode bottleneck — stays 1/G of the
    MHA cache, which is the entire point of GQA.

    k_buf/v_buf may be `(s8 data, scale)` pairs (cfg.kv_cache_dtype
    "int8"): this step's K/V are quantized before the write and the
    buffers dequantize inside the einsum reads, so the loop state — and
    the per-step HBM traffic — stays s8."""
    b, tq, h, dh = q.shape
    if getattr(t, "ndim", 0) == 1:
        assert tq == 1, "per-row slot writes require single-position q"
        rows = jnp.arange(b, dtype=jnp.int32)

        def write(buf, new):
            return buf.at[rows, t].set(
                new[:, 0].astype(buf.dtype), mode="drop")
    else:

        def write(buf, new):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, new.astype(buf.dtype), t, axis=1)

    quantized = isinstance(k_buf, tuple)
    if quantized:
        kq, ks = k_buf
        vq, vs = v_buf
        knew, knew_s = _kv_quantize(k)
        vnew, vnew_s = _kv_quantize(v)
        k_buf = (write(kq, knew), write(ks, knew_s))
        v_buf = (write(vq, vnew), write(vs, vnew_s))
        k_read = _kv_dequantize(*k_buf, q.dtype)
        v_read = _kv_dequantize(*v_buf, q.dtype)
    else:
        k_buf = write(k_buf, k)
        v_buf = write(v_buf, v)
        k_read, v_read = k_buf, v_buf
    hkv = k_read.shape[2]
    g = h // hkv  # 1 for MHA — the grouped path IS the only path
    scale = jnp.sqrt(jnp.asarray(dh, q.dtype))
    qg = q.reshape(b, tq, hkv, g, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_read) / scale
    # [B, Hkv, G, Tq, Tk] -> flatten head groups for the shared mask
    scores = at_least_f32(scores).reshape(b, h, tq, -1)
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    wg = w.reshape(b, hkv, g, tq, -1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", wg, v_read)
    return out.reshape(b, tq, h, dh), k_buf, v_buf


def generate(params, cfg: TransformerConfig, prompt, steps: int, *,
             select_fn=None, rng=None, eos_id: Optional[int] = None,
             pad_id: Optional[int] = None, prompt_lens=None):
    """Greedy decode with a KV cache carried through lax.scan.

    prompt [B,T0] int32 -> [B, T0+steps]. The cache holds K/V per layer
    at full T0+steps length (static shapes for XLA); each scan step
    attends over the valid prefix via an explicit position mask.

    select_fn(logits [B, V], rng_step) -> [B] int chooses each next
    token (default: argmax/greedy); `sample` builds temperature/top-k/
    top-p selectors and threads fresh rng per step through the scan.

    eos_id: once a row emits it, every later position is pad_id
    (default: eos_id) — the scan length stays static, finished rows
    just stop changing.

    prompt_lens [B]: RIGHT-padded variable-length prompts. Row i's real
    prompt is prompt[i, :lens[i]]; pad keys are masked out of every
    attention, rope positions continue from each row's own length, and
    the first generated token reads row i's logits at lens[i]-1.
    Output stays [B, T0+steps]: continuations start at column T0 for
    every row (pads remain in the middle for short rows). The prefill
    stays on the flash path (per-row key-length bound in the kernel);
    only the dense impl materializes [B,H,Tq,Tk] scores, so prefer
    attn_impl "auto"/"flash" for long variable-length prompts.
    """
    require_decodable(cfg)
    b, t0 = prompt.shape
    if cfg.attn_window is not None and prompt_lens is not None:
        raise ValueError(
            "attn_window with variable-length prompts is unsupported: "
            "cache slots and rope positions disagree for padded rows, "
            "so a slot-index window band would be wrong")
    if cfg.kv_cache_dtype not in ("compute", "int8"):
        raise ValueError(
            f"kv_cache_dtype must be compute|int8, got "
            f"{cfg.kv_cache_dtype!r}")
    if select_fn is None:
        select_fn = lambda logits, r: jnp.argmax(logits, axis=-1)
    if rng is None:
        rng = jax.random.key(0)
    fill = eos_id if pad_id is None else pad_id
    total = t0 + steps
    # sliding-window decode uses a ROLLING cache (r5): `window` slots,
    # written at t mod window — the full-length band-masked buffer
    # would still STREAM O(total) cache bytes per step (the einsum
    # reads the whole buffer; masking happens after), so the ring
    # buffer is what converts SWA's O(window) math into O(window) HBM
    # reads and memory. Slot s at step t holds absolute position
    # p = t - ((t - s) mod window); attention order over cache slots is
    # irrelevant (softmax is permutation-invariant over keys) and rope
    # is applied to K before caching, so rotation survives the ring.
    window = cfg.attn_window
    rolling = window is not None and window < total
    cache_len = window if rolling else total
    policy = default_policy()
    # weight-only int8 streaming: prefill uses the hoisted dequant
    # (one-shot, compute-bound); the scan body below re-dequantizes per
    # step so the decode loop streams s8 — see _int8_step_params, and
    # tests/test_compiled_cost.py for the compiled-loop-carries-s8
    # assertion (without the in-body barrier, XLA's LICM hoists the
    # convert and the loop streams f32 — the failure docs/PARITY.md:20
    # asked about, observed on the CPU pipeline)
    params, step_params = _int8_step_params(params)
    head = lambda x_last: _head(params, x_last)

    # prefill: the same _block_parts body as apply() (cfg.attn_impl
    # decides flash vs dense — a 32k prompt needs the flash path), with
    # each layer's rotated K/V captured into fixed-size cache buffers
    x = jnp.take(params["embed"]["table"], prompt, axis=0)
    x = x.astype(policy.compute_dtype)
    pos = jnp.broadcast_to(jnp.arange(t0, dtype=jnp.int32), (b, t0))
    if prompt_lens is None:
        key_ok = None
        prefill_attn = lambda q, k, v: _attention(cfg, q, k, v, causal=True)
    else:
        key_ok = jnp.arange(
            t0, dtype=jnp.int32)[None, :] < prompt_lens[:, None]  # [B, Tk]
        # key_ok itself only feeds the MoE token mask below; attention
        # takes the lens encoding (flash per-row bound, dense builds
        # the equivalent right-padding mask internally)
        prefill_attn = lambda q, k, v: _attention(
            cfg, q, k, v, causal=True, key_lens=prompt_lens)
    caches = []
    for p in params["blocks"]:
        # key_ok doubles as the MoE token mask: pad positions must not
        # claim expert capacity either
        x, k, v, _ = _block_parts(cfg, p, x, pos, prefill_attn, key_ok)
        # buffers take k/v's own head count: compact Hkv under GQA
        if rolling:
            # keep only the last `window` prompt positions, each in its
            # ring slot p mod window (a permutation for consecutive p)
            lo = max(0, t0 - cache_len)
            slots_init = jnp.arange(lo, t0, dtype=jnp.int32) % cache_len
            k_buf = jnp.zeros((b, cache_len) + k.shape[2:], k.dtype) \
                .at[:, slots_init].set(k[:, lo:t0])
            v_buf = jnp.zeros((b, cache_len) + v.shape[2:], v.dtype) \
                .at[:, slots_init].set(v[:, lo:t0])
        else:
            k_buf = jnp.zeros((b, total) + k.shape[2:], k.dtype) \
                .at[:, :t0].set(k)
            v_buf = jnp.zeros((b, total) + v.shape[2:], v.dtype) \
                .at[:, :t0].set(v)
        if cfg.kv_cache_dtype == "int8":
            # quantize the whole prefilled buffer once (zero slots
            # quantize to 0); from here the scan carries s8 + scales
            k_buf, v_buf = _kv_quantize(k_buf), _kv_quantize(v_buf)
        caches.append((k_buf, v_buf))
    # only the last REAL position's logits matter
    rng, first_rng = jax.random.split(rng)
    if prompt_lens is None:
        x_last = x[:, -1]
    else:
        x_last = jnp.take_along_axis(
            x, (prompt_lens - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
    first = select_fn(head(x_last), first_rng).astype(prompt.dtype)
    done0 = jnp.zeros((b,), bool)

    def step(carry, s):
        tok, t, caches, rng, done = carry  # tok [B], t scalar slot
        # int8: dequant traced INSIDE the loop body (see note above);
        # otherwise this is the same params object, zero cost
        p_full = step_params(tok)
        rng, step_rng = jax.random.split(rng)
        x = jnp.take(p_full["embed"]["table"], tok[:, None], axis=0)
        x = x.astype(policy.compute_dtype)
        # rope position continues from each row's OWN length
        if prompt_lens is None:
            pos = jnp.broadcast_to(t[None, None], (b, 1))
        else:
            pos = (prompt_lens.astype(jnp.int32) + s)[:, None]
        ar = jnp.arange(total, dtype=jnp.int32)
        slot = t
        if prompt_lens is None:
            if rolling:
                # the band (p > t-window) holds by construction, so
                # validity is just "the position exists" — ONE ring
                # convention shared with the engine (_ring_slot_valid)
                slot, ring_ok = _ring_slot_valid(t, cache_len)
                valid = ring_ok[None, None, None, :]
            elif cfg.attn_window is not None:
                valid = _band_valid(ar, t, cfg.attn_window)[
                    None, None, None, :]
            else:
                valid = (ar <= t)[None, None, None, :]
        else:
            # real prompt keys + generated slots written so far
            valid = ((ar[None, :] < prompt_lens[:, None]) |
                     ((ar[None, :] >= t0) & (ar[None, :] <= t)))
            valid = valid[:, None, None, :]
        new_caches = []
        for p, (k_buf, v_buf) in zip(p_full["blocks"], caches):

            def cached_attn(q, k, v, k_buf=k_buf, v_buf=v_buf):
                # the update is captured via new_caches (traced normally)
                out, k_buf, v_buf = _cached_attention(
                    q, k, v, k_buf, v_buf, slot, valid)
                new_caches.append((k_buf, v_buf))
                return out

            x, _, _, _ = _block_parts(cfg, p, x, pos, cached_attn)
        nxt = select_fn(_head(p_full, x[:, -1]), step_rng).astype(tok.dtype)
        if eos_id is not None:
            new_done = done | (tok == eos_id)
            nxt = jnp.where(new_done, jnp.asarray(fill, tok.dtype), nxt)
        else:
            new_done = done
        return (nxt, t + 1, new_caches, rng, new_done), tok

    _, toks = jax.lax.scan(
        step, (first, jnp.asarray(t0, jnp.int32), caches, rng, done0),
        jnp.arange(steps, dtype=jnp.int32), length=steps)
    # emitted = [first, t1, ..., t_{steps-1}]: exactly the new tokens
    return jnp.concatenate([prompt, toks.transpose(1, 0)], axis=1)


def speculative_generate(params, cfg: TransformerConfig,
                         draft_params, draft_cfg: TransformerConfig,
                         prompt, steps: int, *, draft_k: int = 4,
                         eos_id: Optional[int] = None,
                         pad_id: Optional[int] = None,
                         return_stats: bool = False):
    """Greedy speculative decoding: a small DRAFT model proposes
    `draft_k` tokens autoregressively, the TARGET model scores all of
    them in ONE K+1-position cached forward, and the longest agreeing
    prefix is accepted plus the target's own token at the first
    disagreement — ≥1 target-quality token per round for ~1 target
    forward per round instead of per token.

    The output is EXACTLY the target model's greedy decode (the
    accept rule keeps every token the target would have picked), so a
    bad draft costs speed, never quality — tested as a hard equality.

    BATCHED (r5; the r4 version was batch-1): rows accept different
    prefix lengths, so each row carries its OWN position pointer and
    the whole round body runs under vmap inside one while_loop — rows
    advance independently, per-row dynamic_slice reads/writes handle
    the desync, and a finished row simply replays idempotent rounds
    (same inputs -> same cache writes) with its pointer, output and
    done flag frozen until every row finishes. Uniform prompt length
    only (the batched analog of generate's prompt_lens is future work).

    eos_id: a row that emits it stops advancing; its positions after
    the eos are pad_id (default eos_id), exactly matching generate()'s
    eos semantics so the hard-equality contract extends to early stop.

    Cache slots are indexed by token position, so rejected speculative
    writes are simply overwritten when the real token reaches that
    position — no rollback copies.

    return_stats=True additionally returns the per-row number of
    rounds [B] — the acceptance-rate observable: a perfect draft
    finishes `steps` tokens in ceil(steps / (draft_k+1)) rounds, a
    hopeless one in `steps`.
    """
    require_decodable(cfg)
    require_decodable(draft_cfg)
    if cfg.kv_cache_dtype != "compute" or \
            draft_cfg.kv_cache_dtype != "compute":
        raise ValueError(
            "kv_cache_dtype='int8' covers generate()/sample() and the "
            "serving engine's slot pool only: the beam/speculative "
            "window path reads fp buffers; decode with generate or "
            "serve.DecodeEngine, or clear kv_cache_dtype")
    b, t0 = prompt.shape
    if t0 < 2:
        raise ValueError("need a >=2-token prompt (prefill t0-1, then "
                         "the last token seeds the first round)")
    policy = default_policy()
    fill = eos_id if pad_id is None else pad_id
    # int8 params stream s8 inside the round loop (the target model is
    # the bandwidth-heavy one; a quantized draft gets the same hook)
    params, tgt_step_params = _int8_step_params(params)
    draft_params, dft_step_params = _int8_step_params(draft_params)
    # pad the buffers so the final round may overshoot by a window
    total = t0 + steps + draft_k + 1

    def window_forward(p, c, caches, toks, start):
        return _window_forward(p, c, caches, toks, start, total)

    # prefill slots 0..t0-2 (token t0-1 stays unprocessed: its logits
    # come from the first verify/draft window)
    tgt_caches = _prefill_kv(params, cfg, prompt[:, :-1], total)
    dft_caches = _prefill_kv(draft_params, draft_cfg, prompt[:, :-1],
                             total)
    out_buf = jnp.zeros((b, total), prompt.dtype).at[:, :t0].set(prompt)
    t_end = t0 + steps
    karange = jnp.arange(draft_k + 1, dtype=jnp.int32)

    def row_round(t, done, rounds, out_row, tgt_c, dft_c, tgt_p, dft_p):
        """One speculative round for ONE row. Runs under vmap: every
        input arrives without its batch dim (caches [total, Hkv, Dh],
        out_row [total], t/done/rounds scalars) and is re-wrapped to
        the batch-1 shapes window_forward expects. tgt_p/dft_p are the
        round's dequantized params, computed OUTSIDE the vmap
        (in_axes=None): `jax.lax.optimization_barrier` has no vmap
        batching rule in this jax, so the int8 LICM barrier
        (_int8_step_params) must fire in the while body before the
        rows fan out — once per round instead of once per forward,
        which streams the s8 weights all the same."""
        active = (~done) & (t < t_end)
        out1 = out_row[None]
        tgt1 = jax.tree.map(lambda a: a[None], tgt_c)
        dft1 = jax.tree.map(lambda a: a[None], dft_c)

        # --- draft proposes draft_k tokens autoregressively ---------
        # round start re-processes positions t-2 AND t-1: after a
        # fully-accepted round the draft never processed its own last
        # accepted token (slot t-2), and that gap would otherwise leave
        # zero K/V attended forever, silently collapsing the acceptance
        # rate. The 2-token window always covers the (at most 1 slot)
        # gap; overwriting an already-filled slot is a no-op.
        last2 = jax.lax.dynamic_slice(
            out1, (jnp.zeros((), t.dtype), t - 2), (1, 2))
        logits2, dft1 = window_forward(
            dft_p, draft_cfg, dft1, last2, t - 2)
        d0 = jnp.argmax(logits2[:, -1], axis=-1).astype(out_row.dtype)

        def draft_step(c, i):
            dft, tok = c
            logits, dft = window_forward(
                dft_p, draft_cfg, dft, tok[:, None], t + i)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(out_row.dtype)
            return (dft, nxt), nxt

        (dft1, _), more = jax.lax.scan(
            draft_step, (dft1, d0), jnp.arange(draft_k - 1, dtype=jnp.int32))
        drafts = jnp.concatenate(
            [d0[None, :], more], axis=0).transpose(1, 0)   # [1, K]

        # --- target verifies the window in one forward --------------
        last = jax.lax.dynamic_slice_in_dim(out1, t - 1, 1, axis=1)
        window = jnp.concatenate([last, drafts], axis=1)   # [1, K+1]
        logits, tgt1 = window_forward(tgt_p, cfg, tgt1,
                                      window, t - 1)
        greedy = jnp.argmax(logits, axis=-1).astype(out_row.dtype)

        # longest agreeing prefix: drafts[j] == greedy[j] for j < n_acc
        agree = drafts[0] == greedy[0, :draft_k]
        n_acc = jnp.argmin(jnp.concatenate(
            [agree, jnp.zeros((1,), bool)]).astype(jnp.int32))
        # accepted drafts then the target's own token at the break
        app = jnp.where(karange < n_acc,
                        jnp.concatenate([drafts[0], greedy[0, -1:]]),
                        greedy[0])                         # [K+1]
        if eos_id is not None:
            # stop AFTER the first eos among the n_acc+1 appended
            # tokens; the post-loop fill mask pads everything beyond it
            hit = (app == eos_id) & (karange <= n_acc)
            found = jnp.any(hit)
            adv = jnp.where(found, jnp.argmax(hit) + 1, n_acc + 1)
        else:
            found = jnp.zeros((), bool)
            adv = n_acc + 1
        new_out = jax.lax.dynamic_update_slice(
            out1, app[None], (jnp.zeros((), t.dtype), t))[0]
        # a frozen row replays an IDENTICAL round (same t, same tokens
        # -> same cache writes: idempotent); only its pointer, output,
        # done flag and round count must not move
        t = jnp.where(active, (t + adv).astype(t.dtype), t)
        done = done | (active & found)
        rounds = rounds + active.astype(rounds.dtype)
        out_row = jnp.where(active, new_out, out_row)
        return (t, done, rounds, out_row,
                jax.tree.map(lambda a: a[0], tgt1),
                jax.tree.map(lambda a: a[0], dft1))

    vround = jax.vmap(row_round, in_axes=(0,) * 6 + (None, None))

    def cond(carry):
        t, done = carry[0], carry[1]
        return jnp.any((~done) & (t < t_end))

    def body(c):
        # dequant ONCE per round, before the rows fan out: the
        # optimization_barrier keyed on the loop-varying pointer
        # vector keeps LICM from hoisting it out of the while_loop,
        # and running it here (not in row_round) keeps it out of vmap,
        # which has no batching rule for the barrier
        return vround(*c, tgt_step_params(c[0]), dft_step_params(c[0]))

    t, done, rounds, out_buf, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.full((b,), t0, jnp.int32), jnp.zeros((b,), bool),
         jnp.zeros((b,), jnp.int32), out_buf, tgt_caches, dft_caches))
    if eos_id is not None:
        # finished rows: everything from their stop point on is fill —
        # generate()'s post-eos semantics, so the hard-equality test
        # covers the padding too
        col = jnp.arange(total, dtype=jnp.int32)[None, :]
        out_buf = jnp.where(done[:, None] & (col >= t[:, None]),
                            jnp.asarray(fill, out_buf.dtype), out_buf)
    if return_stats:
        return out_buf[:, :t_end], rounds
    return out_buf[:, :t_end]


def speculative_sample(params, cfg: TransformerConfig,
                       draft_params, draft_cfg: TransformerConfig,
                       prompt, steps: int, rng, *, draft_k: int = 4,
                       temperature: float = 1.0,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       eos_id: Optional[int] = None,
                       pad_id: Optional[int] = None,
                       return_stats: bool = False):
    """SAMPLED speculative decoding via the modified-rejection scheme
    (Leviathan et al. / Chen et al. 2023): the draft SAMPLES draft_k
    tokens from its own filtered distribution q, the target scores the
    window in one forward, and draft token x_i is accepted with
    probability min(1, p_i(x_i)/q_i(x_i)); at the first rejection the
    round's last token is drawn from the residual max(p_i - q_i, 0)
    (renormalized), and after a fully-accepted window from the
    target's next-position distribution. The output tokens are
    distributed EXACTLY as sampling token-by-token from the target
    with the same temperature/top-k/top-p filters — the draft changes
    only speed, never the distribution (tested empirically, and
    exactly at top_k=1 where the scheme degenerates to greedy).

    Batched like speculative_generate (per-row pointers under vmap,
    per-row rng keys), with the same eos/pad semantics. temperature
    must be > 0 — use speculative_generate for greedy.

    return_stats=True also returns per-row round counts [B].
    """
    require_decodable(cfg)
    require_decodable(draft_cfg)
    if cfg.kv_cache_dtype != "compute" or \
            draft_cfg.kv_cache_dtype != "compute":
        raise ValueError(
            "kv_cache_dtype='int8' covers generate()/sample() and the "
            "serving engine's slot pool only: the beam/speculative "
            "window path reads fp buffers; decode with generate or "
            "serve.DecodeEngine, or clear kv_cache_dtype")
    b, t0 = prompt.shape
    if t0 < 2:
        raise ValueError("need a >=2-token prompt (prefill t0-1, then "
                         "the last token seeds the first round)")
    if temperature <= 0:
        raise ValueError("temperature must be > 0 (speculative_generate "
                         "is the greedy decoder)")
    _validate_sampler_args(temperature, top_k, top_p)
    fill = eos_id if pad_id is None else pad_id
    params, tgt_step_params = _int8_step_params(params)
    draft_params, dft_step_params = _int8_step_params(draft_params)
    total = t0 + steps + draft_k + 1

    tgt_caches = _prefill_kv(params, cfg, prompt[:, :-1], total)
    dft_caches = _prefill_kv(draft_params, draft_cfg, prompt[:, :-1],
                             total)
    out_buf = jnp.zeros((b, total), prompt.dtype).at[:, :t0].set(prompt)
    t_end = t0 + steps
    karange = jnp.arange(draft_k + 1, dtype=jnp.int32)

    def filt_logp(logits):
        """Filtered log-distribution [N, V] — the ONE distribution both
        models sample/score under, so acceptance preserves it."""
        return jax.nn.log_softmax(_filter_logits(
            at_least_f32(logits), temperature, top_k, top_p), axis=-1)

    def row_round(t, done, rounds, key, out_row, tgt_c, dft_c,
                  tgt_p, dft_p):
        # tgt_p/dft_p: the round's dequantized params, computed in the
        # while body OUTSIDE this vmapped round (in_axes=None) — see
        # speculative_generate's row_round for why (the int8 LICM
        # barrier has no vmap batching rule)
        active = (~done) & (t < t_end)
        key, k_draft, k_acc, k_res = jax.random.split(key, 4)
        out1 = out_row[None]
        tgt1 = jax.tree.map(lambda a: a[None], tgt_c)
        dft1 = jax.tree.map(lambda a: a[None], dft_c)

        # --- draft SAMPLES draft_k tokens, recording its filtered
        # log-probs (full rows: the residual needs q_i(·), not just
        # q_i(x_i)); same 2-token catch-up as the greedy decoder ------
        last2 = jax.lax.dynamic_slice(
            out1, (jnp.zeros((), t.dtype), t - 2), (1, 2))
        logits2, dft1 = _window_forward(
            dft_p, draft_cfg, dft1, last2, t - 2, total)
        q0 = filt_logp(logits2[:, -1])                     # [1, V]
        d0 = jax.random.categorical(
            jax.random.fold_in(k_draft, 0), q0, axis=-1
        ).astype(out_row.dtype)

        def draft_step(c, i):
            dft, tok = c
            logits, dft = _window_forward(
                dft_p, draft_cfg, dft, tok[:, None],
                t + i, total)
            q = filt_logp(logits[:, -1])                   # [1, V]
            nxt = jax.random.categorical(
                jax.random.fold_in(k_draft, i + 1), q, axis=-1
            ).astype(out_row.dtype)
            return (dft, nxt), (nxt, q[0])

        (dft1, _), (more, qmore) = jax.lax.scan(
            draft_step, (dft1, d0), jnp.arange(draft_k - 1, dtype=jnp.int32))
        drafts = jnp.concatenate([d0[None, :], more],
                                 axis=0).transpose(1, 0)   # [1, K]
        qdist = jnp.concatenate([q0, qmore], axis=0)       # [K, V]

        # --- target scores the window in one forward ----------------
        last = jax.lax.dynamic_slice_in_dim(out1, t - 1, 1, axis=1)
        window = jnp.concatenate([last, drafts], axis=1)   # [1, K+1]
        logits, tgt1 = _window_forward(tgt_p, cfg,
                                       tgt1, window, t - 1, total)
        pdist = filt_logp(logits[0])                       # [K+1, V]

        # --- modified rejection: accept x_i w.p. min(1, p_i/q_i) ----
        p_x = jnp.take_along_axis(
            pdist[:draft_k], drafts[0][:, None], axis=-1)[:, 0]
        q_x = jnp.take_along_axis(
            qdist, drafts[0][:, None], axis=-1)[:, 0]
        u = jax.random.uniform(k_acc, (draft_k,))
        acc = u < jnp.exp(jnp.minimum(p_x - q_x, 0.0))
        n_acc = jnp.argmin(jnp.concatenate(
            [acc, jnp.zeros((1,), bool)]).astype(jnp.int32))
        # the round's last token: residual (p-q)+ at the rejection
        # position, or the target's next-position dist when all accept
        n_sel = jnp.minimum(n_acc, draft_k - 1)
        p_rej = jnp.exp(jax.lax.dynamic_index_in_dim(
            pdist, n_sel, axis=0, keepdims=False))
        q_rej = jnp.exp(jax.lax.dynamic_index_in_dim(
            qdist, n_sel, axis=0, keepdims=False))
        res = jnp.maximum(p_rej - q_rej, 0.0)
        # float-edge fallback: if the residual mass rounds to zero,
        # sample from p itself (p<=q everywhere means p==q: identical
        # distributions, any p-sample is correct)
        res = jnp.where(jnp.sum(res) > 0, res, p_rej)
        tok_rej = jax.random.categorical(k_res, jnp.log(res + 1e-38))
        tok_all = jax.random.categorical(k_res, pdist[draft_k])
        resolved = jnp.where(n_acc < draft_k, tok_rej,
                             tok_all).astype(out_row.dtype)

        app = jnp.where(karange < n_acc,
                        jnp.concatenate([drafts[0],
                                         resolved[None]]), resolved)
        if eos_id is not None:
            hit = (app == eos_id) & (karange <= n_acc)
            found = jnp.any(hit)
            adv = jnp.where(found, jnp.argmax(hit) + 1, n_acc + 1)
        else:
            found = jnp.zeros((), bool)
            adv = n_acc + 1
        new_out = jax.lax.dynamic_update_slice(
            out1, app[None], (jnp.zeros((), t.dtype), t))[0]
        t = jnp.where(active, (t + adv).astype(t.dtype), t)
        done = done | (active & found)
        rounds = rounds + active.astype(rounds.dtype)
        out_row = jnp.where(active, new_out, out_row)
        return (t, done, rounds, key, out_row,
                jax.tree.map(lambda a: a[0], tgt1),
                jax.tree.map(lambda a: a[0], dft1))

    vround = jax.vmap(row_round, in_axes=(0,) * 7 + (None, None))

    def cond(carry):
        t, done = carry[0], carry[1]
        return jnp.any((~done) & (t < t_end))

    def body(c):
        # per-round dequant outside the vmap (no barrier batching
        # rule), inside the while loop (LICM barrier still binds)
        return vround(*c, tgt_step_params(c[0]), dft_step_params(c[0]))

    t, done, rounds, _, out_buf, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.full((b,), t0, jnp.int32), jnp.zeros((b,), bool),
         jnp.zeros((b,), jnp.int32), jax.random.split(rng, b),
         out_buf, tgt_caches, dft_caches))
    if eos_id is not None:
        col = jnp.arange(total, dtype=jnp.int32)[None, :]
        out_buf = jnp.where(done[:, None] & (col >= t[:, None]),
                            jnp.asarray(fill, out_buf.dtype), out_buf)
    if return_stats:
        return out_buf[:, :t_end], rounds
    return out_buf[:, :t_end]


def beam_decode(params, cfg: TransformerConfig, prompt, steps: int,
                beam_size: int = 4, *, eos_id: Optional[int] = None,
                length_penalty: float = 0.0):
    """Beam-search decode over the KV cache (reference analog: the v1
    SequenceGenerator / RecurrentGradientMachine beam, here closed over
    the transformer's cached step via ops.beam_search's fixed-shape
    engine).

    prompt [B, T0] (uniform length — the fixed-shape engine advances
    every row's cache slot in lockstep; decode variable-length batches
    with `generate(prompt_lens=...)` instead) -> (sequences
    [B, K, T0+steps], scores [B, K]) sorted best-first; without an
    eos_id every beam runs the full `steps`.
    """
    require_decodable(cfg)
    if cfg.kv_cache_dtype != "compute":
        raise ValueError(
            "kv_cache_dtype='int8' covers generate()/sample() and the "
            "serving engine's slot pool only: the beam/speculative "
            "window path reads fp buffers; decode with generate or "
            "serve.DecodeEngine, or clear kv_cache_dtype")
    from paddle_tpu.ops import beam_search as bs

    b, t0 = prompt.shape
    total = t0 + steps
    policy = default_policy()
    # int8 params stream s8 inside the beam-step loop (same hook as
    # generate/speculative_generate)
    params, step_params = _int8_step_params(params)

    # prefill all but the last prompt token; the engine feeds that last
    # token as each row's first input (bos_tokens). A 1-token prompt
    # has nothing to prefill — the caches start empty rather than
    # tracing a T=0 sequence through the attention kernels.
    caches = {}
    if t0 > 1:
        for i, (k_buf, v_buf) in enumerate(
                _prefill_kv(params, cfg, prompt[:, :-1], total)):
            caches[f"k{i}"] = k_buf
            caches[f"v{i}"] = v_buf
    else:
        # each buffer's dtype must equal what the decode step will
        # write into it (dtype promotion depends on that BLOCK's param
        # dtypes, e.g. under x64 or mixed-precision blocks) —
        # eval_shape each block body, threading x's dtype through the
        # stack exactly like the decode step will
        x_shape = jax.ShapeDtypeStruct((b, 1, cfg.dim),
                                       policy.compute_dtype)
        pos_shape = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        for i, p in enumerate(params["blocks"]):
            x_shape, k_shape = jax.eval_shape(
                lambda p, x, pos: _block_parts(cfg, p, x, pos,
                                               lambda q, k, v: q)[:2],
                p, x_shape, pos_shape)
            caches[f"k{i}"] = jnp.zeros(
                (b, total) + k_shape.shape[2:], k_shape.dtype)
            caches[f"v{i}"] = jnp.zeros(
                (b, total) + k_shape.shape[2:], k_shape.dtype)
    caches["t"] = jnp.full((b,), t0 - 1, jnp.int32)

    def step_fn(toks, dec):
        p_full = step_params(toks)   # int8: dequant inside the loop
        t = dec["t"][0]  # slot for THIS input token (uniform)
        x = jnp.take(p_full["embed"]["table"], toks[:, None], axis=0)
        x = x.astype(policy.compute_dtype)
        pos = jnp.broadcast_to(t[None, None], (toks.shape[0], 1))
        new_dec = {"t": dec["t"] + 1}
        if cfg.attn_window is not None:
            valid = _band_valid(jnp.arange(total, dtype=jnp.int32), t,
                                cfg.attn_window)[None, None, None, :]
        else:
            valid = (jnp.arange(
                total, dtype=jnp.int32) <= t)[None, None, None, :]
        for i in range(len(p_full["blocks"])):
            k_buf, v_buf = dec[f"k{i}"], dec[f"v{i}"]

            def cached_attn(q, k, v, k_buf=k_buf, v_buf=v_buf, li=i):
                out, k_buf, v_buf = _cached_attention(
                    q, k, v, k_buf, v_buf, t, valid)
                new_dec[f"k{li}"] = k_buf
                new_dec[f"v{li}"] = v_buf
                return out

            x, _, _, _ = _block_parts(cfg, p_full["blocks"][i], x, pos,
                                      cached_attn)
        return _head(p_full, x[:, -1]), new_dec

    toks, scores, _ = bs.beam_search(
        caches, step_fn, batch_size=b, beam_size=beam_size,
        max_len=steps, bos_id=0,
        eos_id=-1 if eos_id is None else eos_id,
        vocab_size=cfg.vocab, length_penalty=length_penalty,
        bos_tokens=prompt[:, -1])
    seqs = jnp.concatenate(
        [jnp.broadcast_to(prompt[:, None, :], (b, beam_size, t0)), toks],
        axis=-1)
    return seqs, scores


def _validate_sampler_args(temperature, top_k, top_p):
    if temperature < 0:
        raise ValueError("temperature must be >= 0")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _filter_logits(logits, temperature, top_k, top_p):
    """Temperature scaling, then optional top-k truncation, then
    optional nucleus (top-p) filtering over [N, V] logits; filtered-out
    tokens become -inf. Shared by make_sampler and speculative_sample —
    the SAME filtered distribution is what both sample from and what
    the rejection rule must preserve. temperature must be > 0 here
    (the greedy degenerate case is handled by the callers)."""
    logits = logits / temperature
    if top_k is not None or top_p is not None:
        # one descending sort serves both filters; top-k in sorted
        # space is just position < k, and the nucleus is computed
        # over the top-k-FILTERED distribution (sequential filter
        # semantics)
        desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k is not None:
            k_eff = min(top_k, logits.shape[-1])
            kth = desc[:, k_eff - 1][:, None]
            logits = jnp.where(logits >= kth, logits, -jnp.inf)
            desc = jnp.where(jnp.arange(
                desc.shape[-1], dtype=jnp.int32)[None, :] <
                             k_eff, desc, -jnp.inf)
        if top_p is not None:
            probs = jax.nn.softmax(desc, axis=-1)
            cum = jnp.cumsum(probs, axis=-1) - probs
            # keep every token whose preceding nucleus mass < top_p
            # (the argmax always survives: its preceding mass is 0)
            cutoff_logit = jnp.min(jnp.where(
                cum < top_p, desc, jnp.inf), axis=-1, keepdims=True)
            logits = jnp.where(logits >= cutoff_logit, logits,
                               -jnp.inf)
    return logits


# The per-row sampler lives in ops.sampling now (the serving engine and
# the speculative verify rule both draw through it without importing
# models); these names remain the models-side aliases, like _kv_quantize.
per_row_filter_logits = sampling_ops.per_row_filter_logits
per_row_sample = sampling_ops.per_row_sample


def make_sampler(*, temperature: float = 1.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None):
    """Build a select_fn for `generate`: temperature scaling, then
    optional top-k truncation, then optional nucleus (top-p) filtering,
    then a categorical draw. temperature=0 degenerates to greedy.

    top_k is clamped to the vocab size (k >= vocab means no filtering),
    and ties at the kth logit all survive (the filter keeps every logit
    >= the kth largest, so more than k tokens can pass)."""
    _validate_sampler_args(temperature, top_k, top_p)

    def select(logits, rng):
        logits = at_least_f32(logits)
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(
            rng, _filter_logits(logits, temperature, top_k, top_p),
            axis=-1)

    return select


def sample(params, cfg: TransformerConfig, prompt, steps: int, rng, *,
           temperature: float = 1.0, top_k: Optional[int] = None,
           top_p: Optional[float] = None, eos_id: Optional[int] = None,
           pad_id: Optional[int] = None, prompt_lens=None):
    """Sampled decode: generate() with a temperature/top-k/top-p
    selector and per-step rng; forwards eos/pad and variable-length
    prompt support."""
    return generate(params, cfg, prompt, steps,
                    select_fn=make_sampler(temperature=temperature,
                                           top_k=top_k, top_p=top_p),
                    rng=rng, eos_id=eos_id, pad_id=pad_id,
                    prompt_lens=prompt_lens)
