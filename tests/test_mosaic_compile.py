"""Compile the flash-attention kernels for the TPU v5e without one.

`tests/test_pallas_lowering.py` stops at Mosaic's MLIR; the chip's
compiler proper is installed here too and compiles for a chip that is
described and not attached, which is where it refuses a misaligned
slice, a matmul form or too much VMEM. Nothing runs: no result, no
time. The kernels of the LM cells' attention at their real widths (the
forward with its two bodies, unmasked and masked, on the blocks
`_forward_blocks` takes from each shape: PR 35), a second or two each,
all in this one file (only one process may hold the
TPU library: the topology is described inside a fixture, never at
import, so every xdist worker collects the same tests and only the
worker given this file loads it).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.ops import pallas_util

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    monkeypatch.setattr(pallas_util, "on_tpu", lambda: True)
    # conftest turns x64 on; the chip runs without it, Mosaic has no f64
    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize("bh,t,d,dtype,window,lens,fwd_blocks", [
    # starcoder2_3b_l4.train_seq4k: 4095 positions, window inert
    (48, 4095, 128, jnp.bfloat16, 4096, False, (1024, 1024)),
    (48, 4096, 128, jnp.bfloat16, None, False, (1024, 1024)),
    (24, 8192, 128, jnp.bfloat16, 4096, False, (1024, 1024)),  # band active
    # mellum2_12b_a2p5b_ep4.train_seq8k: 2 x 32 heads, a band of 1024
    # (every needed block cut), and its full-causal layer
    (64, 8192, 128, jnp.bfloat16, 1024, False, (1024, 1024)),
    (64, 8192, 128, jnp.bfloat16, None, False, (1024, 1024)),
    # qwen3_next_80b_a3b_ep16.train_seq8k's full layer: 2 x 16 heads of
    # 256 (8 a KV head, expanded before the kernel)
    (32, 8192, 256, jnp.bfloat16, None, False, (1024, 1024)),
    (16, 2048, 64, jnp.bfloat16, 512, True, (1024, 1024)),  # serving width
    (16, 2048, 64, jnp.bfloat16, None, True, (1024, 1024)),  # a prefill
    (16, 2048, 128, jnp.float32, None, False, (1024, 1024)),  # f32 policy
    (8, 100, 64, jnp.bfloat16, None, True, (100, 100)),  # shorter than a tile
    # lengths that 1024 would pad further than 256 x 512: odd blocks
    (8, 1280, 128, jnp.bfloat16, None, False, (640, 768)),
    (8, 1536, 64, jnp.bfloat16, 512, True, (768, 768)),
    (4, 4100, 128, jnp.bfloat16, None, False, (384, 896)),
    # trinity_mini_26b_a3b_ep8.train_seq8k: a band of 2048 (two of the
    # eight blocks a row cut)
    (64, 8192, 128, jnp.bfloat16, 2048, False, (1024, 1024)),
])
def test_flash_forward_and_backward_compile_for_v5e(one_chip, bh, t, d,
                                                    dtype, window, lens,
                                                    fwd_blocks):
    assert FA._forward_blocks(t, t, d, dtype) == fwd_blocks
    x = jax.ShapeDtypeStruct((1, t, bh, d), dtype, sharding=one_chip)
    key_lens = (jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
                if lens else None)

    def loss(q, k, v, key_lens):
        o = FA.flash_attention(q, k, v, causal=True, window=window,
                               key_lens=key_lens)
        return jnp.sum(o.astype(jnp.float32))

    before = pallas_util.traced()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, key_lens).compile()
    text = compiled.as_text()
    # the causal and window masks take no diagonal step
    noted = [key for key, n in pallas_util.traced().items()
             if n > before.get(key, 0)]
    assert any(key.startswith("flash_attention.fwd_block_kinds=")
               for key in noted), noted
    assert not any("diagonal" in key for key in noted), noted
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # under `grad` the forward's instruction is the jvp's; a window
    # that cuts names its kernels apart, an inert one does not
    cut = window is not None and window < t
    for name in ("jvp_flash_attention_fwd_", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert f"%{name}" in text, name
        assert (f"%{name}_window" in text or f"%{name}window" in text) == cut


# a row's forward grid steps: the noised diagonal's four blocks a row
# run on their sub-squares at the cell's shape, none where Bd is 3
_BD_KINDS = {4096: "interior:12,cut:8,diagonal:4,skipped:40",
             1536: "interior:0,cut:8,skipped:1"}


@pytest.mark.parametrize("length,bd,bh", [
    # sdar_30b_a3b_ep8.train_bd4_seq4k: 2 sequences x 32 heads, 2 x 4096
    # positions each (noised copy, then clean copy), Bd 4
    (4096, 4, 64),
    (1536, 3, 8),       # Bd no power of two, L no multiple of a block
])
def test_block_diffusion_flash_compiles_for_v5e(one_chip, length, bd, bh):
    kinds = _BD_KINDS[length]
    assert FA._forward_blocks(2 * length, 2 * length, 128,
                              jnp.bfloat16) == (1024, 1024)
    x = jax.ShapeDtypeStruct((1, 2 * length, bh, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        o = FA.flash_attention(q, k, v, block_diffusion=(length, bd))
        return jnp.sum(o.astype(jnp.float32))

    before = pallas_util.traced()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("jvp_flash_attention_fwd_", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert f"%{name}" in text, name
    after = pallas_util.traced()
    noted = [key for key, n in after.items() if n > before.get(key, 0)]
    assert f"flash_attention.fwd_block_kinds={kinds}" in noted, noted
    sub = f"flash_attention.diagonal_sub={FA._DIAGONAL_SUB}"
    assert (sub in noted) == ("diagonal" in kinds), noted


@pytest.mark.parametrize("bh,t,d,kw,kinds", [
    # starcoder2_3b_l4.train_seq4k
    (48, 4096, 128, dict(causal=True), "interior:6,cut:4,skipped:6"),
    # mellum2_12b_a2p5b_ep4.train_seq8k's band and trinity_mini's
    (64, 8192, 128, dict(causal=True, window=1024),
     "interior:0,cut:15,skipped:49"),
    (64, 8192, 128, dict(causal=True, window=2048),
     "interior:7,cut:14,skipped:43"),
    # their full layers, and qwen3_next_80b_a3b_ep16's at head_dim 256
    (64, 8192, 128, dict(causal=True), "interior:28,cut:8,skipped:28"),
    (32, 8192, 256, dict(causal=True), "interior:28,cut:8,skipped:28"),
    # sdar_30b_a3b_ep8.train_bd4_seq4k
    (64, 8192, 128, dict(causal=False, block_diffusion=(4096, 4)),
     "interior:12,cut:8,diagonal:4,skipped:40"),
])
def test_backward_kernels_compile_for_v5e_with_their_kinds(one_chip, bh, t,
                                                           d, kw, kinds):
    """Both backward kernels, each with its interior, cut (and diagonal)
    bodies, at the cells' shapes on their 1024 x 1024 blocks."""
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    x, rows = spec((bh, t, d)), spec((bh, t), jnp.float32)
    kw = dict(dict(window=None), **kw)

    def backward(q, k, v, lens, o, lse, g):
        return FA._flash_backward(q, k, v, lens, o, lse, g,
                                  block_q=FA.BWD_BLOCK_Q,
                                  block_k=FA.BWD_BLOCK_K, **kw)

    before = pallas_util.traced()
    text = jax.jit(backward).lower(
        x, x, x, spec((bh,), jnp.int32), x, rows, x).compile().as_text()
    noted = [key for key, n in pallas_util.traced().items()
             if n > before.get(key, 0)]
    assert f"flash_attention.bwd_block_kinds={kinds}" in noted, noted
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    suffix = "_window" if kw["window"] is not None else ""
    for name in ("flash_attention_bwd_dkv", "flash_attention_bwd_dq"):
        assert f"%{name}{suffix}" in text, name


@pytest.mark.parametrize("d,f", [
    (2048, 768),        # sdar_30b_a3b_ep8
    (2304, 896),        # mellum2_12b_a2p5b_ep4: K 2304 in two tiles of 1152
    (2048, 1024),       # trinity_mini_26b_a3b_ep8
])
def test_grouped_expert_products_compile_for_v5e(one_chip, d, f):
    """The dropless layer's three grouped products and their gradients
    at the cells' shapes: 16 experts held, a buffer of 16,384 positions
    x 8 choices rows."""
    from paddle_tpu.ops import moe_grouped_matmul as G

    rows, held = 16384 * 8, 16
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def loss(x, w_gate, w_up, w_down, sizes):
        h = (G.grouped_matmul(x, w_gate, sizes).astype(jnp.float32)
             * G.grouped_matmul(x, w_up, sizes).astype(jnp.float32))
        out = G.grouped_matmul(h.astype(x.dtype), w_down, sizes)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec((rows, d)), spec((held, d, f)), spec((held, d, f)),
        spec((held, f, d)), spec((held,), jnp.int32)).compile().as_text()
    # 3 forward products, 3 input gradients, 3 weight gradients
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert "moe_grouped_matmul_dw" in text


@pytest.mark.parametrize("case,kw", [
    ("causal", dict()),
    ("window", dict(attn_window=512)),
    ("block_diffusion", dict()),
])
def test_checkpointed_block_runs_the_forward_kernel_once_for_v5e(
        one_chip, case, kw):
    """`value_and_grad` of a 2-layer `remat` model at 2 x 2048
    positions, 4 heads of 128: a checkpointed block keeps the forward
    kernel's output and log-sum-exp by name, so the compiled program
    holds n forward kernels and n of each backward kernel; under a
    plain checkpoint it held 2n forward ones."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.models import transformer as T

    cfg = T.TransformerConfig(vocab=512, dim=512, n_layers=2, n_heads=4,
                              n_kv_heads=2, mlp_ratio=1, attn_impl="flash",
                              remat=True, **kw)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    if case == "block_diffusion":
        batch = (sds((2, 1024), jnp.int32), sds((2, 1024), jnp.bool_),
                 sds((2, 1024), jnp.float32))
        loss = lambda p, toks, masked, prob: T.block_diffusion_loss(
            p, cfg, toks, masked, prob, block_length=4)[0]
    else:
        batch = (sds((2, 2049), jnp.int32),)
        loss = lambda p, toks: T.loss(p, cfg, toks)
    prev = dtypes.default_policy()
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    try:
        text = jax.jit(jax.value_and_grad(loss)).lower(
            jax.tree.map(lambda x: sds(x.shape, x.dtype), shapes),
            *batch).compile().as_text()
    finally:
        dtypes.set_default_policy(prev)
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 3 * cfg.n_layers
    # the instructions' names: `%[jvp_]flash_attention_<kernel>[_window][_][.n] =`
    suffix = "_window" if case == "window" else ""
    kernels = re.findall(
        r"^\s*%(?:jvp_)?flash_attention_(fwd|bwd_dkv|bwd_dq)" + suffix
        + r"_?(?:\.\d+)? = ", text, re.M)
    assert sorted(kernels) == sorted(
        ["fwd", "bwd_dkv", "bwd_dq"] * cfg.n_layers), kernels


@pytest.mark.parametrize("d,f", [
    (2048, 768),        # sdar_30b_a3b_ep8: top 8 of 128, 16 held
    (2304, 896),        # mellum2_12b_a2p5b_ep4: top 8 of 64, 16 held
])
def test_dropless_layer_moves_only_held_rows_for_v5e(one_chip, d, f):
    """The dropless layer forward and backward at the cells' shapes
    (16,384 positions, a 131,072-row buffer, bf16 rows): the row
    kernels of `ops.moe_rows` compile and take the place of XLA's
    gathers, so no instruction of `moe/dispatch` or `moe/combine` but
    a kernel makes a `[131072, D]` array."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.parallel import moe

    t, k, experts = 16384, 8, 128 if d == 2048 else 64
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda: moe.init_dropless_params(jax.random.key(0), experts, 16,
                                         d, f)))
    x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        return jnp.sum(moe.dropless_ffn(p, x, k=k).y.astype(jnp.float32))

    prev = dtypes.default_policy()
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    try:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()
    finally:
        dtypes.set_default_policy(prev)
    names = re.findall(r"^\s*%(moe_\w+?)(?:\.\d+)? = ", text, re.M)
    assert sorted(names) == sorted(
        ["moe_take_held_rows"] * 2 + ["moe_sum_held_rows"] * 2
        + ["moe_pack_rows"] * 4 + ["moe_grouped_matmul"] * 6
        + ["moe_grouped_matmul_dw"] * 3), names
    # an instruction that computes such an array (not the kernel's call
    # and the element of its tuple result)
    buffer = re.compile(rf"^\s*%\S+ = \(?[a-z0-9]+\[{t * k},{d}\][^=]*? "
                        r"(?!custom-call|get-tuple-element|bitcast)[\w-]+\("
                        r".*op_name=\"[^\"]*moe/(dispatch|combine)", re.M)
    assert not buffer.findall(text)


@pytest.mark.parametrize("b,t,hk,hv,dk,dv,dtype,tiling", [
    # qwen3_next_80b_a3b_ep16.train_seq8k: 2 x 8192 positions, 16 key
    # heads and 32 value heads of 128
    (2, 8192, 16, 32, 128, 128, jnp.bfloat16, (128, 4)),
    (1, 200, 2, 4, 64, 128, jnp.float32, (128, 4)),  # padded to a chunk
    # short sequences: a chunk under the 128 lanes, a batch of one head
    (1, 50, 2, 4, 64, 128, jnp.float32, (64, 1)),
    (2, 20, 1, 2, 64, 64, jnp.bfloat16, (32, 1)),
])
def test_gated_delta_kernels_compile_for_v5e(one_chip, b, t, hk, hv, dk, dv,
                                            dtype, tiling):
    """The chunked gated delta rule's forward and backward kernels, with
    their TN products and the float32 inverse, compile for the v5e on
    the tiling `_tiling` chooses: Hb value heads a step, their products
    batched."""
    from paddle_tpu.ops import gated_delta as GD

    assert GD._tiling(b * hv, t, dk, dv, dtype) == tiling
    before = pallas_util.traced()

    sds = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,
                                                       sharding=one_chip)

    def loss(q, k, v, g, beta):
        o = GD.gated_delta_rule(q, k, v, g, beta, impl="pallas")
        return jnp.sum(o.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        sds((b, t, hk, dk)), sds((b, t, hk, dk)), sds((b, t, hv, dv)),
        sds((b, t, hv), jnp.float32), sds((b, t, hv), jnp.float32)
    ).compile().as_text()
    names = re.findall(r"^\s*%(\w*gated_delta_\w+?)(?:\.\d+)? = ", text,
                       re.M)
    assert sorted(names) == ["gated_delta_bwd", "jvp_gated_delta_fwd_"]
    noted = pallas_util.traced()
    for name in (f"gated_delta.chunk={tiling[0]}",
                 f"gated_delta.heads_per_step={tiling[1]}"):
        assert noted.get(name, 0) > before.get(name, 0), name


def test_checkpointed_hybrid_block_compiles_for_v5e(one_chip):
    """`value_and_grad` of a 2-layer `remat` model, a Gated DeltaNet
    layer and a gated full-attention layer with partial rotary, at 2 x
    2048 positions: the GDN layer runs its forward kernel in the forward
    pass and again, keeping the chunk states, when the backward pass
    recomputes the block; the attention layer keeps its flash output."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.models import transformer as T

    kinds = (("linear_attention", T.AttentionKind(mixer="gated_delta")),
             ("full_attention", T.AttentionKind(output_gate=True,
                                                rotary_dim=64)))
    cfg = T.TransformerConfig(
        vocab=512, dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
        head_size=256, mlp_ratio=1, norm="rms", bias=False, qk_norm=True,
        layer_types=("linear_attention", "full_attention"),
        attention_kinds=kinds, gdn_key_heads=2, gdn_value_heads=4,
        attn_impl="flash", remat=True)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    prev = dtypes.default_policy()
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    try:
        text = jax.jit(jax.value_and_grad(
            lambda p, toks: T.loss(p, cfg, toks))).lower(
                jax.tree.map(lambda x: sds(x.shape, x.dtype), shapes),
                sds((2, 2049), jnp.int32)).compile().as_text()
    finally:
        dtypes.set_default_policy(prev)
    names = re.findall(r"^\s*%(\w*(?:gated_delta|flash_attention)_\w+?)"
                       r"(?:\.\d+)? = ", text, re.M)
    assert sorted(names) == sorted([
        "gated_delta_fwd", "jvp_gated_delta_fwd_", "gated_delta_bwd",
        "jvp_flash_attention_fwd_", "flash_attention_bwd_dkv",
        "flash_attention_bwd_dq"]), names
