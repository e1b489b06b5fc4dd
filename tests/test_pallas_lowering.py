"""Lower every Pallas kernel `auto` dispatch can select for the TPU,
from the CPU host: interpret off (`pallas_util.on_tpu` patched to
True), forward and backward, at the consumer's shapes and dtypes,
through `jit(f).trace(...).lower(lowering_platforms=("tpu",))`. That
runs the whole Pallas->Mosaic-MLIR stage — block-shape rules, SMEM
operands, matmul forms — which is where the compiler first refused the
flash and ragged kernels, in seconds and with no chip. The Mosaic
compile proper and the numerics only happen on a chip
(benchmarks/kernel_check.py, chip_smoke.py)."""

import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.ops import pallas_util, rnn
from paddle_tpu.ops import ragged_paged_attention as RPA

pytestmark = pytest.mark.pallas


@pytest.fixture(autouse=True)
def _as_if_on_one_chip(monkeypatch):
    monkeypatch.setattr(pallas_util, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    prev = dtypes.default_policy()
    # conftest turns x64 on for the numeric-gradient suites; the chip
    # runs without it, and Mosaic has no float64
    with jax.enable_x64(False):
        yield
    dtypes.set_default_policy(prev)


def _lowered_text(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _lower_for_tpu(fn, *args) -> int:
    """Lower; return how many Mosaic kernels the program holds."""
    return _lowered_text(fn, *args).count("tpu_custom_call")


def _kernels(text: str) -> list:
    """(kernel name, operand types) of every Mosaic call a lowered
    program makes, in program order: ('flash_attention_fwd',
    ['tensor<4xi32>', 'tensor<4x256x128xbf16>', ...]). A kernel inside
    a function (a jitted entry point, lowered once) counts once a call."""
    body, fun = {}, None
    for ln in text.splitlines():
        head = re.search(r"func\.func (?:public |private )?@(\w+)\(", ln)
        if head:
            fun = head.group(1)
            body[fun] = []
        elif "@tpu_custom_call" in ln:
            body[fun].append((
                re.search(r'kernel_name = "([^"]*)"', ln).group(1),
                re.findall(r"tensor<[^>]*>",
                           re.search(r" : \((.*?)\) -> ", ln).group(1))))
        elif fun is not None:
            body[fun] += re.findall(r"\bcall @(\w+)\(", ln)

    def expand(fun):
        return [k for item in body[fun]
                for k in (expand(item) if isinstance(item, str) else [item])]

    return expand("main")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("batch,t,heads,head_dim,window,lens", [
    (2, 2048, 8, 64, None, False),   # the serving/training width (dim 512)
    (2, 2048, 4, 128, None, False),
    (2, 2048, 8, 64, 512, False),    # sliding-window configs
    (2, 2048, 8, 64, None, True),    # the engine's bucket-padded prefill
    # starcoder2_3b_l4.train_seq4k: BH 48, 4095 padded to 4096, window inert
    (2, 4095, 24, 128, 4096, False),
])
def test_flash_fwd_bwd_lowers(batch, t, heads, head_dim, window, lens):
    """float32 q, k, v under the bf16 policy: what a biased qkv
    projection hands `_attention` (float32 bias promotes the product).
    The kernel must still get the policy's bf16."""
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    cfg = T.TransformerConfig(vocab=128, dim=heads * head_dim,
                              n_heads=heads, n_layers=1,
                              attn_impl="auto", attn_window=window)
    x = _sds((batch, t, heads, head_dim), jnp.float32)
    key_lens = jnp.asarray([t, 300], jnp.int32) if lens else None

    def loss(q, k, v):
        o = T._attention(cfg, q, k, v, causal=True, key_lens=key_lens)
        return jnp.sum(o.astype(jnp.float32))

    text = _lowered_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    # three kernels: the forward, and the backward's dk/dv and dq
    # kernels on their own blocks; every tensor operand that carries q,
    # k, v or the output's cotangent is bf16, lse and delta float32 rows
    (fwd_name, fwd), (dkv_name, dkv), (dq_name, dq) = _kernels(text)
    # a window that cuts names its kernels apart; an inert one does not
    cut = "_window" if window is not None and window < t else ""
    assert (fwd_name, dkv_name, dq_name) == (
        "flash_attention_fwd" + cut, "flash_attention_bwd_dkv" + cut,
        "flash_attention_bwd_dq" + cut)
    bh = batch * heads
    # q padded to whole q blocks, k and v to whole k blocks, of the
    # chooser's (1024 x 1024 at these lengths: 4095 becomes 4096)
    tq_pad, tk_pad = (-(-t // block) * block for block in
                      FA._forward_blocks(t, t, head_dim, jnp.bfloat16))
    assert fwd == [f"tensor<{bh}xi32>",
                   f"tensor<{bh}x{tq_pad}x{head_dim}xbf16>"] + [
        f"tensor<{bh}x{tk_pad}x{head_dim}xbf16>"] * 2
    block = max(FA.BWD_BLOCK_Q, FA.BWD_BLOCK_K)
    t_bwd = -(-t // block) * block
    wide = f"tensor<{bh}x{t_bwd}x{head_dim}xbf16>"
    row = f"tensor<{bh}x1x{t_bwd}xf32>"
    assert dkv == dq == [f"tensor<{bh}xi32>", wide, wide, row, row,
                         wide, wide]


@pytest.mark.parametrize("bf16", [True, False])
def test_model_hands_flash_the_policys_dtype(bf16):
    """The consumer's real path: grad of `T.loss` on the default block,
    whose qkv, proj, fc1 and fc2 all carry float32 biases. Under the
    bf16 policy every tensor operand of the flash kernel is bf16, as
    the policy promises for every matmul's inputs."""
    if bf16:
        dtypes.set_default_policy(dtypes.bf16_compute_policy())
    # `jax.checkpoint` keeps its traces by (function, static args,
    # avals) and cannot see the policy: block 2's float32 input under
    # bf16 would hand the float32 case the bf16 case's trace
    jax.clear_caches()
    cfg = T.TransformerConfig(vocab=128, dim=256, n_heads=2, n_kv_heads=1,
                              n_layers=2, attn_impl="auto", remat=True)
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    assert params["blocks"][0]["qkv"]["bias"].dtype == jnp.float32
    text = _lowered_text(jax.grad(lambda p, toks: T.loss(p, cfg, toks)),
                         params, _sds((2, 257), jnp.int32))
    calls = _kernels(text)
    # 2 layers: the forward, once (the checkpointed block keeps its
    # output and log-sum-exp by name, PR 38), and the two backward
    # kernels of each
    names = [name for name, _ in calls]
    assert sorted(names) == sorted(
        ["flash_attention_fwd", "flash_attention_bwd_dkv",
         "flash_attention_bwd_dq"] * 2)
    want = "bf16" if bf16 else "f32"
    wide, row = f"tensor<4x256x128x{want}>", "tensor<4x1x256xf32>"
    for name, operands in calls:
        assert operands == ["tensor<4xi32>"] + (
            [wide, wide, row, row, wide, wide] if "bwd" in name
            else [wide] * 3), name


# a dropless layer's kernels in a step under remat
EXPERTS = ["moe_grouped_matmul"] * 9 + ["moe_grouped_matmul_dw"] * 3
ROWS = (["moe_take_held_rows"] * 3 + ["moe_sum_held_rows"] * 2
        + ["moe_pack_rows"] * 5)


def test_block_diffusion_model_lowers_with_its_kernels():
    """The second LM configuration's real path: grad of
    `T.block_diffusion_loss` on an RMSNorm, bias-free, QK-normed block
    with heads of their own size and a dropless MoE that holds half of
    its experts, under the bf16 policy. A layer launches the flash
    forward (once: the checkpointed block keeps what the kernel names)
    and its two backward kernels on bf16 operands over 2L positions,
    the grouped products: three forward, three again under remat,
    three input gradients, three weight gradients; and the row kernels
    of `ops.moe_rows`: the take by slot forward, again under remat and
    in the combine's backward, the sum by position in the combine's
    forward and the take's backward, each behind a pack into words."""
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    jax.clear_caches()
    pallas_util._traced.clear()
    cfg = T.TransformerConfig(
        vocab=128, dim=256, n_heads=4, n_kv_heads=2, head_size=128,
        n_layers=2, norm="rms", bias=False, qk_norm=True,
        moe_router="dropless", moe_experts=8, moe_every=1, moe_k=2,
        moe_dim=128, moe_held=4, attn_impl="auto", remat=True,
        fused_ce_chunk=128)
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    length = 256

    def loss(p, toks, masked, prob):
        return T.block_diffusion_loss(p, cfg, toks, masked, prob,
                                      block_length=4)[0]

    text = _lowered_text(
        jax.grad(loss), params, _sds((2, length), jnp.int32),
        _sds((2, length), jnp.bool_), _sds((2, length), jnp.float32))
    calls = _kernels(text)
    names = [name for name, _ in calls]
    assert sorted(names) == sorted(
        (["flash_attention_fwd", "flash_attention_bwd_dkv",
          "flash_attention_bwd_dq"] + EXPERTS + ROWS) * 2)
    wide = f"tensor<8x{2 * length}x128xbf16>"       # B * H, 2L, head size
    for name, operands in calls:
        if name.startswith("flash"):
            assert operands.count(wide) == (4 if "bwd" in name else 3), name
        elif name.startswith("moe_grouped"):
            # the row buffer holds every choice: 2 * 2L * k rows
            assert any(op.startswith(f"tensor<{2 * 2 * length * 2}x")
                       and op.endswith("xbf16>") for op in operands), name
    traced = pallas_util.traced()
    assert traced["flash_attention.mask=block_diffusion"] > 0
    assert traced["transformer.ffn=moe_dropless"] > 0
    assert traced["moe.expert_matmul=pallas_grouped"] > 0
    assert traced["moe.row_gather=held_rows"] > 0


def test_model_with_kinds_by_layer_lowers_with_its_kernels():
    """The third LM configuration's real path: grad of `T.loss_and_aux`
    on a block whose layers are sliding, sliding, sliding, full, with
    YaRN on the full one and a dropless MoE that holds a quarter of its
    experts, under the bf16 policy. The three band layers launch the
    flash kernels under their `_window` names, the full layer under the
    plain ones: three to one, the forward once a layer (the
    checkpointed block keeps its output and log-sum-exp)."""
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    jax.clear_caches()
    pallas_util._traced.clear()
    kinds = (("sliding", T.AttentionKind(window=128)),
             ("full", T.AttentionKind(rope_scaling="yarn", rope_factor=16.0,
                                      rope_original=256)))
    cfg = T.TransformerConfig(
        vocab=128, dim=256, n_heads=4, n_kv_heads=2, head_size=128,
        n_layers=4, norm="rms", bias=False, qk_norm=True,
        layer_types=("sliding",) * 3 + ("full",), attention_kinds=kinds,
        moe_router="dropless", moe_experts=16, moe_every=1, moe_k=2,
        moe_dim=128, moe_held=4, attn_impl="auto", remat=True,
        fused_ce_chunk=128)
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    text = _lowered_text(
        jax.grad(lambda p, toks: T.loss_and_aux(p, cfg, toks)[0]), params,
        _sds((2, 513), jnp.int32))
    names = [name for name, _ in _kernels(text)]
    band = ["flash_attention_fwd_window",
            "flash_attention_bwd_dkv_window", "flash_attention_bwd_dq_window"]
    full = ["flash_attention_fwd", "flash_attention_bwd_dkv",
            "flash_attention_bwd_dq"]
    assert sorted(names) == sorted(3 * band + full + 4 * (EXPERTS + ROWS))
    traced = pallas_util.traced()
    assert traced["transformer.layer_kinds=sliding:3,full:1"] > 0
    assert traced["transformer.rope=sliding:none,full:yarn"] > 0
    assert traced["flash_attention.mask=window"] > 0
    # a (batch x head) row of the band call and of the full call
    assert traced["flash_attention.fwd_block_kinds="
                  "interior:0,cut:1,skipped:0"] > 0


@pytest.mark.parametrize("name,run,init,hidden,t", [
    ("gru", rnn.gru, rnn.init_gru_params, 512, 30),          # seq2seq
    ("lstm", rnn.lstm, rnn.init_lstm_params, 256, 100),      # classifier
    ("lstm", rnn.lstm, rnn.init_lstm_params, 512, 100),
    ("rnn", rnn.simple_rnn, rnn.init_rnn_params, 512, 100),
])
@pytest.mark.parametrize("bf16", [True, False])
def test_fused_rnn_fwd_bwd_lowers(name, run, init, hidden, t, bf16):
    if bf16:
        dtypes.set_default_policy(dtypes.bf16_compute_policy())
    b = 64
    params = jax.eval_shape(lambda: init(jax.random.key(0), hidden, hidden))
    lens = jnp.full((b,), t, jnp.int32)

    def loss(p, x):
        out, _ = run(p, x, lens)                 # impl="auto"
        return jnp.sum(out.astype(jnp.float32))

    n = _lower_for_tpu(jax.grad(loss), params,
                       _sds((b, t, hidden), jnp.float32))
    assert n == 2, f"{name}: expected fwd+bwd kernels, got {n}"


def test_ragged_auto_lowers_without_the_kernel():
    """The ragged kernel is deselected: the serving read at the
    benchmark width lowers for TPU as plain XLA, no Mosaic call."""
    page, hkv, dh, rows = 16, 8, 64, 8
    arena = _sds((rows * 128, page, hkv, dh), jnp.bfloat16)

    def read(q, ka, va, pt, pos0, active):
        return RPA.ragged_attention(q, ka, va, pt, pos0, active,
                                    page_size=page, max_len=2048)

    n = _lower_for_tpu(read, _sds((rows, 1, 8, dh), jnp.bfloat16), arena,
                       arena, _sds((rows, 128), jnp.int32),
                       _sds((rows,), jnp.int32), _sds((rows,), jnp.bool_))
    assert n == 0



def test_auto_keeps_kernels_out_of_partitioned_programs(monkeypatch):
    """XLA cannot split a Mosaic call. On a host with several devices
    `auto` selects a kernel only inside a shard_map over the whole mesh
    (per-device shapes); in a plain jit, which may be partitioned, it
    takes the XLA path."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    params = jax.eval_shape(
        lambda: rnn.init_gru_params(jax.random.key(0), 128, 128))
    x = _sds((8, 6, 128), jnp.float32)

    def run(p, x):
        return rnn.gru(p, x)[0]

    assert _lower_for_tpu(run, params, x) == 0
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    inside = jax.shard_map(run, mesh=mesh, in_specs=(P(), P("data")),
                           out_specs=P("data"), check_vma=False)
    assert _lower_for_tpu(inside, params, x) == 1


def test_hybrid_model_lowers_with_the_gated_delta_kernels():
    """`auto` on one chip: a Gated DeltaNet layer hands the chunked rule
    the policy's bf16 q, k (repeated to the value heads) and v, float32
    decays and betas by chunk, and its backward gets the chunk states in
    float32; the gated full layer runs the flash kernels."""
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    kinds = (("linear_attention", T.AttentionKind(mixer="gated_delta")),
             ("full_attention", T.AttentionKind(output_gate=True,
                                                rotary_dim=16)))
    cfg = T.TransformerConfig(
        vocab=128, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
        head_size=64, mlp_ratio=1, norm="rms", bias=False, qk_norm=True,
        layer_types=("linear_attention", "full_attention"),
        attention_kinds=kinds, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=64, gdn_value_dim=64, attn_impl="auto")
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    text = _lowered_text(jax.grad(lambda p, toks: T.loss(p, cfg, toks)),
                         shapes, _sds((2, 257), jnp.int32))
    kernels = dict(_kernels(text))
    rows = "tensor<8x256x64xbf16>"          # 2 x 4 value heads, 2 chunks
    chunks = "tensor<8x2x1x128xf32>"
    assert kernels["gated_delta_fwd"] == [rows] * 3 + [chunks] * 2
    assert kernels["gated_delta_bwd"] == [rows] * 3 + [chunks] * 2 + [
        "tensor<8x2x64x64xf32>", rows]
    assert {"flash_attention_fwd", "flash_attention_bwd_dkv",
            "flash_attention_bwd_dq"} <= set(kernels)
