"""Transformer LM tests: causality, training signal, KV-cache decode
consistency, and dp x tp sharded-step equivalence on the 8-CPU mesh.

The model has no reference counterpart (the reference predates
transformers); these tests follow the same strategies SURVEY §4 lists —
impl-vs-impl equivalence (KV-cache decode vs teacher forcing, sharded vs
single-device) and gradient checks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import pallas_util
from paddle_tpu.ops.flash_attention import flash_attention


CFG = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")


@pytest.fixture
def params():
    return T.init_params(jax.random.key(0), CFG)


def test_shapes_and_finite(params):
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 61, (3, 12)))
    logits = T.apply(params, CFG, toks)
    assert logits.shape == (3, 12, 61)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(params):
    """Logits at position t must not depend on tokens after t."""
    rs = np.random.RandomState(1)
    a = rs.randint(0, 61, (1, 10))
    b = a.copy()
    b[0, 7:] = (b[0, 7:] + 5) % 61  # perturb the future
    la = T.apply(params, CFG, jnp.asarray(a))
    lb = T.apply(params, CFG, jnp.asarray(b))
    np.testing.assert_allclose(la[0, :7], lb[0, :7], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(la[0, 7:] - lb[0, 7:]))) > 1e-4


def test_loss_mask(params):
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 61, (2, 9)))
    full = T.loss(params, CFG, toks)
    short = T.loss(params, CFG, toks, lengths=jnp.asarray([3, 4]))
    assert np.isfinite(float(full)) and np.isfinite(float(short))
    # a loss() that ignores lengths would return the same value
    assert not np.isclose(float(full), float(short))


def test_overfits_tiny_batch(params):
    """A few adam steps on one repeated batch must cut the loss — the
    training-signal smoke the book tests use (SURVEY §4 e2e row)."""
    from paddle_tpu import optim

    toks = jnp.asarray(np.random.RandomState(3).randint(0, 61, (4, 16)))
    opt = optim.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(lambda p: T.loss(p, CFG, toks))(p)
        p2, s2 = opt.update(g, s, p, jnp.zeros((), jnp.int32))
        return p2, s2, l

    first = None
    for _ in range(30):
        params, opt_state, l = step(params, opt_state)
        if first is None:
            first = float(l)
    assert float(l) < first * 0.7, (first, float(l))


def _forward_kernel_calls(jaxpr) -> int:
    """The flash forward kernel's `pallas_call`s of a jaxpr, whichever
    sub-jaxpr (checkpoint, custom_vjp, jit) holds them."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"].startswith("flash_attention_fwd")
        n += sum(_forward_kernel_calls(sub)
                 for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


def _remat_saved_noted(before: dict) -> list:
    """The `transformer.remat_saved=` counters written since `before`."""
    return sorted(k for k, v in pallas_util.traced().items()
                  if k.startswith("transformer.remat_saved=")
                  and v > before.get(k, 0))


REMAT_NAMES = "transformer.remat_saved=flash_attention_out,flash_attention_lse"


@pytest.mark.parametrize("case,kw", [
    ("causal", dict()),
    ("window", dict(attn_window=5)),            # cuts the 12 positions
    ("block_diffusion", dict()),
])
def test_remat_keeps_what_the_flash_kernel_names(params, monkeypatch, case,
                                                 kw):
    """A checkpointed block keeps the kernel's output and log-sum-exp by
    name, so `value_and_grad` holds the forward kernel once a layer
    where a plain `jax.checkpoint` holds it twice, and the kept arrays
    are the ones the recomputation would produce: loss and gradients
    equal those of the plain checkpoint and of no checkpoint bit for
    bit."""
    jax.clear_caches()      # `jax.checkpoint` keeps a block's trace
    plain = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                                attn_impl="flash", **kw)
    remat = dataclasses.replace(plain, remat=True)
    toks = jnp.asarray(np.random.RandomState(5).randint(0, 60, (2, 13)))
    if case == "block_diffusion":
        toks = toks[:, :8]
        masked, prob = T.block_diffusion_noise(jax.random.key(1), toks, 4)
        loss = lambda cfg: lambda q: T.block_diffusion_loss(
            q, cfg, toks, masked, prob, block_length=4)[0]
    else:
        loss = lambda cfg: lambda q: T.loss(q, cfg, toks)

    def run(cfg):
        # compiled unoptimised: optimised, XLA fuses the three programs
        # apart and a sum's order (its last digit) is then the
        # compiler's choice, not the checkpoint's
        traced = jax.jit(jax.value_and_grad(loss(cfg))).trace(params)
        compiled = traced.lower().compile(
            compiler_options={"xla_backend_optimization_level": 0})
        return compiled(params), _forward_kernel_calls(traced.jaxpr.jaxpr)

    before = pallas_util.traced()
    none, calls_none = run(plain)
    assert _remat_saved_noted(before) == []
    named, calls_named = run(remat)
    assert _remat_saved_noted(before) == [REMAT_NAMES]
    # the checkpoint of every PR before 38: no policy, the input alone
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    kept_input_alone, calls_plain = run(remat)
    assert (calls_none, calls_named, calls_plain) == (
        remat.n_layers, remat.n_layers, 2 * remat.n_layers)
    for other in (none, kept_input_alone):
        for a, b in zip(jax.tree_util.tree_leaves(named),
                        jax.tree_util.tree_leaves(other)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["dense", "attn_fn"])
def test_remat_keeps_the_input_alone_where_no_kernel_names(params, case):
    """Dense attention and an injected `attn_fn` (ring / Ulysses) name
    nothing: the checkpointed block keeps its input alone, the counter
    says `none`, and the gradients are those of no checkpoint."""
    jax.clear_caches()      # `jax.checkpoint` keeps a block's trace
    remat = dataclasses.replace(CFG, remat=True)
    toks = jnp.asarray(np.random.RandomState(5).randint(0, 60, (2, 13)))
    attn = None
    if case == "attn_fn":
        attn = lambda q, k, v: T._dense_attention(q, k, v, True)
    grad = lambda cfg: jax.jit(jax.value_and_grad(
        lambda q: T.loss(q, cfg, toks, attn_fn=attn)))(params)
    before = pallas_util.traced()
    want = grad(CFG)
    assert _remat_saved_noted(before) == []
    got = grad(remat)
    assert _remat_saved_noted(before) == ["transformer.remat_saved=none"]
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_generate_matches_teacher_forcing(params):
    """KV-cache greedy decode == argmax over apply() at every step (the
    cache path and the full forward are different codepaths)."""
    prompt = jnp.asarray(np.random.RandomState(4).randint(0, 61, (2, 5)))
    steps = 6
    out = T.generate(params, CFG, prompt, steps)
    assert out.shape == (2, 5 + steps)
    np.testing.assert_array_equal(out[:, :5], prompt)
    cur = prompt
    for _ in range(steps):
        logits = T.apply(params, CFG, cur)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(prompt.dtype)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, cur)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_remat_matches(params):
    toks = jnp.asarray(np.random.RandomState(5).randint(0, 61, (2, 8)))
    cfg_r = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                                attn_impl="dense", remat=True)
    np.testing.assert_allclose(T.loss(params, CFG, toks),
                               T.loss(params, cfg_r, toks), rtol=1e-6)
    g0 = jax.grad(lambda p: T.loss(p, CFG, toks))(params)
    g1 = jax.grad(lambda p: T.loss(p, cfg_r, toks))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_tp_sharded_loss_matches(params):
    """dp x tp over the 8-CPU mesh computes the same loss/grads as one
    device (GSPMD inserts the collectives; TP_RULES shard qkv/fc1 by
    output, proj/fc2 by input, lm_head by vocab)."""
    from paddle_tpu.core import mesh as mesh_lib
    from paddle_tpu.parallel import sharding as shard_lib

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(data=2, model=4))
    toks = jnp.asarray(np.random.RandomState(6).randint(0, 61, (4, 12)))

    ref_loss = T.loss(params, CFG, toks)
    ref_grad = jax.grad(lambda p: T.loss(p, CFG, toks))(params)

    sh = shard_lib.make_param_shardings(params, mesh, T.TP_RULES)
    p_sharded = jax.device_put(params, sh)
    # at least one leaf actually sharded over the model axis
    specs = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda s: s.spec, sh,
                               is_leaf=lambda x: hasattr(x, "spec")))
    assert any("model" in str(s) for s in specs)

    # no ambient mesh needed: the sharded params carry NamedShardings
    # and GSPMD propagates/inserts collectives
    l = jax.jit(lambda p: T.loss(p, CFG, toks))(p_sharded)
    g = jax.jit(jax.grad(lambda p: T.loss(p, CFG, toks)))(p_sharded)
    np.testing.assert_allclose(l, ref_loss, rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grad),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_transformer_serving_artifact(tmp_path, params):
    """The generic StableHLO artifact path serves the transformer LM
    (weights folded; greedy next-token head)."""
    from paddle_tpu.serve import export_compiled_model, load_compiled_model

    path = str(tmp_path / "lm.ptc")
    toks = jnp.asarray(np.random.RandomState(9).randint(0, 61, (2, 12)))

    def next_token_logits(toks):
        return T.apply(params, CFG, toks)[:, -1]

    export_compiled_model(next_token_logits, [toks], path, name="tiny-lm")
    m = load_compiled_model(path)
    got = m.predict(np.asarray(toks))
    want = next_token_logits(toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


class TestContextParallel:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_cp_loss_matches_dense(self):
        """Sequence-sharded (ring attention) transformer loss must equal
        the single-device dense loss — values and gradients."""
        from paddle_tpu.core import mesh as mesh_lib

        cfg = T.TransformerConfig(vocab=64, dim=16, n_layers=2, n_heads=2,
                                  mlp_ratio=2, attn_impl="dense")
        params = T.init_params(jax.random.key(0), cfg)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(data=2, model=1, seq=4),
            devices=jax.devices()[:8])
        # T = 16 sharded positions + 1 for targets
        toks_h = np.random.RandomState(0).randint(0, 64, (4, 17)) \
            .astype(np.int32)
        toks = jax.device_put(
            toks_h, jax.NamedSharding(mesh, jax.sharding.PartitionSpec(
                mesh_lib.DATA_AXIS, None)))
        cp_loss = T.make_context_parallel_loss(
            cfg, mesh, batch_axis=mesh_lib.DATA_AXIS)

        dense = float(T.loss(params, cfg, jnp.asarray(toks_h)))
        cp = float(jax.jit(cp_loss)(params, toks))
        assert abs(dense - cp) < 1e-4, (dense, cp)

        g_dense = jax.grad(lambda p: T.loss(p, cfg, jnp.asarray(toks_h)))(
            params)
        g_cp = jax.jit(jax.grad(cp_loss))(params, toks)
        for a, b in zip(jax.tree_util.tree_leaves(g_dense),
                        jax.tree_util.tree_leaves(g_cp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_cp_with_remat_and_lengths(self):
        from paddle_tpu.core import mesh as mesh_lib

        cfg = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                                  mlp_ratio=2, attn_impl="dense",
                                  remat=True)
        params = T.init_params(jax.random.key(1), cfg)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(data=1, model=1, seq=8),
            devices=jax.devices()[:8])
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 32, (2, 25)), jnp.int32)
        lens = jnp.asarray([24, 17])
        cp_loss = T.make_context_parallel_loss(cfg, mesh)
        dense = float(T.loss(params, cfg, toks, lens))
        cp = float(jax.jit(cp_loss)(params, toks, lens))
        assert abs(dense - cp) < 1e-4, (dense, cp)


    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_cp_matches_dense_under_bf16_policy(self):
        """The f32-scores invariant must hold inside ring attention too:
        under the bf16 compute policy CP and dense stay within bf16
        round-off of each other."""
        from paddle_tpu.core import dtypes
        from paddle_tpu.core import mesh as mesh_lib

        cfg = T.TransformerConfig(vocab=64, dim=16, n_layers=2, n_heads=2,
                                  mlp_ratio=2, attn_impl="dense")
        params = T.init_params(jax.random.key(2), cfg)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(data=1, model=1, seq=8),
            devices=jax.devices()[:8])
        toks = jnp.asarray(
            np.random.RandomState(2).randint(0, 64, (2, 33)), jnp.int32)
        old = dtypes.default_policy()
        dtypes.set_default_policy(dtypes.bf16_compute_policy())
        try:
            cp_loss = T.make_context_parallel_loss(cfg, mesh)
            dense = float(T.loss(params, cfg, toks))
            cp = float(jax.jit(cp_loss)(params, toks))
        finally:
            dtypes.set_default_policy(old)
        assert abs(dense - cp) < 3e-2 * max(1.0, abs(dense)), (dense, cp)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_cp_composes_with_moe(self):
        """Context parallelism and MoE blocks in one model: the seq-
        sharded loss must still equal the single-device loss (routing is
        over the same global token set either way)."""
        from paddle_tpu.core import mesh as mesh_lib

        cfg = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                                  mlp_ratio=2, attn_impl="dense",
                                  moe_experts=4, moe_capacity_factor=8.0)
        params = T.init_params(jax.random.key(3), cfg)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(data=1, model=1, seq=8),
            devices=jax.devices()[:8])
        toks = jnp.asarray(
            np.random.RandomState(3).randint(0, 32, (2, 33)), jnp.int32)
        cp_loss = T.make_context_parallel_loss(cfg, mesh)
        dense = float(T.loss(params, cfg, toks))
        cp = float(jax.jit(cp_loss)(params, toks))
        assert abs(dense - cp) < 1e-4, (dense, cp)


class TestSampling:
    CFG = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                              mlp_ratio=2, attn_impl="dense")

    @pytest.mark.slow

    def test_temperature_zero_is_greedy(self):
        params = T.init_params(jax.random.key(0), self.CFG)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 32, (3, 5)), jnp.int32)
        greedy = T.generate(params, self.CFG, prompt, steps=6)
        sampled = T.sample(params, self.CFG, prompt, steps=6,
                           rng=jax.random.key(1), temperature=0.0)
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.asarray(sampled))

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_sampling_deterministic_per_key_and_varies(self):
        params = T.init_params(jax.random.key(0), self.CFG)
        prompt = jnp.zeros((2, 4), jnp.int32)
        a = T.sample(params, self.CFG, prompt, steps=8,
                     rng=jax.random.key(7), temperature=1.5)
        b = T.sample(params, self.CFG, prompt, steps=8,
                     rng=jax.random.key(7), temperature=1.5)
        c = T.sample(params, self.CFG, prompt, steps=8,
                     rng=jax.random.key(8), temperature=1.5)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_top_k_and_top_p_filters(self):
        # direct selector check on a known distribution
        logits = jnp.log(jnp.asarray(
            [[0.5, 0.3, 0.15, 0.05]], jnp.float32))
        draws = []
        sel = T.make_sampler(top_k=2)
        for i in range(64):
            draws.append(int(sel(logits, jax.random.key(i))[0]))
        assert set(draws) <= {0, 1}
        draws = []
        sel = T.make_sampler(top_p=0.6)
        for i in range(64):
            draws.append(int(sel(logits, jax.random.key(i))[0]))
        # nucleus 0.6: token 0 (mass 0.5, preceding 0) and token 1
        # (preceding 0.5 < 0.6) survive; token 2 (preceding 0.8) doesn't
        assert set(draws) <= {0, 1}
        # extreme: tiny top_p keeps only the argmax
        sel = T.make_sampler(top_p=1e-6)
        assert int(sel(logits, jax.random.key(0))[0]) == 0

    def test_sampler_validation_and_combined_filters(self):
        import pytest as _pytest
        with _pytest.raises(ValueError, match="top_k"):
            T.make_sampler(top_k=0)
        with _pytest.raises(ValueError, match="top_p"):
            T.make_sampler(top_p=0.0)
        with _pytest.raises(ValueError, match="top_p"):
            T.make_sampler(top_p=1.5)
        # combined: nucleus over the top-k-filtered distribution.
        # probs .4/.3/.2/.1 -> top_k=3 renormalizes to .444/.333/.222;
        # top_p=.5 then keeps tokens 0 (preceding 0) and 1 (preceding
        # .444 < .5) but NOT 2 (preceding .777)
        logits = jnp.log(jnp.asarray([[0.4, 0.3, 0.2, 0.1]], jnp.float32))
        sel = T.make_sampler(top_k=3, top_p=0.5)
        draws = {int(sel(logits, jax.random.key(i))[0]) for i in range(64)}
        assert draws == {0, 1}, draws

    def test_top_k_beyond_vocab_is_noop(self):
        # k >= vocab must degrade to no filtering, not index OOB
        logits = jnp.log(jnp.asarray(
            [[0.4, 0.3, 0.2, 0.1]], jnp.float32))
        sel = T.make_sampler(top_k=9)
        draws = {int(sel(logits, jax.random.key(i))[0]) for i in range(96)}
        assert draws == {0, 1, 2, 3}, draws

    @pytest.mark.slow

    def test_eos_stops_generation(self):
        """After a row emits eos, every later position is pad."""
        params = T.init_params(jax.random.key(0), self.CFG)
        prompt = jnp.asarray(
            np.random.RandomState(4).randint(0, 32, (4, 5)), jnp.int32)
        # pick the greedy run's own 2nd generated token as "eos" for row0
        free = np.asarray(T.generate(params, self.CFG, prompt, steps=8))
        eos = int(free[0, 5 + 1])
        out = np.asarray(T.generate(params, self.CFG, prompt, steps=8,
                                    eos_id=eos, pad_id=0))
        for b in range(out.shape[0]):
            row = out[b, 5:]
            hits = np.where(row == eos)[0]
            if hits.size:
                assert (row[hits[0] + 1:] == 0).all(), (b, row)
        # row 0 definitely hit it at step 1
        assert (out[0, 5 + 2:] == 0).all(), out[0]


class TestVariableLengthPrompts:
    CFG = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                              mlp_ratio=2, attn_impl="dense")

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_padded_row_matches_solo_run(self):
        """A short prompt decoded inside a padded batch must produce
        exactly the continuation it gets when decoded alone."""
        params = T.init_params(jax.random.key(0), self.CFG)
        r = np.random.RandomState(0)
        long_p = r.randint(1, 32, (1, 8)).astype(np.int32)
        short_p = r.randint(1, 32, (1, 5)).astype(np.int32)

        solo = np.asarray(T.generate(params, self.CFG,
                                     jnp.asarray(short_p), steps=6))
        batch = np.zeros((2, 8), np.int32)
        batch[0] = long_p[0]
        batch[1, :5] = short_p[0]
        lens = jnp.asarray([8, 5], jnp.int32)
        out = np.asarray(T.generate(params, self.CFG, jnp.asarray(batch),
                                    steps=6, prompt_lens=lens))
        # row 1's continuation (cols 8..13) == solo continuation (5..10)
        np.testing.assert_array_equal(out[1, 8:], solo[0, 5:11])
        # row 0 (full length) must match an unpadded batch-of-one run
        full = np.asarray(T.generate(params, self.CFG,
                                     jnp.asarray(long_p), steps=6))
        np.testing.assert_array_equal(out[0, 8:], full[0, 8:])

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_variable_length_sampling_matches_solo(self):
        """sample() forwards prompt_lens: with temperature 0 (greedy)
        the padded short row must equal its solo sampled run."""
        params = T.init_params(jax.random.key(1), self.CFG)
        r = np.random.RandomState(1)
        short_p = r.randint(1, 32, (1, 4)).astype(np.int32)
        batch = np.zeros((2, 7), np.int32)
        batch[0] = r.randint(1, 32, 7)
        batch[1, :4] = short_p[0]
        out = np.asarray(T.sample(
            params, self.CFG, jnp.asarray(batch), steps=5,
            rng=jax.random.key(2), temperature=0.0,
            prompt_lens=jnp.asarray([7, 4], jnp.int32)))
        solo = np.asarray(T.sample(params, self.CFG, jnp.asarray(short_p),
                                   steps=5, rng=jax.random.key(2),
                                   temperature=0.0))
        np.testing.assert_array_equal(out[1, 7:], solo[0, 4:9])

    @pytest.mark.slow

    def test_flash_prefill_matches_dense_prefill(self):
        """attn_impl='flash' + prompt_lens: the prefill rides the Pallas
        kernel's per-row key-length bound and must reproduce the dense
        masked prefill's continuations exactly."""
        import dataclasses as dc
        params = T.init_params(jax.random.key(3), self.CFG)
        r = np.random.RandomState(3)
        batch = np.zeros((2, 8), np.int32)
        batch[0] = r.randint(1, 32, 8)
        batch[1, :5] = r.randint(1, 32, 5)
        lens = jnp.asarray([8, 5], jnp.int32)
        dense = np.asarray(T.generate(params, self.CFG, jnp.asarray(batch),
                                      steps=3, prompt_lens=lens))
        flash_cfg = dc.replace(self.CFG, attn_impl="flash")
        flash = np.asarray(T.generate(params, flash_cfg, jnp.asarray(batch),
                                      steps=3, prompt_lens=lens))
        np.testing.assert_array_equal(flash, dense)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_padded_row_matches_solo_with_moe(self):
        """Pad positions must not claim MoE expert capacity: at a
        no-drop capacity the padded short row still equals its solo
        continuation through sparse blocks."""
        cfg = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                                  mlp_ratio=2, attn_impl="dense",
                                  moe_experts=4, moe_capacity_factor=8.0)
        params = T.init_params(jax.random.key(2), cfg)
        r = np.random.RandomState(2)
        short_p = r.randint(1, 32, (1, 5)).astype(np.int32)
        batch = np.zeros((2, 8), np.int32)
        batch[0] = r.randint(1, 32, 8)
        batch[1, :5] = short_p[0]
        out = np.asarray(T.generate(
            params, cfg, jnp.asarray(batch), steps=4,
            prompt_lens=jnp.asarray([8, 5], jnp.int32)))
        solo = np.asarray(T.generate(params, cfg, jnp.asarray(short_p),
                                     steps=4))
        np.testing.assert_array_equal(out[1, 8:], solo[0, 5:9])


class TestBeamDecode:
    CFG = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                              mlp_ratio=2, attn_impl="dense")

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_beam1_equals_greedy(self):
        params = T.init_params(jax.random.key(0), self.CFG)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(1, 32, (3, 6)), jnp.int32)
        greedy = np.asarray(T.generate(params, self.CFG, prompt, steps=5))
        seqs, scores = T.beam_decode(params, self.CFG, prompt, steps=5,
                                     beam_size=1)
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]), greedy)

    @pytest.mark.slow

    def test_beam1_int8_equals_greedy_int8(self):
        """Quantized params stream s8 through the beam loop (r5 shared
        _int8_step_params hook); scoring must match int8 greedy
        exactly (both decode on the same dequantized values)."""
        from paddle_tpu.serve import quant

        params = T.init_params(jax.random.key(5), self.CFG)
        qp = quant.quantize_params(params)
        prompt = jnp.asarray(
            np.random.RandomState(5).randint(1, 32, (2, 6)), jnp.int32)
        greedy = np.asarray(T.generate(qp, self.CFG, prompt, steps=5))
        seqs, _ = T.beam_decode(qp, self.CFG, prompt, steps=5,
                                beam_size=1)
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]), greedy)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_wider_beam_never_scores_worse(self):
        """The best beam's total log-prob must be >= the greedy
        sequence's (verified with score())."""
        params = T.init_params(jax.random.key(1), self.CFG)
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(1, 32, (2, 6)), jnp.int32)
        steps = 6
        greedy = T.generate(params, self.CFG, prompt, steps=steps)
        seqs, scores = T.beam_decode(params, self.CFG, prompt,
                                     steps=steps, beam_size=4)

        def continuation_logprob(full):
            lp, _ = T.score(params, self.CFG, full)
            return np.asarray(lp)[:, -steps:].sum(axis=1)

        greedy_lp = continuation_logprob(greedy)
        best_lp = continuation_logprob(seqs[:, 0])
        assert (best_lp >= greedy_lp - 1e-4).all(), (greedy_lp, best_lp)
        # the engine's own scores agree with independently recomputed
        # log-probs of the returned sequences
        np.testing.assert_allclose(np.asarray(scores[:, 0]), best_lp,
                                   atol=1e-3)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_single_token_prompt(self):
        """t0 == 1 has nothing to prefill: the caches must start empty
        instead of tracing a T=0 sequence through the blocks, and beam-1
        must still equal greedy from the same one-token prompt."""
        params = T.init_params(jax.random.key(3), self.CFG)
        prompt = jnp.asarray([[5], [17]], jnp.int32)
        greedy = np.asarray(T.generate(params, self.CFG, prompt, steps=4))
        seqs, _ = T.beam_decode(params, self.CFG, prompt, steps=4,
                                beam_size=1)
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]), greedy)
        # wider beam still runs (the r3 advisor flagged the T=0 prefill)
        seqs2, scores2 = T.beam_decode(params, self.CFG, prompt, steps=4,
                                       beam_size=3)
        assert seqs2.shape == (2, 3, 5)
        assert np.isfinite(np.asarray(scores2)).all()

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_eos_finishes_beams(self):
        params = T.init_params(jax.random.key(2), self.CFG)
        prompt = jnp.asarray(
            np.random.RandomState(2).randint(1, 32, (2, 5)), jnp.int32)
        free = np.asarray(T.beam_decode(params, self.CFG, prompt, steps=6,
                                        beam_size=2)[0])
        eos = int(free[0, 0, 5])  # first continuation token of best beam
        seqs, _ = T.beam_decode(params, self.CFG, prompt, steps=6,
                                beam_size=2, eos_id=eos)
        rows = np.asarray(seqs)[0, :, 5:]
        # step-0 candidates are identical to the free run, so SOME beam
        # must emit the free run's first token (= eos) and finish
        assert (rows == eos).any(), rows
        for row in rows:
            hits = np.where(row == eos)[0]
            if hits.size:  # once finished, only eos follows
                assert (row[hits[0]:] == eos).all(), row


class TestGQA:
    """Grouped-query attention: compact KV caches (the decode-bandwidth
    lever), decode ≡ teacher-forced forward, and training."""

    def _cfg(self, kv):
        return T.TransformerConfig(vocab=32, dim=32, n_layers=2,
                                   n_heads=4, n_kv_heads=kv, mlp_ratio=2,
                                   attn_impl="dense")

    def test_invalid_kv_heads_raises(self):
        with pytest.raises(ValueError, match="n_kv_heads"):
            T.init_params(jax.random.key(0), self._cfg(3))

    def test_param_shapes_compact(self):
        cfg = self._cfg(1)
        params = T.init_params(jax.random.key(0), cfg)
        # H*Dh for q + 2 * Hkv*Dh for k/v = 32 + 2*8
        assert params["blocks"][0]["qkv"]["kernel"].shape == (32, 48)

    def test_full_kv_equals_mha_layout(self):
        # n_kv_heads == n_heads must be exactly the MHA parameterization
        cfg = self._cfg(4)
        params = T.init_params(jax.random.key(0), cfg)
        assert params["blocks"][0]["qkv"]["kernel"].shape == (32, 96)

    @pytest.mark.parametrize("kv", [1, 2])
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_decode_matches_forward(self, kv):
        """Greedy decode's token-by-token cached path must reproduce the
        teacher-forced argmax of the full forward — the grouped cached
        einsums against the whole-sequence attention."""
        cfg = self._cfg(kv)
        params = T.init_params(jax.random.key(1), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(1, 32, (2, 6)), jnp.int32)
        assert_decode_matches_teacher_forcing(params, cfg, prompt, 4)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_beam1_matches_greedy(self):
        cfg = self._cfg(2)
        params = T.init_params(jax.random.key(2), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(1, 32, (2, 5)), jnp.int32)
        greedy = np.asarray(T.generate(params, cfg, prompt, steps=4))
        seqs, _ = T.beam_decode(params, cfg, prompt, steps=4, beam_size=1)
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]), greedy)

    def test_trains(self):
        from paddle_tpu import optim
        cfg = self._cfg(2)
        params = T.init_params(jax.random.key(3), cfg)
        opt = optim.adam(3e-3)
        ostate = opt.init(params)
        base = np.random.RandomState(0).randint(0, 16, (8, 1))
        toks = jnp.asarray((base + np.arange(12)) % 16, jnp.int32)

        @jax.jit
        def step(p, o, t, i):
            l, g = jax.value_and_grad(lambda p: T.loss(p, cfg, t))(p)
            p, o = opt.update(g, o, p, i)
            return p, o, l

        first = last = None
        for i in range(40):
            params, ostate, l = step(params, ostate, toks, jnp.asarray(i))
            first = first if first is not None else float(l)
            last = float(l)
        assert last < first * 0.6, (first, last)


class TestSpeculativeDecode:
    """Greedy speculative decoding must produce EXACTLY the target
    model's greedy output — the draft only changes speed. That equality
    holds for any draft, so it's asserted token-for-token."""

    CFG = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                              mlp_ratio=2, attn_impl="dense")

    def _models(self, seed_t=0, seed_d=9):
        target = T.init_params(jax.random.key(seed_t), self.CFG)
        draft_cfg = T.TransformerConfig(vocab=32, dim=8, n_layers=1,
                                        n_heads=2, mlp_ratio=2,
                                        attn_impl="dense")
        draft = T.init_params(jax.random.key(seed_d), draft_cfg)
        return target, draft, draft_cfg

    @pytest.mark.parametrize("k", [1, 3, 5])
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_matches_greedy_with_unrelated_draft(self, k):
        target, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(1, 32, (1, 6)), jnp.int32)
        want = np.asarray(T.generate(target, self.CFG, prompt, steps=7))
        got = np.asarray(T.speculative_generate(
            target, self.CFG, draft, draft_cfg, prompt, steps=7,
            draft_k=k))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_matches_greedy_with_perfect_draft(self):
        """draft == target: every window fully accepts, so `steps`
        tokens take exactly ceil(steps/(k+1)) rounds — the observable
        that catches a draft-cache gap silently collapsing acceptance —
        and the output still equals plain greedy."""
        target, _, _ = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(1, 32, (1, 5)), jnp.int32)
        want = np.asarray(T.generate(target, self.CFG, prompt, steps=10))
        got, rounds = T.speculative_generate(
            target, self.CFG, target, self.CFG, prompt, steps=10,
            draft_k=4, return_stats=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert int(rounds[0]) == 2, rounds  # ceil(10/5); rounds is [B]

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_gqa_target(self):
        cfg = T.TransformerConfig(vocab=32, dim=16, n_layers=2,
                                  n_heads=4, n_kv_heads=1, mlp_ratio=2,
                                  attn_impl="dense")
        target = T.init_params(jax.random.key(2), cfg)
        _, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(2).randint(1, 32, (1, 4)), jnp.int32)
        want = np.asarray(T.generate(target, cfg, prompt, steps=5))
        got = np.asarray(T.speculative_generate(
            target, cfg, draft, draft_cfg, prompt, steps=5, draft_k=3))
        np.testing.assert_array_equal(got, want)

    def test_validates_prompt(self):
        target, draft, draft_cfg = self._models()
        with pytest.raises(ValueError, match="prompt"):
            T.speculative_generate(target, self.CFG, draft, draft_cfg,
                                   jnp.zeros((1, 1), jnp.int32), steps=3)

    @pytest.mark.slow

    def test_int8_target_matches_int8_greedy(self):
        """A quantized TARGET must still decode exactly its own int8
        greedy output (s8 streamed through the round loop via the
        shared _int8_step_params hook); the f32 draft only affects
        speed."""
        from paddle_tpu.serve import quant

        target, draft, draft_cfg = self._models()
        qp = quant.quantize_params(target)
        prompt = jnp.asarray(
            np.random.RandomState(6).randint(1, 32, (2, 6)), jnp.int32)
        want = np.asarray(T.generate(qp, self.CFG, prompt, steps=7))
        got = np.asarray(T.speculative_generate(
            qp, self.CFG, draft, draft_cfg, prompt, steps=7, draft_k=3))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_batched_matches_per_row_greedy(self):
        """Rows accept different prefix lengths (different prompts vs
        the same draft) yet each row's output must equal ITS OWN greedy
        decode — the desync case the r4 batch-1 restriction dodged."""
        target, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(1, 32, (3, 6)), jnp.int32)
        got = np.asarray(T.speculative_generate(
            target, self.CFG, draft, draft_cfg, prompt, steps=9,
            draft_k=3))
        for i in range(3):
            want = np.asarray(T.generate(
                target, self.CFG, prompt[i:i + 1], steps=9))
            np.testing.assert_array_equal(got[i:i + 1], want,
                                          err_msg=f"row {i}")

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_batched_mixed_draft_quality(self):
        """One row decodes with a perfect-draft dynamic (target==draft
        would accept everything) while the other disagrees constantly —
        per-row round counts must differ and outputs still match
        per-row greedy."""
        target, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(4).randint(1, 32, (2, 5)), jnp.int32)
        got, rounds = T.speculative_generate(
            target, self.CFG, draft, draft_cfg, prompt, steps=8,
            draft_k=4, return_stats=True)
        assert rounds.shape == (2,)
        assert int(rounds.max()) <= 8
        for i in range(2):
            want = np.asarray(T.generate(
                target, self.CFG, prompt[i:i + 1], steps=8))
            np.testing.assert_array_equal(np.asarray(got)[i:i + 1], want)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_eos_matches_greedy_fill(self):
        """Early-stop parity: pick the eos id that greedy actually
        emits mid-stream, then the speculative output (tokens AND the
        post-eos fill) must equal generate()'s eos output row-for-row,
        and stopped rows must spend fewer rounds than steps."""
        target, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(5).randint(1, 32, (2, 5)), jnp.int32)
        steps = 10
        plain = np.asarray(T.generate(target, self.CFG, prompt,
                                      steps=steps))
        # an id each row emits somewhere in its continuation (fall back
        # to row 0's 3rd token; rows without it just run full length)
        eos = int(plain[0, prompt.shape[1] + 2])
        want = np.asarray(T.generate(target, self.CFG, prompt,
                                     steps=steps, eos_id=eos))
        got, rounds = T.speculative_generate(
            target, self.CFG, draft, draft_cfg, prompt, steps=steps,
            draft_k=3, eos_id=eos, return_stats=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert int(rounds[0]) < steps  # row 0 stopped early


class TestSpeculativeSampling:
    """Speculative SAMPLING must preserve the target's (filtered)
    sampling distribution exactly — the draft changes speed only."""

    CFG = T.TransformerConfig(vocab=16, dim=16, n_layers=2, n_heads=2,
                              mlp_ratio=2, attn_impl="dense")

    def _models(self):
        target = T.init_params(jax.random.key(0), self.CFG)
        draft_cfg = T.TransformerConfig(vocab=16, dim=8, n_layers=1,
                                        n_heads=2, mlp_ratio=2,
                                        attn_impl="dense")
        draft = T.init_params(jax.random.key(9), draft_cfg)
        return target, draft, draft_cfg

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_first_token_distribution_matches_target(self):
        """2000 identical rows, 1 step: the empirical histogram of the
        first sampled token must match the target's filtered softmax at
        the prompt's last position (TV noise at N=2000 is ~0.01/token;
        tolerance 0.05). This is the property the rejection rule
        exists to guarantee — a naive accept-if-likely rule fails it."""
        target, draft, draft_cfg = self._models()
        row = np.random.RandomState(0).randint(1, 16, (1, 4))
        prompt = jnp.asarray(np.repeat(row, 2000, axis=0), jnp.int32)
        out = np.asarray(T.speculative_sample(
            target, self.CFG, draft, draft_cfg, prompt, steps=1,
            rng=jax.random.key(42), draft_k=2, temperature=0.9))
        toks = out[:, 4]
        freq = np.bincount(toks, minlength=16) / 2000.0
        logits = np.asarray(T.apply(
            target, self.CFG, jnp.asarray(row, jnp.int32)))[0, -1]
        want = np.asarray(jax.nn.softmax(
            jnp.asarray(logits, jnp.float32) / 0.9))
        assert np.abs(freq - want).max() < 0.05, (freq, want)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_top_k1_equals_greedy_exactly(self):
        """top_k=1 collapses both filtered distributions to one-hots:
        the sampler must reproduce the target's greedy decode token for
        token, whatever the draft proposes."""
        target, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(1, 16, (2, 5)), jnp.int32)
        want = np.asarray(T.generate(target, self.CFG, prompt, steps=8))
        got = np.asarray(T.speculative_sample(
            target, self.CFG, draft, draft_cfg, prompt, steps=8,
            rng=jax.random.key(3), draft_k=3, top_k=1))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_perfect_draft_accepts_everything(self):
        """draft == target => p == q => acceptance probability 1 per
        token: steps tokens must take exactly ceil(steps/(k+1)) rounds
        per row."""
        target, _, _ = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(2).randint(1, 16, (2, 4)), jnp.int32)
        _, rounds = T.speculative_sample(
            target, self.CFG, target, self.CFG, prompt, steps=10,
            rng=jax.random.key(7), draft_k=4, temperature=0.8,
            return_stats=True)
        np.testing.assert_array_equal(np.asarray(rounds), [2, 2])

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_eos_stops_and_pads(self):
        target, draft, draft_cfg = self._models()
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(1, 16, (2, 4)), jnp.int32)
        steps = 12
        out, rounds = T.speculative_sample(
            target, self.CFG, draft, draft_cfg, prompt, steps=steps,
            rng=jax.random.key(5), draft_k=3, temperature=1.0,
            eos_id=3, pad_id=0, return_stats=True)
        out = np.asarray(out)
        assert out.shape == (2, 4 + steps)
        for r in range(2):
            gen = out[r, 4:]
            hits = np.flatnonzero(gen == 3)
            if hits.size:  # everything after the first eos is pad
                assert (gen[hits[0] + 1:] == 0).all(), gen

    def test_validates_temperature(self):
        target, draft, draft_cfg = self._models()
        with pytest.raises(ValueError, match="temperature"):
            T.speculative_sample(target, self.CFG, draft, draft_cfg,
                                 jnp.zeros((1, 4), jnp.int32), steps=2,
                                 rng=jax.random.key(0), temperature=0.0)


def assert_decode_matches_teacher_forcing(params, cfg, prompt, steps):
    """Cached token-by-token greedy decode must equal the teacher-forced
    argmax of one full forward — THE decode-correctness invariant, used
    by the GQA tests and the cross-feature matrix."""
    t0 = prompt.shape[1]
    out = np.asarray(T.generate(params, cfg, prompt, steps=steps))
    logits = np.asarray(T.apply(params, cfg, jnp.asarray(out)))
    for s in range(steps):
        col = t0 + s
        np.testing.assert_array_equal(
            out[:, col], logits[:, col - 1].argmax(-1),
            err_msg=f"step {s} of {cfg}")


class TestDecodeFeatureMatrix:
    """Cross-feature decode consistency sweep: every combination of
    GQA x MoE x rope-scaling must keep the cached token-by-token decode
    identical to the teacher-forced argmax of the full forward — the
    invariant that catches interactions between features that each pass
    alone."""

    @pytest.mark.parametrize("kv,moe,scaling", [
        (1, 0, "none"), (2, 4, "none"), (1, 4, "ntk"),
        (2, 0, "linear"), (1, 4, "linear"), (4, 4, "ntk"),
    ])
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_decode_matches_teacher_forcing(self, kv, moe, scaling):
        cfg = T.TransformerConfig(
            vocab=32, dim=16, n_layers=2, n_heads=4, n_kv_heads=kv,
            mlp_ratio=2, attn_impl="dense", moe_experts=moe,
            moe_capacity_factor=8.0,  # no drops: decode == forward
            rope_scaling=scaling, rope_factor=2.0)
        params = T.init_params(jax.random.key(kv + moe), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(moe).randint(1, 32, (2, 5)), jnp.int32)
        assert_decode_matches_teacher_forcing(params, cfg, prompt, 4)

    @pytest.mark.parametrize("kv,moe,window,int8", [
        (2, 0, 3, False), (1, 4, 4, False), (2, 0, None, True),
        (1, 0, 3, True), (4, 4, 4, True),
    ])
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_decode_matrix_window_int8(self, kv, moe, window, int8):
        """GQA x MoE x sliding-window x int8: window < t0+steps forces
        the r5 ROLLING ring cache, and int8 forces the in-loop dequant
        — the teacher-forced reference runs on the SAME dequantized
        values, so exact equality must survive both."""
        cfg = T.TransformerConfig(
            vocab=32, dim=16, n_layers=2, n_heads=4, n_kv_heads=kv,
            mlp_ratio=2, attn_impl="dense", moe_experts=moe,
            moe_capacity_factor=8.0, attn_window=window)
        params = T.init_params(jax.random.key(kv + moe + 17), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(kv + moe).randint(1, 32, (2, 6)),
            jnp.int32)
        if not int8:
            assert_decode_matches_teacher_forcing(params, cfg, prompt, 5)
            return
        from paddle_tpu.serve import quant

        qp = quant.quantize_params(params)
        out = np.asarray(T.generate(qp, cfg, prompt, steps=5))
        logits = np.asarray(T.apply(quant.dequantize_params(qp), cfg,
                                    jnp.asarray(out)))
        t0 = prompt.shape[1]
        for s in range(5):
            col = t0 + s
            np.testing.assert_array_equal(
                out[:, col], logits[:, col - 1].argmax(-1),
                err_msg=f"step {s} (kv={kv} moe={moe} window={window})")


class TestSlidingWindowAttention:
    def _cfg(self, window=None):
        return T.TransformerConfig(vocab=32, dim=16, n_layers=2,
                                   n_heads=2, mlp_ratio=2,
                                   attn_impl="dense",
                                   attn_window=window)

    def test_locality(self):
        """A token farther back than the total receptive field
        (window-1 per layer) must not influence the logits; a token
        inside one window must."""
        cfg = self._cfg(window=3)  # 2 layers -> receptive field 5
        params = T.init_params(jax.random.key(0), cfg)
        r = np.random.RandomState(0)
        a = r.randint(1, 32, (1, 12)).astype(np.int32)
        b = a.copy()
        b[0, 2] = (b[0, 2] + 7) % 32  # >receptive-field from pos 11
        la = np.asarray(T.apply(params, cfg, jnp.asarray(a)))
        lb = np.asarray(T.apply(params, cfg, jnp.asarray(b)))
        np.testing.assert_allclose(la[0, -1], lb[0, -1], rtol=1e-5,
                                   atol=1e-5)
        c = a.copy()
        c[0, 10] = (c[0, 10] + 7) % 32  # inside the last window
        lc = np.asarray(T.apply(params, cfg, jnp.asarray(c)))
        assert np.abs(la[0, -1] - lc[0, -1]).max() > 1e-4

    def test_huge_window_equals_full(self):
        params = T.init_params(jax.random.key(1), self._cfg())
        toks = jnp.asarray(
            np.random.RandomState(1).randint(1, 32, (2, 9)), jnp.int32)
        full = np.asarray(T.apply(params, self._cfg(), toks))
        win = np.asarray(T.apply(params, self._cfg(window=1000), toks))
        np.testing.assert_allclose(win, full, rtol=1e-6)

    @pytest.mark.slow
    def test_decode_matches_teacher_forcing(self):
        cfg = self._cfg(window=4)
        params = T.init_params(jax.random.key(2), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(2).randint(1, 32, (2, 6)), jnp.int32)
        assert_decode_matches_teacher_forcing(params, cfg, prompt, 5)

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_beam_and_spec_respect_window(self):
        cfg = self._cfg(window=4)
        params = T.init_params(jax.random.key(3), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(1, 32, (1, 6)), jnp.int32)
        greedy = np.asarray(T.generate(params, cfg, prompt, steps=5))
        seqs, _ = T.beam_decode(params, cfg, prompt, steps=5,
                                beam_size=1)
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]), greedy)
        dcfg = self._cfg(window=4)
        draft = T.init_params(jax.random.key(4), dcfg)
        spec = np.asarray(T.speculative_generate(
            params, cfg, draft, dcfg, prompt, steps=5, draft_k=3))
        np.testing.assert_array_equal(spec, greedy)

    def test_varlen_prompts_rejected(self):
        cfg = self._cfg(window=4)
        params = T.init_params(jax.random.key(5), cfg)
        with pytest.raises(ValueError, match="attn_window"):
            T.generate(params, cfg, jnp.zeros((2, 6), jnp.int32),
                       steps=3, prompt_lens=jnp.asarray([6, 4]))

    def test_context_parallel_rejected(self):
        """CP's ring attention has no band plumbing — silently training
        full attention would diverge from every windowed path."""
        from paddle_tpu.core import mesh as mesh_lib
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(data=2, model=1, seq=4),
            devices=jax.devices()[:8])
        with pytest.raises(ValueError, match="attn_window"):
            T.make_context_parallel_loss(self._cfg(window=4), mesh)


class TestRopeScaling:
    """Context extension without new parameters: linear position
    compression and NTK base rescaling."""

    def _cfg(self, **kw):
        return T.TransformerConfig(vocab=32, dim=16, n_layers=1,
                                   n_heads=2, mlp_ratio=2,
                                   attn_impl="dense", **kw)

    def test_factor_one_is_identity(self):
        params = T.init_params(jax.random.key(0), self._cfg())
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 32, (2, 8)), jnp.int32)
        base = np.asarray(T.apply(params, self._cfg(), toks))
        for mode in ("linear", "ntk"):
            same = np.asarray(T.apply(
                params, self._cfg(rope_scaling=mode, rope_factor=1.0),
                toks))
            np.testing.assert_allclose(same, base, rtol=1e-6)

    def test_linear_scaling_matches_compressed_positions(self):
        """factor-f linear scaling at positions p must equal the
        unscaled model at positions p/f (the definition)."""
        cfg = self._cfg()
        params = T.init_params(jax.random.key(1), cfg)
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 32, (2, 8)), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.float32) * 4.0,
                               (2, 8))
        want = np.asarray(T.apply(params, cfg, toks, positions=pos / 4.0))
        got = np.asarray(T.apply(
            params, self._cfg(rope_scaling="linear", rope_factor=4.0),
            toks, positions=pos))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_ntk_decodes_and_differs(self):
        cfg = self._cfg(rope_scaling="ntk", rope_factor=8.0)
        params = T.init_params(jax.random.key(2), cfg)
        toks = jnp.asarray(
            np.random.RandomState(2).randint(1, 32, (1, 6)), jnp.int32)
        out = T.generate(params, cfg, toks, steps=4)
        assert out.shape == (1, 10)
        plain = np.asarray(T.apply(params, self._cfg(), toks))
        scaled = np.asarray(T.apply(params, cfg, toks))
        assert not np.allclose(plain, scaled)

    def test_bad_mode_raises(self):
        cfg = self._cfg(rope_scaling="bogus", rope_factor=2.0)
        params = T.init_params(jax.random.key(3), cfg)
        with pytest.raises(ValueError, match="rope_scaling"):
            T.apply(params, cfg, jnp.zeros((1, 4), jnp.int32))


class TestScore:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_logprobs_and_masking(self):
        cfg = T.TransformerConfig(vocab=32, dim=16, n_layers=2, n_heads=2,
                                  mlp_ratio=2, attn_impl="dense")
        params = T.init_params(jax.random.key(0), cfg)
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 32, (3, 10)), jnp.int32)
        lens = jnp.asarray([10, 7, 4])
        lp, nll = T.score(params, cfg, toks, lens)
        assert lp.shape == (3, 9) and nll.shape == (3,)
        mask = np.arange(1, 10)[None, :] < np.asarray(lens)[:, None]
        assert (np.asarray(lp)[~mask] == 0).all()
        assert (np.asarray(lp)[mask] < 0).all()
        # an untrained model scores near uniform: NLL ~ log(32)
        assert abs(float(nll[0]) - np.log(32)) < 1.0
        # consistency with loss() (unmasked row)
        full_nll = float(T.loss(params, cfg, toks[:1]))
        np.testing.assert_allclose(float(nll[0]), full_nll, rtol=1e-5)


class TestFusedCE:
    """fused_ce_chunk folds the LM-head matmul into a checkpointed
    chunked scan (ops/losses.chunked_lm_head_nll): loss and grads must
    match the plain materialized-logits path exactly (same matmul, just
    chunked lhs), including ragged lengths, non-divisible chunk sizes,
    and the MoE aux term."""

    def _cfg(self, **kw):
        import dataclasses
        return dataclasses.replace(CFG, **kw)

    @pytest.mark.parametrize("chunk", [4, 7, 64])
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_loss_and_grads_match_plain(self, params, chunk):
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 61, (3, 13)), jnp.int32)
        fcfg = self._cfg(fused_ce_chunk=chunk)
        for lens in (None, jnp.asarray([13, 6, 1])):
            a = T.loss(params, CFG, toks, lens)
            b = T.loss(params, fcfg, toks, lens)
            np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
            ga = jax.grad(lambda p: T.loss(p, CFG, toks, lens))(params)
            gb = jax.grad(lambda p: T.loss(p, fcfg, toks, lens))(params)
            for la, lb in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
                np.testing.assert_allclose(la, lb, atol=5e-7)

    def test_with_moe_aux(self):
        import dataclasses
        cfg = dataclasses.replace(CFG, moe_experts=4, moe_every=2,
                                  n_layers=2)
        fcfg = dataclasses.replace(cfg, fused_ce_chunk=8)
        p = T.init_params(jax.random.key(2), cfg)
        toks = jnp.asarray(
            np.random.RandomState(2).randint(0, 61, (2, 9)), jnp.int32)
        np.testing.assert_allclose(float(T.loss(p, cfg, toks)),
                                   float(T.loss(p, fcfg, toks)),
                                   rtol=1e-6)

    def test_trains(self, params):
        from paddle_tpu import optim
        fcfg = self._cfg(fused_ce_chunk=16)
        opt = optim.adam(1e-2)
        state = opt.init(params)
        toks = jnp.asarray(
            np.random.RandomState(3).randint(0, 61, (4, 12)), jnp.int32)

        @jax.jit
        def step(p, s):
            l, g = jax.value_and_grad(
                lambda p: T.loss(p, fcfg, toks))(p)
            p, s = opt.update(g, s, p, jnp.zeros((), jnp.int32))
            return p, s, l

        p = params
        p, state, l0 = step(p, state)
        for _ in range(30):
            p, state, l = step(p, state)
        assert float(l) < float(l0) - 0.5


class TestFusedCEComposition:
    """fused_ce_chunk must compose with the other loss-path features:
    score() (gold logp = -nll) and context parallelism (loss_fn
    delegates to loss(), so the chunked scan runs over the
    sequence-sharded hidden)."""

    @pytest.mark.slow

    def test_score_matches_plain(self, params):
        import dataclasses
        fcfg = dataclasses.replace(CFG, fused_ce_chunk=8)
        toks = jnp.asarray(
            np.random.RandomState(5).randint(0, 61, (3, 14)), jnp.int32)
        lens = jnp.asarray([14, 9, 4])
        ga, na = T.score(params, CFG, toks, lens)
        gb, nb = T.score(params, fcfg, toks, lens)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   atol=5e-6)
        np.testing.assert_allclose(np.asarray(na), np.asarray(nb),
                                   atol=5e-6)

    @pytest.mark.slow

    def test_cp_fused_matches_dense_plain(self):
        import dataclasses

        from paddle_tpu.core import mesh as mesh_lib

        cfg = T.TransformerConfig(vocab=64, dim=16, n_layers=2,
                                  n_heads=2, mlp_ratio=2,
                                  attn_impl="dense")
        fcfg = dataclasses.replace(cfg, fused_ce_chunk=8)
        params = T.init_params(jax.random.key(0), cfg)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshConfig(data=2, model=1, seq=4),
            devices=jax.devices()[:8])
        toks_h = np.random.RandomState(0).randint(0, 64, (4, 17)) \
            .astype(np.int32)
        toks = jax.device_put(
            toks_h, jax.NamedSharding(mesh, jax.sharding.PartitionSpec(
                mesh_lib.DATA_AXIS, None)))
        cp_loss = T.make_context_parallel_loss(
            fcfg, mesh, batch_axis=mesh_lib.DATA_AXIS)
        dense = float(T.loss(params, cfg, jnp.asarray(toks_h)))
        cp = float(jax.jit(cp_loss)(params, toks))
        assert abs(dense - cp) < 1e-4, (dense, cp)


class TestInt8KVCache:
    """kv_cache_dtype="int8": the decode cache stores s8 + per-(pos,
    kv-head) scales, quantized at write, dequantized inside the
    attention reads. Lossy by design — the tests assert near-exact
    token agreement at small configs plus composition with the other
    decode features; the loop-state evidence lives in
    test_compiled_cost.py::TestInt8KVCacheState."""

    def _gen(self, cfg, params, prompt, steps=16, **kw):
        import dataclasses
        q = dataclasses.replace(cfg, kv_cache_dtype="int8")
        a = T.generate(params, cfg, prompt, steps=steps, **kw)
        b = T.generate(params, q, prompt, steps=steps, **kw)
        return a, b

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_tokens_agree_with_fp_cache(self, params):
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 61, (3, 9)), jnp.int32)
        a, b = self._gen(CFG, params, prompt)
        assert a.shape == b.shape
        agree = float(jnp.mean((a == b).astype(jnp.float32)))
        assert agree >= 0.95, agree

    @pytest.mark.slow

    def test_composes_with_gqa_and_window(self):
        import dataclasses
        cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2,
                                  n_heads=4, n_kv_heads=2,
                                  attn_window=6, attn_impl="dense")
        p = T.init_params(jax.random.key(3), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(0, 61, (2, 5)), jnp.int32)
        a, b = self._gen(cfg, p, prompt, steps=12)  # rolling ring cache
        agree = float(jnp.mean((a == b).astype(jnp.float32)))
        assert agree >= 0.9, agree

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_composes_with_varlen_prompts_and_int8_weights(self, params):
        from paddle_tpu.serve import quant
        qp = quant.quantize_params(params)
        prompt = jnp.asarray(
            np.random.RandomState(4).randint(0, 61, (3, 8)), jnp.int32)
        lens = jnp.asarray([8, 5, 2])
        a, b = self._gen(CFG, qp, prompt, steps=10, prompt_lens=lens)
        agree = float(jnp.mean((a == b).astype(jnp.float32)))
        assert agree >= 0.9, agree

    def test_sample_path_runs(self, params):
        import dataclasses
        q = dataclasses.replace(CFG, kv_cache_dtype="int8")
        prompt = jnp.asarray(
            np.random.RandomState(5).randint(0, 61, (2, 6)), jnp.int32)
        out = T.sample(params, q, prompt, steps=8,
                       rng=jax.random.key(1), temperature=0.8)
        assert out.shape == (2, 14)

    def test_beam_and_spec_raise(self, params):
        import dataclasses
        q = dataclasses.replace(CFG, kv_cache_dtype="int8")
        prompt = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(ValueError, match="generate"):
            T.beam_decode(params, q, prompt, steps=2)
        with pytest.raises(ValueError, match="generate"):
            T.speculative_generate(params, q, params, q, prompt, steps=2)
        with pytest.raises(ValueError, match="compute|int8"):
            T.generate(params,
                       dataclasses.replace(CFG, kv_cache_dtype="fp4"),
                       prompt, steps=2)


# ---- `_attention` hands both implementations the policy's compute dtype

@pytest.fixture(params=["float32", "bfloat16"])
def policy(request):
    """Each compute policy in turn, the default restored afterwards."""
    old = dtypes.default_policy()
    if request.param == "bfloat16":
        dtypes.set_default_policy(dtypes.bf16_compute_policy())
    yield request.param
    dtypes.set_default_policy(old)


def _gqa_qkv(seed=0, b=2, t=48, h=4, hkv=2, d=16):
    """float32 q, k, v with compact K/V heads: what `_block_parts`
    hands `_attention` under either policy (a float32 bias promotes the
    biased qkv projection)."""
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(b, t, h, d), jnp.float32),
            jnp.asarray(rs.randn(b, t, hkv, d), jnp.float32),
            jnp.asarray(rs.randn(b, t, hkv, d), jnp.float32))


def _attn_cfg(impl, window=None):
    return T.TransformerConfig(vocab=61, dim=64, n_layers=1, n_heads=4,
                               n_kv_heads=2, attn_impl=impl,
                               attn_window=window)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_attention_output_is_in_the_compute_dtype(policy, impl):
    q, k, v = _gqa_qkv()
    out = T._attention(_attn_cfg(impl), q, k, v, causal=True)
    assert out.dtype == jnp.dtype(policy)
    assert out.shape == q.shape


@pytest.mark.parametrize("window", [None, 16])
def test_attention_impls_agree_under_each_policy(policy, window):
    q, k, v = _gqa_qkv(1)
    dense = T._attention(_attn_cfg("dense", window), q, k, v, causal=True)
    flash = T._attention(_attn_cfg("flash", window), q, k, v, causal=True)
    tol = 3e-2 if policy == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=tol, atol=tol)
    if policy == "bfloat16":
        # and both stay within bf16 round-off of the float32 result
        dtypes.set_default_policy(dtypes.Policy())
        exact = T._attention(_attn_cfg("dense", window), q, k, v,
                             causal=True)
        np.testing.assert_allclose(np.asarray(flash, np.float32),
                                   np.asarray(exact), rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_attention_cotangents_keep_the_inputs_dtype(policy, impl):
    """The cast's transpose returns float32 cotangents to the float32
    projection whatever the policy; under bf16 they stay within its
    round-off of the float32 policy's."""
    q, k, v = _gqa_qkv(2)

    def loss(q, k, v):
        out = T._attention(_attn_cfg(impl), q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, x in zip(grads, (q, k, v)):
        assert g.dtype == jnp.float32 and g.shape == x.shape
    if policy == "bfloat16":
        dtypes.set_default_policy(dtypes.Policy())
        exact = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g, e in zip(grads, exact):
            assert float(jnp.max(jnp.abs(g - e))) <= 3e-2 * float(
                jnp.max(jnp.abs(e)))


@pytest.mark.parametrize("impl,window", [("dense", None), ("dense", 16),
                                         ("flash", None), ("flash", 16)])
def test_attention_is_bit_equal_under_the_default_policy(impl, window):
    """float32 policy: the cast is the identity, so `_attention` is the
    implementation called directly on the expanded heads."""
    q, k, v = _gqa_qkv(3)
    out = T._attention(_attn_cfg(impl, window), q, k, v, causal=True)
    ke, ve = T._expand_kv(q, k, v)
    if impl == "dense":
        direct = T._dense_attention(q, ke, ve, True, None, window)
    else:
        direct = flash_attention(q, ke, ve, causal=True, window=window)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(direct))


def test_attention_notes_its_operands_dtype(policy, params):
    """The counter a chip run prints: which dtype the attention's
    matmuls were traced with, once per layer."""
    key = f"transformer.attention.operands={policy}"
    toks = jnp.zeros((2, 9), jnp.int32)
    before = pallas_util.traced()
    jax.make_jaxpr(lambda p, t: T.loss(p, CFG, t))(params, toks)
    after = pallas_util.traced()
    grew = {k for k in after if after[k] != before.get(k, 0)}
    assert grew == {key, "transformer.attention=dense"}
    assert after[key] - before.get(key, 0) == CFG.n_layers
