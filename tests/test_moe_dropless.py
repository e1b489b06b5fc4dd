"""The dropless expert layer (`parallel.moe.dropless_ffn`) against
"every expert on every position, weighted by that position's w_e or
zero": values and gradients, the imbalanced corners, the counts, and
the share test (the eight shares of 16 experts add up to the uncut
128-expert layer). float32, grouped kernels in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_grouped_matmul as G
from paddle_tpu.parallel import moe


def every_expert(params, x, k, first_held=0, score="softmax",
                 route_scale=1.0):
    """No sort, no grouping: each held expert applied to all of x. A
    sigmoid router chooses by score + `expert_bias` where the params have
    one, and weights by the unbiased scores."""
    logits = x @ params["router"]["kernel"]
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.sigmoid(logits)
    top_e = jax.lax.top_k(probs + params.get("expert_bias", 0.0), k)[1]
    top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    w = route_scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(params["w_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(top_e == first_held + e, w, 0.0), axis=-1)
        h = jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
        y = y + w_e[:, None] * (h @ params["w_down"][e])
    return y


def rows_of_held(params, x, k, first_held=0):
    """[n_held] (position, choice) rows of each held expert."""
    _, top_e = jax.lax.top_k(x @ params["router"]["kernel"], k)
    n = params["w_gate"].shape[0]
    return np.bincount(np.asarray(top_e).ravel() - first_held + n * 1000,
                       minlength=n * 1001)[n * 1000:n * 1001]


def _layer(seed, n_experts, n_held, d=32, f=16, t=32):
    params = moe.init_dropless_params(jax.random.key(seed), n_experts,
                                      n_held, d, f)
    # a router with some spread, so the top-k sets are not near ties
    params["router"]["kernel"] = 3.0 * params["router"]["kernel"]
    x = jax.random.normal(jax.random.key(seed + 1), (t, d), jnp.float32)
    return params, x


# the sigmoid router of Trinity: its route_scale, and a bias that
# chooses (the gradient of the layer does not reach it)
SIGMOID = dict(score="sigmoid", route_scale=2.826)


@pytest.mark.parametrize("n_experts,n_held,first,k,router", [
    (8, 8, 0, 2, {}),       # top-2 of 8, all held
    (128, 16, 0, 8, {}),    # the cell's share: top-8 of 128, 16 held
    (128, 16, 48, 8, {}),   # another chip's share
    (64, 16, 0, 8, {}),     # top-8 of 64, 16 held: a 4-way share
    (64, 16, 32, 8, {}),    # the third chip of the four
    (128, 16, 0, 8, SIGMOID),   # Trinity's share: sigmoid, biased choice
    (128, 16, 112, 8, SIGMOID),  # the last chip of its eight
], ids=["8-8-0-2", "128-16-0-8", "128-16-48-8", "64-16-0-8", "64-16-32-8",
        "128-16-0-8-sigmoid_bias", "128-16-112-8-sigmoid_bias"])
def test_values_and_gradients(n_experts, n_held, first, k, router):
    params, x = _layer(0, n_experts, n_held)
    if router:
        params["expert_bias"] = 0.05 * jax.random.normal(
            jax.random.key(8), (n_experts,))
    out = moe.dropless_ffn(params, x, k=k, first_held=first, **router)
    np.testing.assert_allclose(np.asarray(out.y), np.asarray(
        every_expert(params, x, k, first, **router)), rtol=1e-4, atol=1e-5)
    if not router:
        rows = rows_of_held(params, x, k, first)
        assert int(out.stats.rows_held) == rows.sum()   # no row dropped
        assert int(out.stats.rows_max_expert) == rows.max()
        assert out.stats.route_counts is None
    else:
        counts = out.stats.route_counts
        assert counts.shape == (n_experts,) and int(counts.sum()) == (
            k * x.shape[0])
        assert int(out.stats.rows_held) == int(
            counts[first:first + n_held].sum())

    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    got = jax.grad(lambda p, x: jnp.sum(moe.dropless_ffn(
        p, x, k=k, first_held=first, **router).y * w), argnums=(0, 1))(
            params, x)
    want = jax.grad(lambda p, x: jnp.sum(
        every_expert(p, x, k, first, **router) * w), argnums=(0, 1))(
            params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def _biased(params, experts, by=50.0):
    kernel = params["router"]["kernel"]
    # every position's logit for `experts` far above the rest
    bias = jnp.zeros((kernel.shape[1],)).at[jnp.asarray(experts)].set(by)
    return {**params, "router": {"kernel": jnp.concatenate(
        [kernel, bias[None]], axis=0)}}


def test_every_position_on_one_held_expert():
    """All T rows land on expert 3: nothing is dropped, whatever a
    capacity factor would have allowed."""
    params, x = _layer(1, 8, 8)
    x1 = jnp.concatenate([x, jnp.ones((x.shape[0], 1))], axis=1)
    pad = lambda w, axis: jnp.concatenate(
        [w, jnp.zeros_like(jnp.take(w, jnp.arange(1, dtype=jnp.int32), axis=axis))], axis=axis)
    p = _biased({**params, "w_gate": pad(params["w_gate"], 1),
                 "w_up": pad(params["w_up"], 1),
                 "w_down": pad(params["w_down"], 2)}, [3])
    out = moe.dropless_ffn(p, x1, k=1)
    assert int(out.stats.rows_held) == x.shape[0]
    assert int(out.stats.rows_max_expert) == x.shape[0]
    np.testing.assert_allclose(np.asarray(out.y), np.asarray(
        every_expert(p, x1, 1)), rtol=1e-4, atol=1e-5)
    assert float(jnp.min(jnp.sum(jnp.abs(out.y), axis=1))) > 0.0


def test_no_position_on_a_held_expert():
    """The chosen experts are all another chip's: zero output, finite
    (zero) gradients, though the kernels wrote no row at all."""
    params, x = _layer(2, 128, 16)
    x1 = jnp.concatenate([x, jnp.ones((x.shape[0], 1))], axis=1)
    pad = lambda w, axis: jnp.concatenate(
        [w, jnp.ones_like(jnp.take(w, jnp.arange(1, dtype=jnp.int32), axis=axis))], axis=axis)
    p = _biased({**params, "w_gate": pad(params["w_gate"], 1),
                 "w_up": pad(params["w_up"], 1),
                 "w_down": pad(params["w_down"], 2)}, list(range(40, 48)))
    out = moe.dropless_ffn(p, x1, k=8, first_held=16)
    assert int(out.stats.rows_held) == 0
    np.testing.assert_array_equal(np.asarray(out.y), 0.0)
    grads = jax.grad(lambda p, x: jnp.sum(moe.dropless_ffn(
        p, x, k=8, first_held=16).y ** 2), argnums=(0, 1))(p, x1)
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_masked_positions_route_nowhere():
    params, x = _layer(3, 8, 8)
    mask = jnp.arange(x.shape[0], dtype=jnp.int32) % 3 != 0
    out = moe.dropless_ffn(params, x, k=2, token_mask=mask)
    want = jnp.where(mask[:, None], every_expert(params, x, 2), 0.0)
    np.testing.assert_allclose(np.asarray(out.y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(out.stats.rows_held) == 2 * int(mask.sum())


@pytest.mark.parametrize("n_experts,chips,router", [
    (128, 8, {}), (64, 4, {}), (128, 8, SIGMOID)],
    ids=["128-8", "64-4", "128-8-sigmoid_bias"])
def test_the_shares_add_up_to_the_whole_layer(n_experts, chips, router):
    """The share test: a chip of an 8-way (4-way) expert-parallel layer
    routes over all 128 (64) experts and computes its 16; the partial
    results of all the shares add up to the uncut layer. With an expert
    bias every chip holds the same bias and chooses alike."""
    whole, x = _layer(4, n_experts, n_experts)
    if router:
        whole["expert_bias"] = 0.05 * jax.random.normal(
            jax.random.key(8), (n_experts,))
    uncut = every_expert(whole, x, 8, **router)
    np.testing.assert_allclose(
        np.asarray(moe.dropless_ffn(whole, x, k=8, **router).y),
        np.asarray(uncut), rtol=1e-4, atol=1e-5)
    total, rows = jnp.zeros_like(x), 0
    for chip in range(chips):
        share = {name: whole[name] for name in ("router", "expert_bias")
                 if name in whole}
        share.update({name: whole[name][16 * chip:16 * chip + 16]
                      for name in ("w_gate", "w_up", "w_down")})
        out = moe.dropless_ffn(share, x, k=8, first_held=16 * chip, **router)
        total, rows = total + out.y, rows + int(out.stats.rows_held)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    assert rows == 8 * x.shape[0]       # every choice computed once


@pytest.mark.parametrize("sizes,tm", [
    ([10, 0, 23, 5], 8), ([0, 0, 0, 0], 8), ([16, 16, 16, 16], 16),
    ([1, 62, 0, 1], 16), ([0, 3, 0, 0], 32),
])
@pytest.mark.parametrize("k,tiles", [
    (16, (8, 8)),
    (384, (256, 8)),    # K no multiple of its tile: three lane tiles of 128
])
def test_grouped_kernels_against_plain_products(np_rng, sizes, tm, k, tiles):
    m, n, g = 64, 24, len(sizes)
    lhs = jnp.asarray(np_rng.randn(m, k), jnp.float32)
    rhs = jnp.asarray(np_rng.randn(g, k, n), jnp.float32)
    dout = jnp.asarray(np_rng.randn(m, n), jnp.float32)
    tiling = (tm,) + tiles
    out = G.moe_grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                               tiling=tiling)
    back = G.moe_grouped_matmul(dout, rhs, jnp.asarray(sizes, jnp.int32),
                                tiling=tiling, transpose_rhs=True)
    dw = G.moe_grouped_matmul_dw(lhs, dout, jnp.asarray(sizes, jnp.int32),
                                 tiling=tiling)
    off = np.concatenate([[0], np.cumsum(sizes)])
    # float32 sums in another order: the rounding grows with the root
    # of the terms summed (1e-5 at the 16 of the first case)
    close = dict(rtol=1e-5, atol=1e-5 * (k / 16) ** 0.5)
    for i in range(g):
        rows = slice(off[i], off[i + 1])
        np.testing.assert_allclose(out[rows], lhs[rows] @ rhs[i], **close)
        np.testing.assert_allclose(back[rows], dout[rows] @ rhs[i].T,
                                   **close)
        np.testing.assert_allclose(dw[i], lhs[rows].T @ dout[rows], **close)


@pytest.mark.parametrize("size,tile,want", [
    (768, 1024, (768, 1)),      # no larger than its tile: itself
    (2048, 1024, (1024, 2)),    # a multiple of its tile
    (2304, 2048, (1152, 2)),    # neither: the largest lane multiple under it
    (2304, 1024, (768, 3)),
    (896, 1024, (896, 1)),
    (48, 2048, (48, 1)),        # the tiny sizes of the tests
])
def test_tiles_divide_the_size(size, tile, want):
    assert G._tiles(size, tile, "K") == want


def test_a_size_no_lane_tile_divides_is_refused():
    with pytest.raises(ValueError, match="multiple of neither"):
        G._tiles(2000, 1024, "N")


def test_counts_reach_the_timeline():
    from paddle_tpu.obs.trace import Timeline

    tl = Timeline()
    stats = moe.DroplessStats(jnp.asarray([100, 120], jnp.int32),
                              jnp.asarray([9, 11], jnp.int32))
    moe.count_dropless_stats(stats, positions=64, timeline=tl)
    moe.count_dropless_stats(stats, positions=64, timeline=tl)
    assert tl.counters() == {"moe.rows_held": 440, "moe.rows_max_expert": 40,
                             "moe.positions": 256}
    # layers that count their routes over all experts add two counters
    routed = stats._replace(route_counts=jnp.asarray(
        [[30, 50, 48], [40, 40, 48]], jnp.int32))
    tl = Timeline()
    moe.count_dropless_stats(routed, positions=64, timeline=tl)
    assert tl.counters()["moe.route_rows"] == 256
    assert tl.counters()["moe.route_rows_max"] == 50 + 48


def test_the_bias_chooses_and_the_scores_weight():
    """A sigmoid router under a bias: an expert the bias lifts into the
    top k is chosen over a higher score, and the chosen are weighted by
    their unbiased scores, renormalised and times route_scale."""
    x = jnp.eye(4, dtype=jnp.float32)               # position t reads row t
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] * 4)
    params = {"router": {"kernel": logits},
              "w_gate": jnp.ones((4, 4, 2)), "w_up": jnp.ones((4, 4, 2)),
              "w_down": jnp.ones((4, 2, 4)),
              "expert_bias": jnp.asarray([0.0, 0.0, 0.5, 0.0])}
    out = moe.dropless_ffn(params, x, k=2, score="sigmoid", route_scale=3.0)
    np.testing.assert_array_equal(out.stats.route_counts, [4, 0, 4, 0])
    s = jax.nn.sigmoid(jnp.asarray([2.0, 0.0]))
    w = 3.0 * s / jnp.sum(s)
    # every expert computes silu(1) * 1 * 2 on each lane of a one-hot row
    unit = 2.0 * float(jax.nn.silu(1.0))
    np.testing.assert_allclose(out.y, jnp.full((4, 4), unit) * jnp.sum(w),
                               rtol=1e-6)
    # without the bias the top 2 are experts 0 and 1
    plain = moe.dropless_ffn({k: v for k, v in params.items()
                              if k != "expert_bias"}, x, k=2,
                             score="sigmoid")
    assert plain.stats.route_counts is None
    np.testing.assert_array_equal(out.stats.rows_held, 8)


# -- the row kernels (`ops.moe_rows`) against the jnp gathers they replace --


def _oracle_take(x, row_of_slot):
    return jnp.take(x, row_of_slot, axis=0)


def _oracle_sum(src, slot_of_pair, held, weight=None):
    picked = jnp.take(src, slot_of_pair, axis=0).astype(jnp.float32)
    if weight is not None:
        picked = weight[..., None] * picked
    return jnp.sum(jnp.where(held[..., None], picked, 0.0), axis=1)


def _routing(np_rng, t, k, share):
    """held [T, k] with about `share` of the choices held, and the
    layer's slot order: held slots first."""
    held = jnp.asarray(np_rng.rand(t, k) < share)
    key = jnp.where(held, 0, 1).reshape(-1)
    pair_of_slot = jnp.argsort(key, stable=True).astype(jnp.int32)
    slot_of_pair = jnp.zeros((t * k,), jnp.int32).at[pair_of_slot].set(
        jnp.arange(t * k, dtype=jnp.int32)).reshape(t, k)
    return held, pair_of_slot, slot_of_pair


ROW_CASES = [
    # (positions, k, width, share held, row tile, position tile)
    (64, 8, 256, 0.2, 64, 16),      # several tiles of each; a partial last
    (48, 2, 33, 1.0, 16, 16),       # every choice held; an odd width
    (16, 4, 64, 0.0, 8, 8),         # no held row: no tile visited
    (40, 8, 128, 0.35, 24, 8),      # rows held no multiple of the tile
]


@pytest.mark.parametrize("t,k,d,share,tm,tt", ROW_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_take_held_rows_against_the_gather(np_rng, t, k, d, share, tm, tt,
                                           dtype):
    from paddle_tpu.ops import moe_rows as M

    held, pair_of_slot, _ = _routing(np_rng, t, k, share)
    n = int(held.sum())
    x = jnp.asarray(np_rng.randn(t, d), dtype)
    got = M.moe_take_held_rows(x, pair_of_slot // k, jnp.int32(n), tile=tm)
    want = _oracle_take(x, pair_of_slot // k)
    np.testing.assert_array_equal(np.asarray(got[:n], np.float32),
                                  np.asarray(want[:n], np.float32))
    # scaled, with the dot: the combine's backward (g float32)
    g = jnp.asarray(np_rng.randn(t, d), jnp.float32)
    scale = jnp.asarray(np_rng.randn(t * k), jnp.float32)
    other = jnp.asarray(np_rng.randn(t * k, d), dtype)
    dst, dot = M.moe_take_held_rows(g, pair_of_slot // k, jnp.int32(n),
                                    scale=scale, other=other,
                                    out_dtype=dtype, tile=tm)
    rows = _oracle_take(g, pair_of_slot // k)
    np.testing.assert_array_equal(
        np.asarray(dst[:n], np.float32),
        np.asarray((scale[:, None] * rows).astype(dtype)[:n], np.float32))
    np.testing.assert_allclose(
        np.asarray(dot[:n]),
        np.asarray(jnp.sum(other.astype(jnp.float32) * rows, axis=1)[:n]),
        rtol=1e-6, atol=1e-6 * d ** 0.5)


@pytest.mark.parametrize("t,k,d,share,tm,tt", ROW_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weighted", [True, False])
def test_sum_held_rows_against_the_gather(np_rng, t, k, d, share, tm, tt,
                                          dtype, weighted):
    from paddle_tpu.ops import moe_rows as M

    held, _, slot_of_pair = _routing(np_rng, t, k, share)
    src = jnp.asarray(np_rng.randn(t * k, d), dtype)
    weight = (jnp.asarray(np_rng.rand(t, k), jnp.float32) if weighted
              else None)
    got = M.moe_sum_held_rows(src, slot_of_pair, held, weight, tile=tt,
                              row_tile=tm)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_oracle_sum(src, slot_of_pair, held,
                                                weight)),
        rtol=1e-6, atol=1e-6)


@jax.custom_vjp
def _jnp_take_rows(x, row_of_slot, slot_of_pair, held):
    return jnp.take(x, row_of_slot, axis=0)


def _jnp_take_rows_fwd(x, row_of_slot, slot_of_pair, held):
    return jnp.take(x, row_of_slot, axis=0), (slot_of_pair, held)


def _jnp_take_rows_bwd(res, g):
    slot_of_pair, held = res
    return _oracle_sum(g, slot_of_pair, held).astype(g.dtype), None, None, None


_jnp_take_rows.defvjp(_jnp_take_rows_fwd, _jnp_take_rows_bwd)


@jax.custom_vjp
def _jnp_combine_rows(out, weight, slot_of_pair, held, pair_of_slot):
    return _oracle_sum(out, slot_of_pair, held, weight)


def _jnp_combine_rows_fwd(out, weight, slot_of_pair, held, pair_of_slot):
    return (_jnp_combine_rows(out, weight, slot_of_pair, held, pair_of_slot),
            (out, weight, slot_of_pair, held, pair_of_slot))


def _jnp_combine_rows_bwd(res, g):
    out, weight, slot_of_pair, held, pair_of_slot = res
    k = weight.shape[1]
    picked = jnp.take(out, slot_of_pair, axis=0).astype(jnp.float32)
    d_weight = jnp.where(held, jnp.sum(picked * g[:, None, :], axis=-1), 0.0)
    w_slot = jnp.where(held, weight, 0.0).reshape(-1)[pair_of_slot]
    d_out = w_slot[:, None] * jnp.take(g, pair_of_slot // k, axis=0)
    return d_out.astype(out.dtype), d_weight, None, None, None


_jnp_combine_rows.defvjp(_jnp_combine_rows_fwd, _jnp_combine_rows_bwd)


def _layer_both_ways(monkeypatch, params, x, w, **kw):
    """(y, gradients) of `dropless_ffn` with the row kernels, then with
    the jnp gathers they replace."""
    def loss(p, x):
        y = moe.dropless_ffn(p, x, **kw).y
        return jnp.sum(y.astype(jnp.float32) * w), y

    def run():
        g, y = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
            params, x)
        return y, g

    kernels = run()
    monkeypatch.setattr(moe, "_take_rows", _jnp_take_rows)
    monkeypatch.setattr(moe, "_combine_rows", _jnp_combine_rows)
    return kernels, run()


@pytest.mark.parametrize("n_experts,n_held,first,k", [
    (8, 8, 0, 2), (128, 16, 0, 8), (128, 16, 48, 8), (64, 16, 0, 8),
    (64, 16, 32, 8),
])
def test_layer_with_the_kernels_matches_the_gathers(monkeypatch, n_experts,
                                                    n_held, first, k):
    """float32 through the whole layer, values and every gradient: the
    kernels move the rows bit for bit and sum in float32, so only the
    order of a position's few terms differs."""
    params, x = _layer(0, n_experts, n_held)
    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    (y, g), (y0, g0) = _layer_both_ways(monkeypatch, params, x, w, k=k,
                                        first_held=first)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-6,
                               atol=1e-7)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("case", ["token_mask", "none_held", "all_held",
                                  "bf16"])
def test_layer_corners_match_the_gathers(monkeypatch, case):
    from paddle_tpu.core import dtypes

    params, x = _layer(5, 8 if case == "all_held" else 128,
                       8 if case == "all_held" else 16)
    kw = dict(k=2 if case == "all_held" else 8)
    if case == "token_mask":
        kw["token_mask"] = jnp.arange(x.shape[0], dtype=jnp.int32) % 3 != 0
    if case == "none_held":     # every position's top 8 is experts 40-47
        x = jnp.concatenate([x, jnp.ones((x.shape[0], 1))], axis=1)
        pad = lambda a, axis: jnp.concatenate([a, jnp.ones_like(
            jnp.take(a, jnp.arange(1, dtype=jnp.int32), axis=axis))], axis=axis)
        params = _biased({**params, "w_gate": pad(params["w_gate"], 1),
                          "w_up": pad(params["w_up"], 1),
                          "w_down": pad(params["w_down"], 2)},
                         list(range(40, 48)))
        kw["first_held"] = 16
    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    prev = dtypes.default_policy()
    if case == "bf16":
        dtypes.set_default_policy(dtypes.bf16_compute_policy())
    try:
        (y, g), (y0, g0) = _layer_both_ways(monkeypatch, params, x, w, **kw)
        held = moe.dropless_ffn(params, x, **kw).stats.rows_held
    finally:
        dtypes.set_default_policy(prev)
    if case == "none_held":
        assert int(held) == 0
    if case == "all_held":
        assert int(held) == 2 * x.shape[0]
    close = dict(rtol=1e-6, atol=1e-7) if case != "bf16" else dict(
        rtol=0.01, atol=1e-3)   # a bf16 rounding of a float32 sum
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y0, np.float32), **close)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **close,
                                   err_msg=jax.tree_util.keystr(path))
