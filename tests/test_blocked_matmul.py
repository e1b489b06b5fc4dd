"""Sharded matmul primitives vs the jnp oracle, across shard counts.

Every `parallel.blocked_matmul` form — output-dim ring, contracting-dim
reduce ring, weight-streaming blocked matmul, and the row-parallel
`tp_dense` consumer seam — must match `matmul_reference` on the
virtual CPU mesh in BOTH its overlap and naive arms, for even AND odd
ring sizes (the bidirectional gather ring takes a different final hop
on even rings; an off-by-one in the block bookkeeping passes one
parity and fails the other). Tolerances are allclose, not bit-equal:
the ring adds partial products in ring order while the oracle reduces
one big contraction, and fp reassociation differs — `atol`/`rtol`
2e-6 on f32 is ulp-scale for these magnitudes, anything real fails it.

The pipeline tests pin the consumer contract: `tp_axis` routes every
stage matmul through `tp_dense` over a second mesh axis and must
reproduce the plain pipeline's outputs (and gradients — ppermute's
transpose runs backward through the ring).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddle_tpu.parallel import blocked_matmul as BM
from paddle_tpu.parallel import pipeline as PP

pytestmark = pytest.mark.kernels

TOL = dict(rtol=2e-6, atol=2e-6)


def _mesh(p):
    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    return Mesh(np.array(jax.devices()[:p]), ("x",))


def _xw(np_rng, m, k, n, dtype=np.float32):
    return (jnp.asarray(np_rng.standard_normal((m, k)).astype(dtype)),
            jnp.asarray(np_rng.standard_normal((k, n)).astype(dtype)))


class TestCollectiveMatmul:
    @pytest.mark.parametrize("p", [2, 4])
    @pytest.mark.parametrize("mode", ["gather", "reduce"])
    @pytest.mark.parametrize("overlap", [True, False])
    def test_matches_oracle(self, np_rng, p, mode, overlap):
        mesh = _mesh(p)
        x, w = _xw(np_rng, 4 * p, 6 * p, 5 * p)
        ref = BM.matmul_reference(x, w)
        fn = jax.jit(BM.collective_matmul(mesh, axis="x", mode=mode,
                                          overlap=overlap))
        got = fn(x, w)
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **TOL)

    @pytest.mark.slow
    @pytest.mark.parametrize("p", [3, 8])
    def test_odd_and_full_rings(self, np_rng, p):
        # odd ring: the bidirectional gather has NO antipodal extra
        # hop; p=8: the full mesh, deepest reduce chain
        mesh = _mesh(p)
        x, w = _xw(np_rng, 3 * p, 4 * p, 2 * p)
        ref = BM.matmul_reference(x, w)
        fns = {  # explicit literal: one jit wrapper per arm (GL004)
            "gather": jax.jit(BM.collective_matmul(
                mesh, axis="x", mode="gather", overlap=True)),
            "reduce": jax.jit(BM.collective_matmul(
                mesh, axis="x", mode="reduce", overlap=True)),
        }
        for mode, fn in fns.items():
            np.testing.assert_allclose(np.asarray(fn(x, w)),
                                       np.asarray(ref), **TOL)

    def test_reduce_rejects_untileable_rows(self, np_rng):
        mesh = _mesh(2)
        x, w = _xw(np_rng, 5, 8, 4)  # M=5 not divisible by p=2
        with pytest.raises(ValueError, match="M % p"):
            jax.jit(BM.collective_matmul(mesh, axis="x",
                                         mode="reduce"))(x, w)

    def test_bf16_accumulates_in_f32(self, np_rng):
        # the >=f32 accumulation contract: bf16 operands, bf16 result,
        # but partial products summed wide — matches the oracle, which
        # does the same (a bf16-accumulated ring would drift visibly)
        mesh = _mesh(4)
        x, w = _xw(np_rng, 8, 32, 8)
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        ref = BM.matmul_reference(x, w)
        assert ref.dtype == jnp.bfloat16
        fns = {  # explicit literal: one jit wrapper per arm (GL004)
            "gather": jax.jit(BM.collective_matmul(
                mesh, axis="x", mode="gather", overlap=True)),
            "reduce": jax.jit(BM.collective_matmul(
                mesh, axis="x", mode="reduce", overlap=True)),
        }
        for mode, fn in fns.items():
            got = fn(x, w)
            assert got.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(ref, np.float32),
                rtol=2e-2, atol=2e-2)


class TestStreamMatmul:
    @pytest.mark.parametrize("p", [2, 4])
    def test_matches_oracle(self, np_rng, p):
        mesh = _mesh(p)
        x, w = _xw(np_rng, 6, 4 * p, 3 * p)
        ref = BM.matmul_reference(x, w)
        got = jax.jit(BM.blocked_matmul(mesh, axis="x"))(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **TOL)


class TestTpDense:
    @pytest.mark.parametrize("overlap", [True, False])
    def test_matches_oracle(self, np_rng, overlap):
        mesh = _mesh(4)
        x, w = _xw(np_rng, 8, 16, 12)
        ref = BM.matmul_reference(x, w)

        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            lambda a, b: BM.tp_dense(a, b, axis="x", overlap=overlap),
            mesh=mesh, in_specs=(P(None, None), P("x", None)),
            out_specs=P(None, None), check_vma=False)
        got = jax.jit(fn)(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **TOL)

    def test_untileable_batch_falls_back_to_psum(self, np_rng):
        # B=5 doesn't tile over p=4: the ring form must degrade to the
        # textbook psum, not crash — same numbers either way
        mesh = _mesh(4)
        x, w = _xw(np_rng, 5, 16, 12)
        ref = BM.matmul_reference(x, w)

        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            lambda a, b: BM.tp_dense(a, b, axis="x", overlap=True),
            mesh=mesh, in_specs=(P(None, None), P("x", None)),
            out_specs=P(None, None), check_vma=False)
        got = jax.jit(fn)(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **TOL)


def _stage_params(np_rng, n_stage, k):
    return [{"w": jnp.asarray(
                 np_rng.standard_normal((k, k)).astype(np.float32)) * 0.3,
             "b": jnp.asarray(
                 np_rng.standard_normal((k,)).astype(np.float32))}
            for _ in range(n_stage)]


def _stage_plain(p, x):
    return jax.nn.relu(x @ p["w"] + p["b"])


def _stage_tp(p, x, mm):
    return jax.nn.relu(mm(x, p["w"]) + p["b"])


class TestPipelineTensorParallel:
    @pytest.mark.parametrize("n_pipe,n_tp", [(2, 4), (4, 2)])
    def test_forward_matches_plain_pipeline(self, np_rng, n_pipe,
                                            n_tp):
        if len(jax.devices()) < n_pipe * n_tp:
            pytest.skip(f"needs {n_pipe * n_tp} devices")
        mesh = Mesh(np.array(jax.devices()).reshape(n_pipe, n_tp),
                    ("pipe", "tp"))
        pipe_mesh = Mesh(np.array(jax.devices()[:n_pipe]), ("pipe",))
        k, m, bm = 16, 5, 8
        stacked = PP.stack_stage_params(
            _stage_params(np_rng, n_pipe, k))
        micro_x = jnp.asarray(
            np_rng.standard_normal((m, bm, k)).astype(np.float32))
        ref = jax.jit(PP.make_pipeline_forward(_stage_plain,
                                               pipe_mesh))(
            PP.shard_stage_params(stacked, pipe_mesh), micro_x)
        sharded = PP.shard_stage_params(stacked, mesh, tp_axis="tp")
        fwds = {  # explicit literal: one jit wrapper per arm (GL004)
            "overlap": jax.jit(PP.make_pipeline_forward(
                _stage_tp, mesh, tp_axis="tp", tp_overlap=True)),
            "naive": jax.jit(PP.make_pipeline_forward(
                _stage_tp, mesh, tp_axis="tp", tp_overlap=False)),
        }
        for arm, fwd in fwds.items():
            np.testing.assert_allclose(np.asarray(fwd(sharded, micro_x)),
                                       np.asarray(ref), **TOL)

    @pytest.mark.slow
    def test_gradients_flow_through_ring(self, np_rng):
        """autodiff through scan + ppermute + the reduce ring: the tp
        pipeline's parameter gradients must match the plain pipeline's
        (ppermute transposes to the reverse permute; a broken ring
        transpose shows up here, not in forward)."""
        n_pipe, n_tp = 2, 4
        mesh = Mesh(np.array(jax.devices()).reshape(n_pipe, n_tp),
                    ("pipe", "tp"))
        pipe_mesh = Mesh(np.array(jax.devices()[:n_pipe]), ("pipe",))
        k, m, bm = 8, 4, 4
        stacked = PP.stack_stage_params(
            _stage_params(np_rng, n_pipe, k))
        micro_x = jnp.asarray(
            np_rng.standard_normal((m, bm, k)).astype(np.float32))

        def loss_of(fwd, params):
            return lambda p: jnp.sum(fwd(p, micro_x) ** 2)

        fwd_ref = PP.make_pipeline_forward(_stage_plain, pipe_mesh)
        g_ref = jax.jit(jax.grad(loss_of(fwd_ref, stacked)))(
            PP.shard_stage_params(stacked, pipe_mesh))
        fwd_tp = PP.make_pipeline_forward(_stage_tp, mesh,
                                          tp_axis="tp")
        g_tp = jax.jit(jax.grad(loss_of(fwd_tp, stacked)))(
            PP.shard_stage_params(stacked, mesh, tp_axis="tp"))
        for leaf_ref, leaf_tp in zip(jax.tree.leaves(g_ref),
                                     jax.tree.leaves(g_tp)):
            np.testing.assert_allclose(np.asarray(leaf_tp),
                                       np.asarray(leaf_ref),
                                       rtol=1e-5, atol=1e-5)
