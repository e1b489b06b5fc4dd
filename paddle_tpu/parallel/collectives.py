"""Named communication primitives over the device mesh.

The TPU-native replacement for the reference's three comm stacks — the
block-sharded parameter-server RPC (reference: pserver/ParameterServer2.h:341
sendParameter/addGradient), the Go pserver's SendGrad/GetParam (reference:
go/pserver/service.go:285,311), and Fluid's NCCL ops (reference:
operators/nccl_op.cu.cc:41-209 ncclAllReduce/Reduce/Bcast). On TPU every
one of those wire exchanges is an XLA collective over ICI/DCN; this
module names them with the reference's semantics:

  all_reduce_sum/mean  — addGradient + op_SGD barrier round trip
  all_gather           — getParameter broadcast of fresh values
  reduce_scatter       — ZeRO-style sharded-optimizer grad exchange
  all_to_all           — sparse/embedding row exchange (getParameterSparse)
  ppermute_ring        — MultiGradientMachine's neighbor ring copy
  broadcast_from       — parameter-init broadcast (FinishInitParams)

Each primitive has (a) an in-context form for use inside shard_map
(operates on per-shard values, names the mesh axis), and (b) a
whole-array convenience wrapper that builds the shard_map itself.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from paddle_tpu.core.mesh import DATA_AXIS

# ---- in-context primitives (call inside shard_map) ----

def all_reduce_sum(x, axis: str = DATA_AXIS):
    return jax.lax.psum(x, axis_name=axis)


def all_reduce_mean(x, axis: str = DATA_AXIS):
    return jax.lax.pmean(x, axis_name=axis)


def all_gather(x, axis: str = DATA_AXIS, *, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name=axis, tiled=tiled)


def reduce_scatter(x, axis: str = DATA_AXIS, *, scatter_dimension: int = 0):
    return jax.lax.psum_scatter(
        x, axis_name=axis, scatter_dimension=scatter_dimension, tiled=True)


def all_to_all(x, axis: str = DATA_AXIS, *, split_axis: int = 0,
               concat_axis: int = 0):
    return jax.lax.all_to_all(
        x, axis_name=axis, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True)


def ppermute_ring(x, axis: str = DATA_AXIS, *, shift: int = 1):
    """Rotate shards around the ring by `shift` (reference:
    MultiGradientMachine.h:61-95 neighbor-thread ring copy)."""
    n = jax.lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str = DATA_AXIS):
    return jax.lax.axis_index(axis)


# ---- whole-array wrappers (build the shard_map for you) ----

def _shmap(mesh: Mesh, fn, in_spec: P, out_spec: P):
    return jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec)


def device_all_reduce_mean(x, mesh: Mesh, axis: str = DATA_AXIS):
    """Mean-reduce an axis-sharded array's shards (the sync-SGD gradient
    exchange as one call)."""
    fn = _shmap(mesh, lambda s: all_reduce_mean(s, axis), P(axis), P(axis))
    return fn(x)


def device_broadcast_from(x, mesh: Mesh, axis: str = DATA_AXIS,
                          source: int = 0):
    """Replicate shard `source`'s value to every device along `axis`
    (reference: FinishInitParams once-only init broadcast,
    go/pserver/service.go:260)."""

    def body(s):
        idx = jax.lax.axis_index(axis)
        n = jax.lax.axis_size(axis)
        mask = (idx == source).astype(s.dtype)
        return jax.lax.psum(s * mask, axis_name=axis)

    fn = _shmap(mesh, body, P(axis), P())
    # drop the leading shard axis the P(axis) input implies: input is
    # [n*k, ...] sharded; output replicated [k, ...] from shard `source`
    return fn(x)


def replicate(x, mesh: Mesh):
    return jax.device_put(x, NamedSharding(mesh, P()))
