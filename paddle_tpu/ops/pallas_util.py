"""Where a Pallas call site runs: compiled by Mosaic, or interpreted.

Every kernel in `ops/` and every `auto` dispatch that may pick one asks
this module, through the module attribute (`pallas_util.on_tpu()`), so
one answer governs the lot. It is also the test seam:
tests/test_pallas_lowering.py patches `on_tpu` to True on the CPU host
and lowers each kernel for the `tpu` platform, which runs the whole
Pallas->Mosaic stage without a chip.
"""

from __future__ import annotations

import collections

import jax

# Mosaic scopes a kernel to 16 MiB of VMEM unless the call says
# otherwise; a v5e core has 128 MiB. Kernels that keep weights resident
# pass VMEM_LIMIT_BYTES as `vmem_limit_bytes`, and their `fits_vmem`
# gates admit a shape only while its resident set, with the pipeline's
# second buffers counted, stays inside VMEM_BUDGET_BYTES — the gap is
# the compiler's own temporaries.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
VMEM_BUDGET_BYTES = 64 * 1024 * 1024


def on_tpu() -> bool:
    """True when the default backend is a TPU: kernels compile natively
    (see `auto_kernel` for when `auto` dispatch may select them)."""
    return jax.default_backend() == "tpu"


def auto_kernel() -> bool:
    """May an `auto` dispatch select a Mosaic kernel in the program
    being traced? Only on a TPU, and only where that program is known
    to lower for ONE device: the process has a single device, or the
    trace is inside a `shard_map` over every axis of its mesh (the
    kernel then sees per-device shapes).

    XLA will not split a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), a
    trace cannot see whether the jit around it will span devices, and
    jax's `custom_partitioning` hook does not reach libtpu on this
    installation ("Custom emitter for CustomSPMDPartitioning not
    found", measured on the four-chip host). So on a host with several
    devices, outside `shard_map`, `auto` takes the XLA path, which the
    partitioner can split. An explicit impl="pallas"/"flash" still
    launches the kernel and, in a program that spans devices, fails
    with the compiler's words."""
    if not on_tpu():
        return False
    if jax.device_count() == 1:
        return True
    mesh = jax.sharding.get_abstract_mesh()
    return bool(mesh.axis_names) and (
        set(mesh.manual_axes) == set(mesh.axis_names))


def interpret() -> bool:
    """The `interpret=` argument for `pl.pallas_call`: the interpreter
    everywhere except on a TPU (CPU tests run the kernel bodies as
    plain XLA ops)."""
    return not on_tpu()


_traced: collections.Counter = collections.Counter()


def note_traced(site: str, impl: str) -> None:
    """Called by each `auto` dispatch while TRACING (never per step):
    which implementation the site took. chip_smoke.py prints the
    counts, so a chip run says what it really compiled."""
    _traced[f"{site}={impl}"] += 1


def traced() -> dict:
    """'site=impl' -> number of traces since process start."""
    return dict(_traced)

