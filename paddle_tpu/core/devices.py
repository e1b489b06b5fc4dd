"""Device runtime helpers."""

from __future__ import annotations


def require_chip():
    """The TPU devices of this process, or an error.

    A path that measures, or that claims to have run on the chip, calls
    this first: it fails when JAX's default backend is not `tpu`
    instead of going on to run on the CPU, and when the device is not
    in the peaks table (`core.hw.PEAKS`), so that a utilization is
    never computed from another chip's peak. Returns
    (devices, peaks of `devices[0].device_kind`).
    """
    import jax

    from paddle_tpu.core import hw

    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"no TPU: jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={jax.config.jax_platforms!r}); this path "
            "runs on the chip only")
    devices = jax.devices()
    return devices, hw.peaks(devices[0].device_kind)
