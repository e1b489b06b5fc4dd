"""Per-chip peaks, keyed by `device_kind`.

ONE table: every utilization or roofline share in the repo divides by a
row of `PEAKS`, looked up by the `device_kind` JAX reports for the
device the number was measured on. A device that is not in the table is
an error, not a default.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_tflops: float      # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbps: float         # HBM bandwidth, GB/s per chip
    hbm_gb: float           # HBM capacity, GB per chip
    source: str


PEAKS = {
    # `jax.devices()[0].device_kind` on a v5e (v5 lite) chip
    "TPU v5 lite": ChipPeaks(
        bf16_tflops=197.0, hbm_gbps=819.0, hbm_gb=16.0,
        source="Google Cloud documentation, 'TPU v5e' system "
               "architecture: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s "
               "per chip"),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} has no row in "
            f"paddle_tpu.core.hw.PEAKS (known: {sorted(PEAKS)}); add its "
            "published peaks with their source before reporting a "
            "utilization on it") from None

