"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``. A kind that is not listed is an error, never a
default."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16 per chip, 16 GB HBM2 at 819 GB/s. f32 matmuls at the
#: default precision take one bf16 pass, so the bf16 peak bounds them.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peak(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises KeyError naming the known
    kinds when it is not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
