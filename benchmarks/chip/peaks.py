"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A kind that is not in the table has no peaks: :func:`peaks` raises, so a
CPU or an unknown accelerator never borrows another chip's numbers.
"""

from __future__ import annotations

#: TPU v5e, which JAX reports as "TPU v5 lite".  Source: Google Cloud
#: documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
#: bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
#: interchip interconnect.  Copied from ``repro.launch.mesh.HW``.
PEAKS = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(kind: str) -> dict:
    """The peaks of ``device_kind`` ``kind``; ``KeyError`` for an unknown one."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; known: {sorted(PEAKS)}"
        ) from None
