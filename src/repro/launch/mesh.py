"""Production mesh construction and per-chip hardware peaks.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; smoke tests must keep seeing 1 device.

Mesh shapes per the assignment:

* single-pod:  (16, 16)      axes ("data", "model")   — 256 chips
* multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Every mesh axis is ``Auto``: the sharding layer places arrays through
``with_sharding_constraint``, which only accepts Auto axes.

Published chip peaks live in ``HW``, keyed by ``jax.Device.device_kind``,
so every consumer (roofline, benchmarks, docs) quotes one source.  A kind
that is not in the table has no peaks: :func:`hardware` raises.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_host_mesh",
    "HW",
    "Hardware",
    "TARGET_KIND",
    "hardware",
]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops_bf16: float  # per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per link
    hbm_bytes: float  # per chip


#: Per-chip peaks by ``device_kind``.  TPU v5e (which JAX reports as
#: ``"TPU v5 lite"``): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect
#: (four links of 50 GB/s).
HW: Dict[str, Hardware] = {
    "TPU v5 lite": Hardware(
        name="TPU v5e",
        peak_flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,
        hbm_bytes=16e9,
    ),
}

#: The chip the production meshes and dry-run roofline model.
TARGET_KIND = "TPU v5 lite"


def hardware(kind: Optional[str] = None) -> Hardware:
    """Peaks of the chip ``kind`` (default: the first visible device's).
    Raises ``ValueError`` for a kind with no published peaks — a CPU or an
    unknown accelerator never borrows another chip's numbers."""
    if kind is None:
        kind = jax.devices()[0].device_kind
    try:
        return HW[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; known kinds: "
            f"{sorted(HW)}"
        ) from None


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many real devices exist (tests)."""
    return _auto_mesh((data, model), ("data", "model"))
