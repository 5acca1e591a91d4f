"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds (assignment formulas):

    compute    = HLO_FLOPs      / (chips × peak_FLOP/s)
    memory     = HLO_bytes      / (chips × HBM_bw)
    collective = collective_B   / (chips × link_bw)

``compiled.cost_analysis()`` provides FLOPs and bytes accessed.  Collective
bytes are NOT in cost_analysis: :func:`collective_bytes` parses the
optimized HLO (``compiled.as_text()``) and sums operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

cost_analysis on the CPU backend reports totals for the *whole program*
(all shards execute on the 512 host "devices", so FLOPs are global); the
per-chip terms divide by the chip count, matching the assignment formulas.

MODEL_FLOPS uses the classic 6·N·D (dense) / 6·N_active·D (MoE) estimate
per training step, or 2·N·D per generated token for decode — the
"useful compute" yardstick the §Roofline table compares HLO_FLOPs against.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

from .mesh import TARGET_KIND, Hardware, hardware

__all__ = [
    "RooflineTerms",
    "TraversalNodeTerms",
    "collective_bytes",
    "roofline_terms",
    "model_flops",
    "traversal_node_terms",
]

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

#: one HLO op result, e.g. ``f32[8,128]{1,0}`` or a tuple of them.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op, by op kind.

    Parses the *optimized* HLO (post-SPMD-partitioning), where shapes are
    already per-shard; an op's result size ~= bytes moved per chip (the
    standard approximation for ring all-gather / reduce-scatter; all-reduce
    moves ~2× its payload — accounted with a factor below).
    """
    out: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        # skip the paired ``-done`` ops (zero-size start tokens parse as 0)
        if b == 0:
            continue
        out[kind] = out.get(kind, 0) + b
    return out


def total_collective_bytes(per_kind: Dict[str, int]) -> float:
    """Weighted wire bytes: ring all-reduce = reduce-scatter + all-gather
    (2× payload); the others move ~1× their result."""
    tot = 0.0
    for kind, b in per_kind.items():
        tot += 2.0 * b if kind == "all-reduce" else float(b)
    return tot


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float  # as reported by cost_analysis (see flops_scope)
    hlo_bytes: float
    coll_bytes: float
    coll_by_kind: Dict[str, int]
    model_flops: float
    per_device_hbm_peak: Optional[float] = None
    #: calibrated semantics of cost_analysis on this backend (dryrun
    #: --calibrate): "per_shard" = numbers are already per device.
    flops_scope: str = "per_shard"
    #: the chip whose published peaks the terms are taken against
    device_kind: str = TARGET_KIND

    @property
    def hw(self) -> Hardware:
        return hardware(self.device_kind)

    @property
    def _div(self) -> float:
        return float(self.chips) if self.flops_scope == "global" else 1.0

    @property
    def flops_per_device(self) -> float:
        return self.hlo_flops / self._div

    @property
    def bytes_per_device(self) -> float:
        return self.hlo_bytes / self._div

    @property
    def global_flops(self) -> float:
        return self.flops_per_device * self.chips

    @property
    def t_compute(self) -> float:
        # == HLO_FLOPs_global / (chips × peak): evaluated per device
        return self.flops_per_device / self.hw.peak_flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        # coll_bytes are already per-shard (post-SPMD shapes)
        return self.coll_bytes / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.global_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """compute-term share of the bound: T_comp / max(all terms)."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        return self.t_compute / t if t else 0.0

    def to_json(self) -> Dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "device_kind": self.device_kind,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_by_kind": self.coll_by_kind,
            "model_flops": self.model_flops,
            "flops_scope": self.flops_scope,
            "flops_per_device": self.flops_per_device,
            "global_flops": self.global_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "per_device_hbm_peak": self.per_device_hbm_peak,
        }


@dataclasses.dataclass
class TraversalNodeTerms:
    """Analytic bytes/FLOPs for ONE factorized-traversal feature node —
    the fused ``segment_view`` pass vs the unfused extend-then-group pair
    (``repro.core.factorize``).  Shapes: ``n_rows`` view rows with blocks
    (c [N], l [N, k], q [N, k, k]), reduced to ``num_groups`` groups at
    ``degree`` ∈ {1, 2}; ``dtype_bytes`` per element, int32 segment ids.

    The fused kernel reads each input block once and writes only the
    ``[G, k+2, k+2]`` packed output — the extended ``[N, k+1, k+1]``
    tensor never round-trips through memory.  The unfused path writes it
    (extend) and reads it back (group), which is where the predicted
    speedup (a pure byte ratio — both paths are bandwidth-bound, the
    FLOP/byte intensity is far below any machine balance point) comes
    from.  ``achieved_fraction(seconds)`` turns a measured node time into
    the fraction of the HBM bandwidth bound of ``device_kind`` — the chip
    the time was measured on; a kind with no published peaks raises.
    """

    n_rows: int
    k: int
    num_groups: int
    device_kind: str
    degree: int = 2
    dtype_bytes: int = 4

    @property
    def hw(self) -> Hardware:
        return hardware(self.device_kind)

    def _block_elems(self, k: int) -> int:
        """Elements per row of (c, l[, q]) blocks with k features."""
        return 1 + k + (k * k if self.degree == 2 else 0)

    @property
    def packed_width(self) -> int:
        w = self.k + 2
        return w * w if self.degree == 2 else w

    @property
    def bytes_in(self) -> float:
        """Input blocks + feature column + int32 segment ids."""
        n, b = self.n_rows, self.dtype_bytes
        return n * (self._block_elems(self.k) + 1) * b + n * 4

    @property
    def bytes_fused(self) -> float:
        return self.bytes_in + self.num_groups * self.packed_width * self.dtype_bytes

    @property
    def bytes_unfused(self) -> float:
        """Extend writes the [N, k+1(, k+1)] blocks, group reads them back
        and writes the grouped result — two extra N-sized round-trips."""
        n, b = self.n_rows, self.dtype_bytes
        ext = self._block_elems(self.k + 1)
        return (
            self.bytes_in
            + 2.0 * n * ext * b  # write + re-read of the extended blocks
            + n * b  # re-read of c by the group stage
            + self.num_groups * ext * b
        )

    @property
    def flops_fused(self) -> float:
        """Assembly muls (x·c, x²·c, x·l) + one add per packed cell."""
        n = self.n_rows
        muls = n * (self.k + 2) if self.degree == 2 else n * 1
        return muls + n * self.packed_width

    @property
    def arith_intensity(self) -> float:
        return self.flops_fused / self.bytes_fused if self.bytes_fused else 0.0

    @property
    def t_memory_fused(self) -> float:
        return self.bytes_fused / self.hw.hbm_bw

    @property
    def t_memory_unfused(self) -> float:
        return self.bytes_unfused / self.hw.hbm_bw

    @property
    def predicted_speedup(self) -> float:
        """Bandwidth-bound fused-over-unfused node throughput ratio."""
        return self.bytes_unfused / self.bytes_fused if self.bytes_fused else 0.0

    def achieved_gbs(self, seconds: float) -> float:
        return self.bytes_fused / seconds / 1e9 if seconds > 0 else 0.0

    def achieved_fraction(self, seconds: float) -> float:
        """Measured node time → fraction of the HBM bandwidth bound."""
        return self.t_memory_fused / seconds if seconds > 0 else 0.0

    def to_json(self) -> Dict:
        return {
            "n_rows": self.n_rows,
            "k": self.k,
            "device_kind": self.device_kind,
            "num_groups": self.num_groups,
            "degree": self.degree,
            "dtype_bytes": self.dtype_bytes,
            "bytes_fused": self.bytes_fused,
            "bytes_unfused": self.bytes_unfused,
            "flops_fused": self.flops_fused,
            "arith_intensity": self.arith_intensity,
            "t_memory_fused": self.t_memory_fused,
            "predicted_speedup": self.predicted_speedup,
        }


def traversal_node_terms(
    n_rows: int,
    k: int,
    num_groups: int,
    degree: int = 2,
    dtype_bytes: int = 4,
    device_kind: Optional[str] = None,
) -> TraversalNodeTerms:
    """Per-node traversal accounting for the §Roofline audit: bytes/FLOPs
    of one fused extend-and-group node from its view shape and degree.
    ``device_kind`` defaults to the first visible device's."""
    if degree not in (1, 2):
        raise ValueError(f"degree must be 1 or 2, got {degree}")
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return TraversalNodeTerms(
        n_rows=int(n_rows),
        k=int(k),
        num_groups=int(num_groups),
        device_kind=device_kind,
        degree=int(degree),
        dtype_bytes=int(dtype_bytes),
    )


def model_flops(cfg, shape) -> float:
    """Analytic 'useful FLOPs' for one step of this cell."""
    counts = cfg.param_counts()
    n_active = counts["active"]
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    if shape.kind == "train":
        return 6.0 * n_active * tokens  # fwd + bwd
    return 2.0 * n_active * tokens  # inference fwd only


def roofline_terms(
    cfg,
    shape,
    mesh_name: str,
    chips: int,
    cost: Dict[str, float],
    hlo_text: str,
    memory_stats: Optional[Dict] = None,
) -> RooflineTerms:
    per_kind = collective_bytes(hlo_text)
    return RooflineTerms(
        arch=cfg.name,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=total_collective_bytes(per_kind),
        coll_by_kind=per_kind,
        model_flops=model_flops(cfg, shape),
        per_device_hbm_peak=(memory_stats or {}).get("peak_bytes"),
    )
