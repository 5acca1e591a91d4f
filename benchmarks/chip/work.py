"""The work a traversal node step requires, from its shapes alone.

A node step turns a view of ``M`` rows — a count ``c``, ``k`` linear sums
``l`` and the ``k x k`` symmetric quadratic sums ``q`` (degree 2) — into
the view of its ``G`` groups.  With a feature ``x`` (``ops.segment_view``)
the output is the view extended by ``x``, width ``k + 1``; without one
(``ops.segment_blocks``) it keeps width ``k``.  What any implementation
must do, whatever kernel computes it:

- read each distinct input value once: ``c``, ``l``, the ``k(k+1)/2``
  distinct entries of ``q``, ``x`` where there is one, and the int32
  group id of every row;
- write each distinct output value once, per group;
- form the new degree-2 products of each row (``x*c``, ``x*x*c`` and
  ``x*l_i``) and add every distinct output entry of every row into its
  group.

Everything is 4 bytes (float32 values, int32 ids).  Shares of a peak
computed from these counts cannot pass 100% unless a time leaves out work.
The roofline time is the larger of operations over peak FLOP/s and
bytes over peak bytes/s.
"""

from __future__ import annotations

WORD = 4


def distinct(k: int, degree: int) -> int:
    """Distinct values of one view row of width ``k``."""
    return 1 + (k if degree >= 1 else 0) + (k * (k + 1) // 2 if degree == 2 else 0)


def node_step(rows: int, k: int, degree: int, groups: int, feature: bool) -> dict:
    """Bytes and FLOPs one node step requires (see the module docstring)."""
    k_out = k + 1 if feature else k
    out = distinct(k_out, degree)
    read = rows * (distinct(k, degree) + (1 if feature else 0) + 1)
    products = (2 + (k if degree == 2 else 0)) if feature else 0
    if feature and degree == 1:
        products = 1  # x*c
    return {
        "bytes": WORD * (read + groups * out),
        "flops": rows * (products + out),
    }


def roofline_seconds(work: dict, peak: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
