"""Segmented Gram kernels: per-group cofactors out[g] = Σ_{seg(m)=g} x_m x_m^T.

This is the factorized engine's leaf-level hot op: a relation sorted by its
group key contributes, per distinct key, the [K, K] monomial block
(count / linear / quadratic in one shot when the wrapper appends an
all-ones column — u = [1, x] makes u·u^T carry c, l and q together).

TPU adaptation of the SQL ``GROUP BY``: scatter-add is hostile to the MXU,
so the kernels use the canonical **one-hot matmul** formulation —

    onehot[g, m] = (seg[m] == g)
    out[:, g]   += Σ_m flatten(x_m x_m^T) · onehot[g, m]

which turns the grouped reduction into two dense ops: a row-wise outer
product (VPU) and a ``[K², bm] @ [bm, G]`` matmul (MXU).  Operands are
feature-major (rows on lanes), as in ``segment_view``.

* ``segment_gram_kernel_call`` runs on segment-sorted rows through the
  staircase schedule of :mod:`repro.kernels.segment_view` — O(N + G) work
  for any group count.
* ``multi_segment_gram_kernel_call`` scatters every row into ``n_seg``
  group bands at once (a multi-hot row), which no single sort order serves,
  so its ``[K², ΣG]`` accumulator stays resident in VMEM across the row
  grid; the wrapper falls back to per-column ``segment_gram`` when the
  resident tile would not fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .segment_view import (
    DEFAULT_BG,
    DEFAULT_BM,
    LANE,
    PRECISION,
    SUBLANE,
    VMEM_BUDGET_BYTES,
    _pad_rows,
    _round_up,
    sorted_reduce_call,
    step_vmem_bytes,
)

__all__ = [
    "multi_segment_gram_kernel_call",
    "segment_gram_kernel_call",
]


def cross_rows(x, *, wo: int):
    """Row-wise outer products of a feature-major ``[K, bm]`` block as
    ``[K², bm]`` (row ``i·K + j`` is ``x_i·x_j``), zero-padded to ``wo``."""
    k = x.shape[0]
    return _pad_rows([x[i : i + 1] * x for i in range(k)], wo)


def _gram_rows(p, *, k: int, wo: int):
    return cross_rows(p[:k], wo=wo)


@functools.partial(
    jax.jit, static_argnames=("num_groups", "k", "bm", "bg", "interpret")
)
def segment_gram_kernel_call(
    seg: jnp.ndarray,
    payload: jnp.ndarray,
    num_groups: int,
    k: int,
    bm: int = DEFAULT_BM,
    bg: int = DEFAULT_BG,
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-group Gram on sorted ids and their feature-major payload
    ``[x_0..x_{K-1}]`` (see ``segment_view.sorted_reduce_call``).  Returns f32 ``[Wo, ng·bg]``
    whose first ``K²`` rows are the row-major per-group Grams.  Use
    ``ops.segment_gram`` generally."""
    wo = _round_up(k * k, SUBLANE)
    rows_fn = functools.partial(_gram_rows, k=k, wo=wo)
    return sorted_reduce_call(
        seg, payload, num_groups, wo, rows_fn, bm=bm, bg=bg,
        interpret=interpret,
    )


def _multi_segment_gram_kernel(ids_ref, p_ref, out_ref, *, k: int):
    """Each segment column's ids are pre-offset into a disjoint band of
    the group axis, so the *sum* of the per-column one-hots is a multi-hot
    matrix H with ``n_seg`` ones per row — and H @ cross scatters the SAME
    row-wise outer products into every column's group band in one MXU
    matmul.  The data block streams from HBM once, not once per column."""
    m = pl.program_id(0)

    @pl.when(m == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # [n_seg, bm] int32
    n_seg, bm = ids.shape
    gp = out_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (gp, bm), 0)
    hot_t = jnp.zeros((gp, bm), jnp.float32)
    for i in range(n_seg):  # static unroll — n_seg is a Python int
        hot_t += (iota == ids[i : i + 1]).astype(jnp.float32)
    cross = cross_rows(p_ref[...][:k], wo=out_ref.shape[0])
    out_ref[...] += jax.lax.dot_general(
        cross,
        hot_t,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=PRECISION,
    )


@functools.partial(
    jax.jit, static_argnames=("num_groups", "k", "bm", "interpret")
)
def multi_segment_gram_kernel_call(
    ids: jnp.ndarray,
    payload: jnp.ndarray,
    num_groups: int,
    k: int,
    bm: int = DEFAULT_BM,
    interpret: bool = True,
) -> jnp.ndarray:
    """Raw pallas_call on int32 ids ``[n_seg, M]`` — row ``i`` the segment
    column ``i`` offset into its own band of ``[0, num_groups)``, padding
    columns out of range — and the feature-major f32 payload ``[wp, M]``
    whose first K rows are the features (``M % bm == 0``).  Returns f32
    ``[Wo, Gp]`` whose first ``K²`` rows are the per-group Grams of all
    bands.  Use ``ops.multi_segment_gram`` generally."""
    wp, m = payload.shape
    n_seg = ids.shape[0]
    wo = _round_up(k * k, SUBLANE)
    gp = _round_up(num_groups, LANE)
    assert m % bm == 0 and bm % LANE == 0, (m, bm)
    assert step_vmem_bytes(wp, wo, bm, gp, n_seg) <= VMEM_BUDGET_BYTES, (
        f"resident {wo}x{gp} accumulator exceeds the VMEM budget — fall "
        "back to per-column segment_gram in the wrapper"
    )
    kernel = functools.partial(_multi_segment_gram_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((n_seg, bm), lambda mm: (0, mm)),
            pl.BlockSpec((wp, bm), lambda mm: (0, mm)),
        ],
        out_specs=pl.BlockSpec((wo, gp), lambda mm: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((wo, gp), jnp.float32),
        interpret=interpret,
    )(ids, payload)
