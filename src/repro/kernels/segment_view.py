"""Fused traversal-node kernels: extend-with-feature + GROUP BY in ONE pass.

The factorized engine's per-node hot loop (``_extend_with_feature`` followed
by ``_aggregate_out`` in ``repro.core.factorize``) used to cost 3+ dispatches
and an ``[N, k+1, k+1]`` HBM intermediate: materialize the extended quad
tensor ``[[x²c, (x·l)ᵀ], [x·l, q]]``, then scatter-add each of the c/l/q
blocks separately.  These kernels fuse the whole node: each row's extended
cofactor matrix is assembled **in VMEM** and accumulated straight into the
per-group output via a one-hot matmul on the MXU — the extended tensor never
touches HBM.

Packed layout (degree 2).  For a view row with blocks (c, l[k], q[k, k]) and
feature value x, the bordered (k+2)×(k+2) matrix

    E = | c    x·c   lᵀ     |
        | x·c  x²·c  (x·l)ᵀ |
        | l    x·l   q      |

segment-sums to exactly the extend-then-group result: the new view's blocks
are slices of ``out = Σ_{seg(m)=g} E_m``::

    c_new = out[:, 0, 0]      l_new = out[:, 1:, 0]      q_new = out[:, 1:, 1:]

(degree 1 drops the quad rows: E = [c, x·c, lᵀ] of width k+2 and
``l_new = out[:, 1:]``).  ``segment_reduce_kernel_call`` is the plain
multi-block companion: it segment-reduces an arbitrary payload (the wrapper
packs c|l|q), replacing one scatter dispatch per block at non-feature nodes
and delta folds.

Layout: **feature-major**.  A node's operands are two lane-dense arrays —
rows on the 128-wide lane axis: the int32 segment ids ``[1, M]`` and one
f32 payload ``P [Wp, M]`` holding the row's fields on sublanes.  A
row-major ``[M, 1]`` or ``[M, k]`` operand would be padded to 128 lanes per
row in the tiled HBM layout a Mosaic kernel reads, a relayout copy up to
128× the data.  The ids keep their own int32 array: bit-cast into the f32
payload, every id below 2²³ is a subnormal float, which a TPU may flush to
zero.  The output is feature-major too, ``[Wo, G]``; the wrapper
transposes the (small) result back.

Schedule: **segment-sorted rows, staircase tiles**.  The wrapper sorts rows
by segment id, so a row block of ``bm`` rows spans a contiguous id range
and touches only the ``bg``-wide group tiles that range overlaps.  The
grid walks the monotone path of (row block, group tile) pairs — at most
``nm + ng - 1`` visits — fed by two scalar-prefetched index arrays; a
group tile's output block stays in VMEM across its consecutive visits and
is zeroed on its first.  Work is O(N + G), not O(N · G): a node whose
groups are almost all singletons (the fact-table leaves of a star schema)
streams its rows once.  Padding rows carry the id ``2³¹ − 1`` and every
out-of-range id falls outside each tile's one-hot window — dropped, with
no masking branch.

Precision: f32 dots on the MXU default to one bf16 pass; every dot here
takes ``PRECISION`` (``Precision.HIGHEST``) so group sums keep f32
accuracy.  ``BF16_PASS`` switches the staircase kernels to that single
bf16 pass (bf16 operands, f32 accumulation) so a check can show what the
f32 sums buy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "DEFAULT_BG",
    "DEFAULT_BM",
    "PAD_SEG",
    "VMEM_BUDGET_BYTES",
    "bucket",
    "pick_tiles",
    "segment_reduce_kernel_call",
    "segment_view_kernel_call",
    "step_vmem_bytes",
    "tile_schedule",
]

DEFAULT_BM = 1024  # rows per block (lane axis)
DEFAULT_BG = 512  # groups per output tile (lane axis)
LANE, SUBLANE = 128, 8
# scoped VMEM a tile may use: v5e's default scoped limit is 16 MiB, and
# Mosaic keeps some for itself
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
PAD_SEG = 2**31 - 1  # id of padding rows: sorts last, never inside a tile
# the one-hot dots' precision; BF16_PASS, read when a staircase kernel is
# traced, feeds them bf16 operands in one pass instead
PRECISION = jax.lax.Precision.HIGHEST
BF16_PASS = False


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def bucket(n: int, floor: int) -> int:
    """The power of two at or above ``max(n, floor)``: sizes padded to it
    share one compiled program (``floor`` a power of two)."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


def step_vmem_bytes(
    wp: int, wo: int, bm: int, bg: int, ids: int = SUBLANE
) -> int:
    """VMEM bytes of one kernel step from a ``[wp, bm]`` payload block and
    its id rows (``ids``, padded to whole sublane tiles) into a ``[wo, bg]``
    output block: all double-buffered, plus the ``[bg, bm]`` one-hot tile,
    the assembled ``[wo, bm]`` rows and the ``[wo, bg]`` dot result."""
    ids = _round_up(ids, SUBLANE)
    return 4 * (
        2 * (wp + ids) * bm + 2 * wo * bg + bg * bm + wo * bm + wo * bg
    )


def pick_tiles(m: int, num_groups: int, wp: int, wo: int, budget: int):
    """Row block ``bm`` and group tile ``bg`` (powers of two, lane
    multiples) for a payload of ``wp`` rows and an output of ``wo`` rows:
    the defaults, clipped to the problem, halved — group tile first —
    until one step fits ``budget``; the smallest tiles when nothing does."""
    bm = min(DEFAULT_BM, bucket(m, LANE))
    bg = min(DEFAULT_BG, bucket(num_groups, LANE))
    while step_vmem_bytes(wp, wo, bm, bg) > budget and bg > LANE:
        bg = max(LANE, bg // 2)
    while step_vmem_bytes(wp, wo, bm, bg) > budget and bm > LANE:
        bm = max(LANE, bm // 2)
    return bm, bg


def tile_schedule(seg_sorted: jnp.ndarray, bm: int, bg: int, ng: int):
    """The staircase of (row block, group tile) visits for ascending ids.

    Row block ``m`` visits tiles ``lb[m-1] .. lb[m]`` (``0 ..`` for the
    first, ``.. ng-1`` for the last), where ``lb[m]`` is the tile of its
    largest in-range id: consecutive ranges share one endpoint, so the
    visits number exactly ``nm + ng - 1`` and every tile — even one no row
    maps to — is visited, hence initialised.  Both index sequences are
    non-decreasing, which is what lets a tile's output block stay resident
    across its run of visits.  Returns int32 ``(row_block, group_tile)``."""
    nm = seg_sorted.shape[0] // bm
    last = seg_sorted[bm - 1 :: bm]  # padding only ends the last block
    end = jnp.clip(last // bg, 0, ng - 1).at[nm - 1].set(ng - 1)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), end[:-1]])
    cnt = end - start + 1
    cum = jnp.cumsum(cnt)
    v = jnp.arange(nm + ng - 1, dtype=jnp.int32)
    rb = jnp.searchsorted(cum, v, side="right").astype(jnp.int32)
    gb = start[rb] + (v - (cum[rb] - cnt[rb]))
    return rb, gb.astype(jnp.int32)


def _sorted_reduce_kernel(
    rb_ref, gb_ref, seg_ref, p_ref, out_ref, *, rows_fn, bg
):
    del rb_ref  # consumed by the index maps
    v = pl.program_id(0)
    g = gb_ref[v]

    @pl.when((v == 0) | (g != gb_ref[jnp.maximum(v - 1, 0)]))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]  # [1, bm] int32
    rows = rows_fn(p_ref[...])  # [wp, bm] -> [wo, bm]
    bm = seg.shape[1]
    local = seg - g * bg
    onehot_t = (
        jax.lax.broadcasted_iota(jnp.int32, (bg, bm), 0) == local
    ).astype(jnp.float32)  # [bg, bm]
    precision = PRECISION
    if BF16_PASS:
        rows, onehot_t = rows.astype(jnp.bfloat16), onehot_t.astype(jnp.bfloat16)
        precision = None
    out_ref[...] += jax.lax.dot_general(
        rows,
        onehot_t,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


def sorted_reduce_call(
    seg: jnp.ndarray,
    payload: jnp.ndarray,
    num_groups: int,
    wo: int,
    rows_fn,
    *,
    bm: int,
    bg: int,
    interpret: bool,
) -> jnp.ndarray:
    """Raw staircase pallas_call.  ``seg`` is ``[1, M]`` int32 segment ids
    in ascending order, padding columns ``PAD_SEG``; ``payload`` is the
    ``[wp, M]`` f32 fields of the same columns, zero on padding; ``M % bm
    == 0``.  ``rows_fn`` maps a ``[wp, bm]`` block to the ``[wo, bm]`` rows
    to group-sum.  Returns f32 ``[wo, ng·bg]``: column ``g`` holds group
    ``g``'s sums."""
    wp, m = payload.shape
    assert seg.shape == (1, m) and seg.dtype == jnp.int32, seg.shape
    assert m % bm == 0 and bm % LANE == 0 and bg % LANE == 0, (m, bm, bg)
    assert wp % SUBLANE == 0 and wo % SUBLANE == 0, (wp, wo)
    assert step_vmem_bytes(wp, wo, bm, bg) <= VMEM_BUDGET_BYTES, (
        f"tile {wp}x{bm} -> {wo}x{bg} exceeds the VMEM budget"
    )
    ng = max(1, -(-num_groups // bg))
    rb, gb = tile_schedule(seg[0], bm, bg, ng)
    kernel = functools.partial(_sorted_reduce_kernel, rows_fn=rows_fn, bg=bg)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rb.shape[0],),
            in_specs=[
                pl.BlockSpec((1, bm), lambda v, rb, gb: (0, rb[v])),
                pl.BlockSpec((wp, bm), lambda v, rb, gb: (0, rb[v])),
            ],
            out_specs=pl.BlockSpec((wo, bg), lambda v, rb, gb: (0, gb[v])),
        ),
        out_shape=jax.ShapeDtypeStruct((wo, ng * bg), jnp.float32),
        interpret=interpret,
    )(rb, gb, seg, payload)


def _pad_rows(parts, wo: int):
    """Stack ``[r, bm]`` row groups on sublanes, zero-padded to ``wo``."""
    rows = jnp.concatenate(parts, axis=0)
    extra = wo - rows.shape[0]
    if extra:
        rows = jnp.concatenate(
            [rows, jnp.zeros((extra, rows.shape[1]), rows.dtype)], axis=0
        )
    return rows


def bordered_rows(p, *, k: int, degree: int, wo: int):
    """The packed E of every row of a feature-major block: payload rows are
    ``[c, x, l_0..l_{k-1}, q_00..q_{k-1,k-1}]`` and the result's row
    ``i·(k+2) + j`` is ``E[i, j]`` (degree 2) or row ``j`` of
    ``[c, x·c, l]`` (degree 1)."""
    c, x = p[0:1], p[1:2]
    xc = x * c
    if not k:  # no zero-row slices: Mosaic refuses zero-sized vectors
        return _pad_rows([c, xc] if degree == 1 else [c, xc, xc, x * xc], wo)
    l = p[2 : 2 + k]
    if degree == 1:
        return _pad_rows([c, xc, l], wo)
    xl = x * l
    q = p[2 + k : 2 + k + k * k]
    parts = [c, xc, l, xc, x * xc, xl]
    for a in range(k):
        parts += [l[a : a + 1], xl[a : a + 1], q[a * k : (a + 1) * k]]
    return _pad_rows(parts, wo)


def view_widths(k: int, degree: int):
    """(payload rows, output rows) of a segment_view node."""
    fields = 2 + k + (k * k if degree == 2 else 0)
    width = (k + 2) * (k + 2) if degree == 2 else k + 2
    return _round_up(fields, SUBLANE), _round_up(width, SUBLANE)


@functools.partial(
    jax.jit, static_argnames=("num_groups", "k", "degree", "bm", "bg", "interpret")
)
def segment_view_kernel_call(
    seg: jnp.ndarray,
    payload: jnp.ndarray,
    num_groups: int,
    k: int,
    degree: int = 2,
    bm: int = DEFAULT_BM,
    bg: int = DEFAULT_BG,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused node on sorted ids and their feature-major payload (see
    :func:`bordered_rows` for its rows).  Returns f32 ``[Wo, ng·bg]`` whose
    first ``(k+2)²`` (degree 2) or ``k+2`` rows are the packed per-group
    sums.  Use ``ops.segment_view`` generally."""
    wo = view_widths(k, degree)[1]
    rows_fn = functools.partial(bordered_rows, k=k, degree=degree, wo=wo)
    return sorted_reduce_call(
        seg, payload, num_groups, wo, rows_fn, bm=bm, bg=bg,
        interpret=interpret,
    )


def _fields(p):
    return p


@functools.partial(
    jax.jit, static_argnames=("num_groups", "bm", "bg", "interpret")
)
def segment_reduce_kernel_call(
    seg: jnp.ndarray,
    payload: jnp.ndarray,
    num_groups: int,
    bm: int = DEFAULT_BM,
    bg: int = DEFAULT_BG,
    interpret: bool = True,
) -> jnp.ndarray:
    """Multi-block segment reduce on sorted ids and their feature-major
    payload: returns f32 ``[wp, ng·bg]`` whose row ``i`` sums payload row
    ``i`` per group — ONE kernel call in place of one scatter per block.
    Use ``ops.segment_blocks``."""
    return sorted_reduce_call(
        seg, payload, num_groups, payload.shape[0], _fields, bm=bm, bg=bg,
        interpret=interpret,
    )
