"""Public jit'd wrappers around the Pallas kernels.

Handle padding, sorting and the feature-major packing so callers pass
arbitrary shapes; select interpret mode automatically off-TPU (the kernels
TARGET TPU; interpret=True executes the kernel body in Python for CPU
validation, per the repo's dry-run-first methodology).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash import DEFAULT_BK as FL_BK, DEFAULT_BQ as FL_BQ, flash_kernel_call
from .gram import DEFAULT_BK, DEFAULT_BM, gram_kernel_call
from .moments import DEFAULT_BM as MOM_BM, moments_kernel_call
from .segment_gram import (
    multi_segment_gram_kernel_call,
    segment_gram_kernel_call,
)
from .segment_view import (
    DEFAULT_BM as SV_BM,
    LANE,
    PAD_SEG,
    SUBLANE,
    VMEM_BUDGET_BYTES,
    bucket,
    pick_tiles,
    segment_reduce_kernel_call,
    segment_view_kernel_call,
    step_vmem_bytes,
    view_widths,
)

__all__ = [
    "gram",
    "segment_gram",
    "multi_segment_gram",
    "segment_view",
    "segment_blocks",
    "group_ids_device",
    "fast_device_grouping",
    "moments",
    "flash_attention",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _budget(vmem_budget: int | None) -> int:
    return min(vmem_budget or VMEM_BUDGET_BYTES, VMEM_BUDGET_BYTES)


def gram(
    x: jnp.ndarray,
    bm: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """X^T X for any [M, K]; fp32 result. Pads to block multiples with zeros
    (zero rows/cols are Gram-neutral) and slices the result back."""
    if interpret is None:
        interpret = not on_tpu()
    m, k = x.shape
    bm = bm or min(DEFAULT_BM, _round_up(max(m, 1), 8))
    bk = bk or min(DEFAULT_BK, _round_up(max(k, 1), 128))
    mp, kp = _round_up(max(m, 1), bm), _round_up(max(k, 1), bk)
    xp = jnp.zeros((mp, kp), dtype=x.dtype).at[:m, :k].set(x)
    out = gram_kernel_call(xp, bm=bm, bk=bk, interpret=interpret)
    return out[:k, :k]


def _rows_of(f):
    """A per-row field ``[M]``, ``[M, w]`` or ``[M, k, k]`` as feature-major
    rows ``[w', M]`` — by slicing and transposing only: on a TPU, a reshape
    that merges a small trailing dim into a long one compiles for a minute
    at ten million rows."""
    if f.ndim == 1:
        return [f[None]]
    if f.ndim == 2:
        return [f.T]
    return [f[:, a, :].T for a in range(f.shape[1])]


def _blocks_of(rows, k: int):
    """Inverse of :func:`_rows_of` for a ``[k·k, G]`` row block: the
    ``[G, k, k]`` tensor, by stacking and moving axes only."""
    if k == 0:
        return jnp.zeros((rows.shape[1], 0, 0), rows.dtype)
    return jnp.moveaxis(
        jnp.stack([rows[a * k : (a + 1) * k] for a in range(k)]), -1, 0
    )


@functools.partial(jax.jit, static_argnames=("wp", "mp"))
def _sorted_payload(seg, order, fields, *, wp: int, mp: int):
    """The staircase kernels' operands, rows sorted by segment id
    (``order``, a stable argsort of ``seg``, computed when not given): the
    int32 ids ``[1, mp]`` and the fields stacked feature-major ``[wp,
    mp]``, padding columns carrying id ``PAD_SEG`` and zero fields."""
    m = seg.shape[0]
    seg = seg.astype(jnp.int32)
    if order is None:
        order = jnp.argsort(seg, stable=True)
    ids = jnp.concatenate(
        [jnp.take(seg, order), jnp.full((mp - m,), PAD_SEG, jnp.int32)]
    )
    rows = []
    for f in fields:
        for r in _rows_of(jnp.take(f, order, axis=0)):
            if r.shape[0]:
                rows.append(
                    jnp.pad(r.astype(jnp.float32), ((0, 0), (0, mp - m)))
                )
    extra = wp - sum(r.shape[0] for r in rows)
    if extra:
        rows.append(jnp.zeros((extra, mp), jnp.float32))
    return ids[None], jnp.concatenate(rows, axis=0)


def _sorted_reduce(seg, order, fields, num_groups, wp, wo, budget, call):
    """Pad, sort and pack a staircase reduction, run ``call(ids, payload,
    groups, bm, bg)`` on it and return its ``[wo, ≥num_groups]`` output.
    Row and group counts are bucketed to powers of two, so nodes of nearby
    sizes share one compiled kernel."""
    m = seg.shape[0]
    groups = bucket(num_groups, 1)
    bm, bg = pick_tiles(m, groups, wp, wo, budget)
    ids, payload = _sorted_payload(
        seg, order, fields, wp=wp, mp=bucket(m, bm)
    )
    return call(ids, payload, groups, bm, bg)


@functools.partial(jax.jit, static_argnames=("num_groups", "k"))
def _gram_blocks(out, *, num_groups: int, k: int):
    return _blocks_of(out[: k * k, :num_groups], k)


def segment_gram(
    x: jnp.ndarray,
    seg: jnp.ndarray,
    num_groups: int,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
) -> jnp.ndarray:
    """Per-group Gram for any [M, K] + int seg [M]; fp32 [G, K, K].

    Rows are sorted by segment and reduced by the staircase kernel; ids
    outside ``[0, num_groups)`` contribute nothing.  ``vmem_budget``
    (default and cap ``VMEM_BUDGET_BYTES``) bounds one step's VMEM and so
    sizes the row block and group tile — override only to force small
    tiles, e.g. in tests.
    """
    if interpret is None:
        interpret = not on_tpu()
    k = x.shape[1]
    out = _sorted_reduce(
        jnp.asarray(seg),
        None,
        [jnp.asarray(x)],
        num_groups,
        _round_up(k, SUBLANE),
        _round_up(k * k, SUBLANE),
        _budget(vmem_budget),
        lambda s, p, g, bm, bg: segment_gram_kernel_call(
            s, p, g, k, bm=bm, bg=bg, interpret=interpret
        ),
    )
    return _gram_blocks(out, num_groups=num_groups, k=k)


@functools.partial(jax.jit, static_argnames=("mp", "wp"))
def _multi_segment_payload(x, segs, offs, *, mp: int, wp: int):
    """int32 ids ``[n_seg, mp]`` offset into their bands (padding
    ``PAD_SEG``) and the features feature-major ``[wp, mp]``."""
    m, k = x.shape
    ids = jnp.pad(
        (segs.astype(jnp.int32) + offs[None, :]).T,
        ((0, 0), (0, mp - m)),
        constant_values=PAD_SEG,
    )
    payload = jnp.pad(x.T.astype(jnp.float32), ((0, wp - k), (0, mp - m)))
    return ids, payload


def multi_segment_gram(
    x: jnp.ndarray,
    segs: jnp.ndarray,
    num_groups,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
):
    """Per-group Grams for SEVERAL segment-id columns in one fused pass.

    ``x`` is any [M, K]; ``segs`` is [M, n_seg] int with column ``i``'s ids
    in ``[0, num_groups[i])``.  Returns a list of fp32 [G_i, K, K] — one
    grouped Gram per segment column — while streaming the data block from
    memory ONCE, instead of re-reading x per column as n_seg separate
    ``segment_gram`` calls would.  Ids are offset into disjoint bands of a
    single [ΣG, K, K] accumulator held in VMEM.  If no row block lets that
    accumulator fit the VMEM budget, falls back to per-column
    ``segment_gram`` (any group count) — correctness never depends on the
    fused path fitting.
    """
    if interpret is None:
        interpret = not on_tpu()
    budget = _budget(vmem_budget)
    m, k = x.shape
    num_groups = [int(g) for g in num_groups]
    n_seg = segs.shape[1]
    assert n_seg == len(num_groups), (segs.shape, num_groups)
    if n_seg == 0:
        return []
    total = sum(num_groups)
    wp = _round_up(k, SUBLANE)
    wo, gp = _round_up(k * k, SUBLANE), _round_up(total, LANE)
    bm = min(SV_BM, bucket(m, LANE))
    while step_vmem_bytes(wp, wo, bm, gp, n_seg) > budget and bm > LANE:
        bm //= 2
    if step_vmem_bytes(wp, wo, bm, gp, n_seg) > budget:
        return [
            segment_gram(
                x, segs[:, i], num_groups[i],
                interpret=interpret, vmem_budget=vmem_budget,
            )
            for i in range(n_seg)
        ]
    offs = np.concatenate([[0], np.cumsum(num_groups)]).astype(np.int32)
    ids, payload = _multi_segment_payload(
        jnp.asarray(x), jnp.asarray(segs), jnp.asarray(offs[:-1]),
        mp=bucket(m, bm), wp=wp,
    )
    out = multi_segment_gram_kernel_call(
        ids, payload, total, k, bm=bm, interpret=interpret
    )
    blocks = _gram_blocks(out, num_groups=total, k=k)
    return [blocks[offs[i] : offs[i + 1]] for i in range(n_seg)]


@functools.partial(jax.jit, static_argnames=("num_groups",))
def _sv_xla_deg1(c, x, l, seg, num_groups: int):
    ext = jnp.concatenate([c[:, None], (x * c)[:, None], l], axis=1)
    return jax.ops.segment_sum(ext, seg, num_segments=num_groups)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def _sv_xla_deg2(c, x, l, q, seg, num_groups: int):
    # compact payload: the packed [k+2, k+2] matrix is symmetric with
    # duplicated borders, so only the 3 + 2k + k² distinct sums go through
    # the row-sized assemble + scatter; the packed form is rebuilt from
    # the [G]-sized sums afterwards (G ≪ N — negligible traffic).
    n, k = l.shape
    xc = x * c
    xl = x[:, None] * l
    payload = jnp.concatenate(
        [
            c[:, None],
            xc[:, None],
            (x * xc)[:, None],
            l,
            xl,
            q.reshape(n, k * k),
        ],
        axis=1,
    )
    s = jax.ops.segment_sum(payload, seg, num_segments=num_groups)
    sc, sxc, sx2c = s[:, :1], s[:, 1:2], s[:, 2:3]
    sl = s[:, 3 : 3 + k]
    sxl = s[:, 3 + k : 3 + 2 * k]
    sq = s[:, 3 + 2 * k :].reshape(num_groups, k, k)
    row0 = jnp.concatenate([sc, sxc, sl], axis=1)
    row1 = jnp.concatenate([sxc, sx2c, sxl], axis=1)
    rest = jnp.concatenate([sl[:, :, None], sxl[:, :, None], sq], axis=2)
    return jnp.concatenate(
        [row0[:, None, :], row1[:, None, :], rest], axis=1
    )


@functools.partial(jax.jit, static_argnames=("num_groups", "k", "degree"))
def _view_blocks(out, *, num_groups: int, k: int, degree: int):
    """``(c, l, q)`` of a node from the kernel's packed ``[Wo, ≥G]`` rows
    (row ``i·(k+2) + j`` is ``E[i, j]``; degree 1: row ``j`` of
    ``[c, x·c, l]``)."""
    w = k + 2
    rows = out[:, :num_groups]
    if degree == 1:
        return rows[0], rows[1:w].T, None
    l = jnp.stack([rows[i * w] for i in range(1, w)], axis=1)
    q = jnp.moveaxis(
        jnp.stack([rows[i * w + 1 : (i + 1) * w] for i in range(1, w)]), -1, 0
    )
    return rows[0], l, q


def default_impl() -> str:
    """The node kernels' implementation on this backend: the compiled
    Pallas kernel on a TPU, the jitted XLA fusion elsewhere."""
    return "pallas" if on_tpu() else "xla"


def segment_view(
    c: jnp.ndarray,
    x: jnp.ndarray,
    l: jnp.ndarray,
    q: jnp.ndarray | None,
    seg: jnp.ndarray,
    num_groups: int,
    *,
    degree: int = 2,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
    impl: str | None = None,
    order: jnp.ndarray | None = None,
):
    """Fused traversal node: extend a view's blocks with feature ``x`` AND
    GROUP BY in one pass — ``(c [M], l [M, k], q [M, k, k])`` plus seg ids
    become ``(c' [G], l' [G, k+1], q' [G, k+1, k+1])`` with the feature
    prepended, and the extended ``[M, k+1, k+1]`` tensor never hits HBM.

    ``impl='pallas'`` is the TPU kernel (default on TPU; interpret mode
    elsewhere is for validation only): rows sorted by segment — ``order``
    is a stable argsort of ``seg`` when the caller's grouping already made
    one — then the staircase schedule, O(M + G) for any group count, with
    tiles sized against ``vmem_budget``.  ``impl='xla'`` (default off-TPU)
    is the same one-dispatch fusion as a jitted assemble +
    ``jax.ops.segment_sum``.  Ids outside ``[0, num_groups)`` contribute
    nothing.  Returns blocks in ``c``'s dtype.
    """
    if degree not in (1, 2):
        raise ValueError(f"segment_view needs degree 1 or 2, got {degree}")
    if impl is None:
        impl = default_impl()
    if interpret is None:
        interpret = not on_tpu()
    c, x, l = jnp.asarray(c), jnp.asarray(x), jnp.asarray(l)
    q = jnp.asarray(q) if degree == 2 else None
    seg = jnp.asarray(seg).astype(jnp.int32)
    k = l.shape[1]
    if impl == "pallas":
        wp, wo = view_widths(k, degree)
        out = _sorted_reduce(
            seg,
            order,
            [c, x, l] + ([q] if degree == 2 else []),
            num_groups,
            wp,
            wo,
            _budget(vmem_budget),
            lambda s, p, g, bm, bg: segment_view_kernel_call(
                s, p, g, k, degree, bm=bm, bg=bg, interpret=interpret
            ),
        )
        blocks = _view_blocks(out, num_groups=num_groups, k=k, degree=degree)
        return tuple(b if b is None else b.astype(c.dtype) for b in blocks)
    if degree == 1:
        packed = _sv_xla_deg1(c, x, l, seg, num_groups).astype(c.dtype)
        return packed[:, 0], packed[:, 1:], None
    packed = _sv_xla_deg2(c, x, l, q, seg, num_groups).astype(c.dtype)
    return packed[:, 0, 0], packed[:, 1:, 0], packed[:, 1:, 1:]


@functools.partial(jax.jit, static_argnames=("num_groups", "k", "degree"))
def _reduced_blocks(out, *, num_groups: int, k: int, degree: int):
    """``(c, l, q)`` from the reduce kernel's ``[c | l | q]`` field rows."""
    rows = out[:, :num_groups]
    l = rows[1 : 1 + k].T if degree >= 1 else None
    q = _blocks_of(rows[1 + k : 1 + k + k * k], k) if degree == 2 else None
    return rows[0], l, q


def segment_blocks(
    c: jnp.ndarray,
    l: jnp.ndarray | None,
    q: jnp.ndarray | None,
    seg: jnp.ndarray,
    num_groups: int,
    *,
    degree: int = 2,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
    impl: str | None = None,
    order: jnp.ndarray | None = None,
):
    """Segment-reduce ALL of a view's blocks in one call: c [M] (+ l [M, k]
    + q [M, k, k] per ``degree``) packed side by side through a single
    kernel dispatch instead of one scatter per block.  Same impl / ``order``
    / tiling contract as :func:`segment_view`; returns ``(c', l', q')``
    with Nones past ``degree``, in ``c``'s dtype."""
    if impl is None:
        impl = default_impl()
    if interpret is None:
        interpret = not on_tpu()
    c = jnp.asarray(c)
    m = c.shape[0]
    k = l.shape[1] if degree >= 1 else 0
    seg = jnp.asarray(seg).astype(jnp.int32)
    if impl == "pallas":
        fields = [c, l, q][: degree + 1]
        wp = _round_up(1 + k + (k * k if degree == 2 else 0), SUBLANE)
        out = _sorted_reduce(
            seg,
            order,
            [jnp.asarray(f) for f in fields],
            num_groups,
            wp,
            wp,
            _budget(vmem_budget),
            lambda s, p, g, bm, bg: segment_reduce_kernel_call(
                s, p, g, bm=bm, bg=bg, interpret=interpret
            ),
        )
        blocks = _reduced_blocks(
            out, num_groups=num_groups, k=k, degree=degree
        )
        return tuple(b if b is None else b.astype(c.dtype) for b in blocks)
    fields = [c[:, None]]
    if degree >= 1:
        fields.append(jnp.asarray(l))
    if degree == 2:
        fields.append(jnp.asarray(q).reshape(m, k * k))
    out = jax.ops.segment_sum(
        jnp.concatenate(fields, axis=1), seg, num_segments=num_groups
    ).astype(c.dtype)
    c_new = out[:, 0]
    l_new = out[:, 1 : 1 + k] if degree >= 1 else None
    q_new = (
        out[:, 1 + k :].reshape(num_groups, k, k) if degree == 2 else None
    )
    return c_new, l_new, q_new


def fast_device_grouping() -> bool:
    """Whether :func:`group_ids_device` beats host ``np.unique`` here.
    XLA's CPU sort is single-threaded and measurably slower than numpy's —
    the device path pays off only where the sort actually runs on an
    accelerator (and the ids would otherwise round-trip to the host)."""
    return jax.default_backend() != "cpu"


@jax.jit
def _sort_pass(key, carry):
    """One stable pass of an LSD sort: ``carry`` reordered by int32
    ``key``.  Every grouping runs this one program shape per size bucket
    (a multi-key TPU sort compiles three to four times slower)."""
    return jax.lax.sort((key, carry), num_keys=1, is_stable=True)[1]


@jax.jit
def _runs(n, order, *cols):
    """Group starts and per-row group ids of the rows ``order`` sorts by
    ``cols`` (lexicographically); entries at or past ``n`` are padding."""
    idx = jnp.arange(order.shape[0], dtype=jnp.int32)
    new = idx == 0
    for c in cols:
        s = jnp.take(c, order)
        new = new | (s != jnp.roll(s, 1))
    start = new & (idx < n)
    gid = jnp.cumsum(start.astype(jnp.int32)) - 1
    inv = jnp.zeros_like(gid).at[order].set(gid)
    return start, inv


def _key_columns(key: np.ndarray) -> list:
    """int32 sort keys whose lexicographic order is the int64 codes' order
    exactly: the codes themselves when they fit int32, else the signed high
    half and the low half biased by 2³¹ (unsigned order as signed)."""
    i32 = np.iinfo(np.int32)
    if key.min() >= i32.min and key.max() <= i32.max:
        return [key.astype(np.int32)]
    return [
        (key >> 32).astype(np.int32),
        ((key & 0xFFFFFFFF) - 2**31).astype(np.int32),
    ]


def group_ids_device(key) -> tuple:
    """Device-resident GROUP BY ids: stable sort + adjacent-difference run
    detection instead of host ``np.unique``.  ``key`` is the host int64
    group code (``relation.group_key``); codes wider than int32 sort as two
    32-bit halves (least significant pass first), so no code is ever
    truncated.  Sizes are padded to a power of two with ``PAD_SEG`` keys,
    which sort last.  Returns ``(seg, num_groups, first, order)``
    bit-compatible with ``np.unique(key, return_index=True,
    return_inverse=True)`` — groups numbered in ascending key order, and
    ``first`` (host int array) the first occurrence of each group, ready to
    gather host key columns.  ``seg`` and ``order`` (the stable sort
    permutation) stay on device, feeding :func:`segment_view` /
    :func:`segment_blocks` without a host round-trip of the per-row ids."""
    key = np.asarray(key, dtype=np.int64)
    n = key.shape[0]
    if n == 0:
        empty = jnp.zeros((0,), jnp.int32)
        return empty, 0, np.zeros((0,), np.int64), empty
    size = bucket(n, 1024)
    cols = [
        jnp.asarray(np.pad(c, (0, size - n), constant_values=PAD_SEG))
        for c in _key_columns(key)
    ]
    order = jnp.arange(size, dtype=jnp.int32)
    for c in reversed(cols):
        order = _sort_pass(jnp.take(c, order), order)
    start, inv = _runs(n, order, *cols)
    first = np.asarray(order)[np.asarray(start)].astype(np.int64)
    return inv[:n], int(first.shape[0]), first, order[:n]


def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, KH, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused online-softmax attention for arbitrary shapes; returns
    [B, Sq, H, D].  Pads Sq/Sk to block multiples (padding keys are masked
    via ``kv_len``; padding queries are sliced off).  GQA KV heads are
    broadcast to query heads before the call — the kernel streams the
    (repeated) K/V tiles from HBM, trading the GQA bandwidth saving for a
    single uniform kernel (measured trade-off documented in
    EXPERIMENTS.md §Perf)."""
    if interpret is None:
        interpret = not on_tpu()
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    bq = bq or min(FL_BQ, _round_up(max(sq, 1), 8))
    bk = bk or min(FL_BK, _round_up(max(sk, 1), 8))
    sqp, skp = _round_up(sq, bq), _round_up(sk, bk)
    qp = jnp.zeros((b * h, sqp, d), qf.dtype).at[:, :sq].set(qf)
    kp = jnp.zeros((b * h, skp, d), kf.dtype).at[:, :sk].set(kf)
    vp = jnp.zeros((b * h, skp, d), vf.dtype).at[:, :sk].set(vf)
    out = flash_kernel_call(
        qp, kp, vp, causal=causal, window=window, kv_len=sk,
        bq=bq, bk=bk, interpret=interpret,
    )
    out = out[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out


def moments(x: jnp.ndarray, bm: int | None = None, interpret: bool | None = None):
    """(Σx, max|x|, count) for a 1-D column in one fused pass."""
    if interpret is None:
        interpret = not on_tpu()
    (m,) = x.shape
    rows = -(-max(m, 1) // 128)
    bm = bm or min(MOM_BM, _round_up(rows, 8))
    rp = _round_up(rows, bm)
    xp = jnp.zeros((rp * 128,), dtype=x.dtype).at[:m].set(x)
    s, mx = moments_kernel_call(
        xp.reshape(rp, 128), bm=bm, interpret=interpret
    )
    return jnp.sum(s), jnp.max(mx), m
