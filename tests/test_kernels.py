"""Pallas kernel correctness: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode on CPU (the kernels TARGET TPU; interpret
executes the kernel body in Python), asserting allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash import flash_kernel_call
from repro.kernels.gram import gram_kernel_call
from repro.core.relation import group_key

KEY = jax.random.key(42)


def rand(shape, dtype, key=KEY):
    x = jax.random.normal(key, shape, jnp.float32) * 3.0
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# gram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 7, 8, 129, 1000])
@pytest.mark.parametrize("k", [1, 3, 64, 130])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_sweep(m, k, dtype):
    x = rand((m, k), dtype)
    out = ops.gram(x)
    expect = ref.gram_ref(x)
    # fp32 accumulation order differs between the blocked kernel and the
    # one-shot oracle: near-zero entries see ~1e-3 relative noise at
    # m=1000 — atol covers them, rtol still catches indexing bugs.
    rtol, atol = (1e-3, 5e-2) if dtype == jnp.float32 else (3e-2, 3e-2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=rtol, atol=atol
    )


def test_gram_blocked_padding_exact():
    """Padding rows/cols must contribute exactly nothing."""
    x = rand((130, 5), jnp.float32)
    out = ops.gram(x, bm=64, bk=128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.gram_ref(x)), rtol=1e-5, atol=1e-4
    )


def test_gram_kernel_call_requires_aligned():
    with pytest.raises(AssertionError):
        gram_kernel_call(jnp.zeros((100, 128)), bm=64, bk=128)


# ---------------------------------------------------------------------------
# segment gram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,g", [(5, 1), (64, 4), (200, 17), (1000, 3)])
@pytest.mark.parametrize("k", [2, 9])
def test_segment_gram_sweep(m, g, k):
    x = rand((m, k), jnp.float32)
    seg = jax.random.randint(KEY, (m,), 0, g)
    out = ops.segment_gram(x, seg, g)
    expect = ref.segment_gram_ref(x, seg, g)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-4
    )


def test_segment_gram_group_chunking():
    """Group counts above the VMEM budget must chunk transparently."""
    m, k = 64, 40  # 40*40*4 = 6.4 KB per group
    x = rand((m, k), jnp.float32)
    g = 4000  # 4000 groups * 6.4KB > 8MB budget -> chunked path
    seg = jax.random.randint(KEY, (m,), 0, g)
    out = ops.segment_gram(x, seg, g)
    expect = ref.segment_gram_ref(x, seg, g)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("budget", [40, 100, 200])
def test_segment_gram_forced_chunking_matches_unchunked(budget):
    """A tiny VMEM budget forces the smallest tiles, so the staircase
    walks several (row block, group tile) visits: the result must match
    the default tiles and the oracle."""
    m, k, g = 57, 3, 10
    x = rand((m, k), jnp.float32)
    seg = jax.random.randint(KEY, (m,), 0, g)
    chunked = ops.segment_gram(x, seg, g, vmem_budget=budget)
    unchunked = ops.segment_gram(x, seg, g)
    np.testing.assert_allclose(
        np.asarray(chunked), np.asarray(unchunked), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(chunked),
        np.asarray(ref.segment_gram_ref(x, seg, g)),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# moments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 8, 100, 4096])
def test_moments_sweep(m):
    x = rand((m,), jnp.float32)
    s, mx, cnt = ops.moments(x)
    es, emx, ecnt = ref.moments_ref(x)
    np.testing.assert_allclose(float(s), float(es), rtol=1e-5)
    np.testing.assert_allclose(float(mx), float(emx), rtol=1e-6)
    assert cnt == ecnt


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,sq,sk,h,kh,d,causal,window",
    [
        (2, 64, 64, 4, 2, 32, True, None),   # GQA causal
        (1, 48, 48, 2, 2, 16, True, 16),     # sliding window
        (2, 24, 72, 3, 1, 64, False, None),  # MQA, non-causal, ragged blocks
        (1, 16, 128, 4, 4, 128, True, None), # long kv, MXU-width head
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(b, sq, sk, h, kh, d, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = rand((b, sq, h, d), dtype, ks[0])
    k = rand((b, sk, kh, d), dtype, ks[1])
    v = rand((b, sk, kh, d), dtype, ks[2])
    out = ops.flash_attention(
        q, k, v, causal=causal, window=window, bq=16, bk=16
    )
    g = h // kh
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    expect = (
        ref.flash_ref(qr, kr, vr, causal=causal, window=window)
        .reshape(b, h, sq, d)
        .transpose(0, 2, 1, 3)
    )
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(expect, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_matches_model_chunked_path():
    """The Pallas kernel and the jnp online-softmax path must agree."""
    from repro.models.attention import chunked_attention

    b, s, h, kh, d = 2, 64, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = rand((b, s, h, d), jnp.float32, ks[0])
    k = rand((b, s, kh, d), jnp.float32, ks[1])
    v = rand((b, s, kh, d), jnp.float32, ks[2])
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)
    out_jnp = chunked_attention(
        q, k, v, pos, pos, causal=True, window=None,
        out_dtype=jnp.float32, q_chunk=16, k_chunk=16,
    )
    out_pl = ops.flash_attention(q, k, v, causal=True, bq=16, bk=16)
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_jnp), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("m", [5, 64, 200])
@pytest.mark.parametrize("doms", [[3], [4, 7], [5, 2, 9]])
def test_multi_segment_gram_matches_per_column(m, doms):
    """The fused multi-column kernel == one segment_gram per column, while
    streaming the data block once."""
    k = 4
    x = rand((m, k), jnp.float32)
    segs = jnp.stack(
        [
            jax.random.randint(jax.random.key(i + 1), (m,), 0, d)
            for i, d in enumerate(doms)
        ],
        axis=1,
    )
    outs = ops.multi_segment_gram(x, segs, doms)
    assert len(outs) == len(doms)
    for i, d in enumerate(doms):
        expect = ref.segment_gram_ref(x, segs[:, i], d)
        np.testing.assert_allclose(
            np.asarray(outs[i]), np.asarray(expect), rtol=1e-4, atol=1e-4
        )


def test_multi_segment_gram_vmem_fallback_matches_fused():
    """Over-budget accumulators fall back to per-column (chunked)
    segment_gram — same numbers either way."""
    m, k, doms = 120, 3, [10, 6]
    x = rand((m, k), jnp.float32)
    segs = jnp.stack(
        [
            jax.random.randint(jax.random.key(i + 9), (m,), 0, d)
            for i, d in enumerate(doms)
        ],
        axis=1,
    )
    fused = ops.multi_segment_gram(x, segs, doms)
    tiny = ops.multi_segment_gram(x, segs, doms, vmem_budget=200)
    for a, b in zip(fused, tiny):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )


def test_multi_segment_gram_empty_columns():
    x = rand((10, 2), jnp.float32)
    assert ops.multi_segment_gram(x, jnp.zeros((10, 0), jnp.int32), []) == []


# ---------------------------------------------------------------------------
# fused traversal node: segment_view / segment_blocks
# ---------------------------------------------------------------------------

def _sv_inputs(m, k, g, dtype=jnp.float32, key=KEY):
    ks = jax.random.split(key, 4)
    c = rand((m,), dtype, ks[0])
    x = rand((m,), dtype, ks[1])
    l = rand((m, k), dtype, ks[2])
    q = rand((m, k, k), dtype, ks[3])
    seg = jax.random.randint(KEY, (m,), 0, g)
    return c, x, l, q, seg


def _assert_view_eq(got, expect, rtol=1e-5, atol=1e-4):
    for a, b in zip(got, expect):
        assert (a is None) == (b is None)
        if a is not None:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=rtol, atol=atol
            )


@pytest.mark.parametrize("m,g", [(5, 1), (64, 4), (200, 17), (1000, 3)])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("degree", [1, 2])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_segment_view_sweep(m, g, k, degree, impl):
    """One fused dispatch == materialized extend + per-block scatter."""
    c, x, l, q, seg = _sv_inputs(m, k, g)
    got = ops.segment_view(
        c, x, l, q if degree == 2 else None, seg, g, degree=degree, impl=impl
    )
    expect = ref.segment_view_ref(c, x, l, q, seg, g, degree=degree)
    _assert_view_eq(got, expect)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_segment_view_k0_padding(impl):
    """Views with no features yet (k=0): the Pallas path pads a zero
    feature column — the slice back must be exact."""
    m, g = 37, 5
    c, x, _, _, seg = _sv_inputs(m, 1, g)
    l = jnp.zeros((m, 0), jnp.float32)
    q = jnp.zeros((m, 0, 0), jnp.float32)
    got = ops.segment_view(c, x, l, q, seg, g, degree=2, impl=impl)
    expect = ref.segment_view_ref(c, x, l, q, seg, g, degree=2)
    _assert_view_eq(got, expect)
    assert got[1].shape == (g, 1) and got[2].shape == (g, 1, 1)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("budget", [100, 400, 1000])
def test_segment_view_forced_chunking(impl, budget):
    """A tiny vmem_budget forces the smallest tiles — same numbers as the
    default tiles and the oracle (mirrors
    test_segment_gram_forced_chunking_matches_unchunked)."""
    m, k, g = 157, 3, 11
    c, x, l, q, seg = _sv_inputs(m, k, g)
    chunked = ops.segment_view(
        c, x, l, q, seg, g, degree=2, impl=impl, vmem_budget=budget
    )
    one_shot = ops.segment_view(c, x, l, q, seg, g, degree=2, impl=impl)
    _assert_view_eq(chunked, one_shot, rtol=1e-6, atol=1e-6)
    _assert_view_eq(chunked, ref.segment_view_ref(c, x, l, q, seg, g))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_segment_view_empty_segments(impl):
    """Groups with no rows must come out exactly zero (not NaN/garbage),
    and out-of-range ids must drop."""
    m, k, g = 40, 2, 8
    c, x, l, q, _ = _sv_inputs(m, k, g)
    seg = jnp.where(jnp.arange(m) % 2 == 0, 1, 6)  # only groups 1 and 6
    got = ops.segment_view(c, x, l, q, seg, g, degree=2, impl=impl)
    expect = ref.segment_view_ref(c, x, l, q, seg, g, degree=2)
    _assert_view_eq(got, expect)
    empty = [i for i in range(g) if i not in (1, 6)]
    assert np.all(np.asarray(got[0])[empty] == 0.0)
    assert np.all(np.asarray(got[2])[empty] == 0.0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("degree", [1, 2])
def test_segment_view_single_group(impl, degree):
    """num_groups=1 (aggregating an attribute fully out) — the packed
    matrix collapses to the global extended cofactor block."""
    m, k = 63, 3
    c, x, l, q, _ = _sv_inputs(m, k, 4)
    seg = jnp.zeros((m,), jnp.int32)
    got = ops.segment_view(
        c, x, l, q if degree == 2 else None, seg, 1, degree=degree, impl=impl
    )
    expect = ref.segment_view_ref(c, x, l, q, seg, 1, degree=degree)
    _assert_view_eq(got, expect)


def test_segment_view_zero_rows():
    c = jnp.zeros((0,), jnp.float32)
    l = jnp.zeros((0, 2), jnp.float32)
    q = jnp.zeros((0, 2, 2), jnp.float32)
    seg = jnp.zeros((0,), jnp.int32)
    got = ops.segment_view(c, c, l, q, seg, 3, degree=2, impl="xla")
    assert got[0].shape == (3,) and np.all(np.asarray(got[0]) == 0.0)


def test_segment_view_fp64_xla():
    """Under x64 the fused XLA path accumulates in fp64 and matches the
    fp64 oracle bit-for-bit-scale (1e-15 rel), preserving the numpy-oracle
    comparisons the engine's property tests rely on."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        m, k, g = 200, 3, 7
        c = jnp.asarray(rng.standard_normal(m))
        x = jnp.asarray(rng.standard_normal(m))
        l = jnp.asarray(rng.standard_normal((m, k)))
        q = jnp.asarray(rng.standard_normal((m, k, k)))
        seg = jnp.asarray(rng.integers(0, g, m).astype(np.int32))
        assert c.dtype == jnp.float64
        got = ops.segment_view(c, x, l, q, seg, g, degree=2, impl="xla")
        expect = ref.segment_view_ref(c, x, l, q, seg, g, degree=2)
        assert got[0].dtype == jnp.float64
        _assert_view_eq(got, expect, rtol=1e-13, atol=1e-13)


def test_segment_view_rejects_bad_degree():
    c, x, l, q, seg = _sv_inputs(8, 2, 2)
    with pytest.raises(ValueError):
        ops.segment_view(c, x, l, q, seg, 2, degree=3)


@pytest.mark.parametrize("m,g", [(5, 1), (200, 17)])
@pytest.mark.parametrize("degree", [0, 1, 2])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_segment_blocks_sweep(m, g, degree, impl):
    """One multi-block reduce == one scatter per block."""
    k = 3
    c, _, l, q, seg = _sv_inputs(m, k, g)
    got = ops.segment_blocks(
        c,
        l if degree >= 1 else None,
        q if degree == 2 else None,
        seg,
        g,
        degree=degree,
        impl=impl,
    )
    expect = ref.segment_blocks_ref(c, l, q, seg, g, degree=degree)
    _assert_view_eq(got, expect)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_segment_blocks_forced_chunking(impl):
    m, k, g = 91, 2, 9
    c, _, l, q, seg = _sv_inputs(m, k, g)
    chunked = ops.segment_blocks(
        c, l, q, seg, g, degree=2, impl=impl, vmem_budget=80
    )
    one_shot = ops.segment_blocks(c, l, q, seg, g, degree=2, impl=impl)
    _assert_view_eq(chunked, one_shot, rtol=1e-6, atol=1e-6)
    _assert_view_eq(chunked, ref.segment_blocks_ref(c, l, q, seg, g))


def _host_groups(cols, doms):
    """What the host path computes: ``np.unique`` over ``group_key``."""
    key = group_key(cols, doms)
    uniq, first, inv = np.unique(key, return_index=True, return_inverse=True)
    return key, len(uniq), first, inv.astype(np.int32)


def _assert_same_groups(cols, doms):
    """Device grouping of ``cols`` equals the host path bit for bit: same
    ids (groups in ascending tuple order), same stable order, the same
    first occurrences and so the same key values at them."""
    key, num, first, inv = _host_groups(cols, doms)
    seg, dnum, dfirst, order = ops.group_ids_device(cols, doms)
    assert dnum == num
    np.testing.assert_array_equal(np.asarray(seg), inv)
    np.testing.assert_array_equal(
        np.asarray(order), np.argsort(key, kind="stable")
    )
    np.testing.assert_array_equal(dfirst, first)


def test_group_ids_device_matches_np_unique():
    """The device sort-based grouping of one key column is bit-compatible
    with the host np.unique path: same segment ids, same group numbering
    (ascending key order), same first-occurrence gather indices."""
    rng = np.random.default_rng(7)
    for n, dom in [(1, 1), (37, 5), (500, 40), (64, 64)]:
        key = rng.integers(0, dom, n).astype(np.int32)
        uniq, first, inv = np.unique(key, return_index=True, return_inverse=True)
        seg, num, dfirst, order = ops.group_ids_device([key], [dom])
        assert num == len(uniq)
        np.testing.assert_array_equal(np.asarray(seg), inv.astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(order), np.argsort(key, kind="stable")
        )
        np.testing.assert_array_equal(key[dfirst], uniq)
        # ties resolve to identical gather targets: same key values
        np.testing.assert_array_equal(key[dfirst], key[first])


def test_group_ids_device_empty():
    empty = np.zeros((0,), np.int32)
    seg, num, first, order = ops.group_ids_device([empty, empty], [3, 4])
    assert num == 0 and seg.shape == (0,) and first.shape == (0,)
    assert order.shape == (0,)


def test_group_ids_device_exact_for_wide_codes():
    """Keys whose packed code passes int32 (``a`` with 70,000 values and
    ``b`` with 65,536 pack to ``a·65536 + b``) sort as two words: rows
    whose codes differ only above bit 31 (``i`` and ``i + 65536``) stay
    two groups, and the grouping equals ``np.unique`` exactly."""
    n, nb = 70_000, 65_536
    a = np.arange(n, dtype=np.int32)
    b = (a % nb).astype(np.int32)
    assert ops.word_layout([n, nb]) == (1, 1)
    codes = group_key([a, b], [n, nb])
    assert codes[nb] - codes[0] == 2**32  # equal once cut to int32
    rng = np.random.default_rng(11)
    rows = rng.integers(0, n, 3000)
    _assert_same_groups([a[rows], b[rows]], [n, nb])


_I31 = 2**31 - 1


@pytest.mark.parametrize(
    "doms",
    [
        (1,),
        (1, 1, 1),
        (9,),
        (1, 5, 1),
        (3, 4, 5, 6, 7),
        (46_341, 46_340),  # product 2,147,441,940: just below 2³¹−1
        (_I31,),
        (46_341, 46_341),  # product 2,147,488,281: just above
        (2**31,),  # ids up to 2³¹−1, the padding rows' own value
        (2**16, 2**16, 2**16),  # two int64 halves, three words
        (2**20, 7, 2**20, 2**20, 2**20),  # past int64: group_key densifies
        (2**31, 2**31, 2**31, 3),
    ],
)
def test_group_ids_device_equals_group_key(doms):
    """Device grouping over 1-5 key columns equals ``np.unique(group_key(
    cols, doms))`` at every boundary of the word layout: domains of 1,
    radix products at and around 2³¹−1, and products past int64."""
    rng = np.random.default_rng(len(doms) * 131 + int(np.log2(max(doms))))
    # a pool of tuples holding each column's extremes, drawn with repeats
    pool = np.stack(
        [
            np.concatenate([[0, d - 1], rng.integers(0, d, 38)])
            for d in doms
        ],
        axis=1,
    )
    rows = pool[rng.integers(0, len(pool), 700)]
    cols = [rows[:, j].astype(np.int32) for j in range(len(doms))]
    _assert_same_groups(cols, list(doms))


@pytest.mark.parametrize(
    "doms, layout",
    [
        # Favorita's three 10M-row GROUP BYs (date, item_nbr, store_nbr,
        # unit_sales): onpromotion, unit_sales and item_nbr nodes
        ((1684, 4100, 54, 9_000_000), (3, 1)),
        ((1684, 4100, 54), (3,)),
        ((1684, 54), (2,)),
        ((_I31,), (1,)),
        ((2, _I31 // 2), (2,)),
        ((2, _I31 // 2 + 1), (1, 1)),
        ((2**16, 2**16, 2**16), (1, 1, 1)),
        ((0, 1, 5), (3,)),
    ],
)
def test_word_layout(doms, layout):
    assert ops.word_layout(doms) == layout


def test_word_layout_is_greedy_and_fits_int32():
    """Over random domain lists: every word's radix product stays at or
    below 2³¹−1 (a lone wider column aside), no word could take the next
    word's first column, and a key whose product fits 31 bits is one
    word."""
    rng = np.random.default_rng(5)
    for _ in range(300):
        doms = [int(d) for d in 2 ** rng.uniform(0, 24, rng.integers(1, 7))]
        layout = ops.word_layout(doms)
        assert sum(layout) == len(doms)
        words, i = [], 0
        for width in layout:
            words.append(doms[i : i + width])
            i += width
        for w, nxt in zip(words, words[1:] + [None]):
            assert len(w) == 1 or np.prod(w, dtype=object) <= _I31
            if nxt is not None:
                assert np.prod(w + nxt[:1], dtype=object) > _I31
        if np.prod(doms, dtype=object) <= _I31:
            assert len(layout) == 1


@pytest.mark.parametrize("degree", [1, 2])
def test_segment_view_groups_match_rows(degree):
    """G ≈ N — a fact-table leaf, nearly every row its own group — through
    the Pallas kernel in interpret mode, with the smallest tiles so the
    staircase walks many (row block, group tile) visits; with and without
    the caller's sort order it equals ``segment_view_ref``."""
    m, k, g = 3000, 2, 2900
    c, x, l, q, _ = _sv_inputs(m, k, g)
    rng = np.random.default_rng(5)
    seg = jnp.asarray(
        rng.permutation(
            np.concatenate([np.arange(g), rng.integers(0, g, m - g)])
        ).astype(np.int32)
    )
    q = q if degree == 2 else None
    expect = ref.segment_view_ref(c, x, l, q, seg, g, degree=degree)
    for order in (None, jnp.argsort(seg, stable=True)):
        got = ops.segment_view(
            c, x, l, q, seg, g, degree=degree, impl="pallas",
            vmem_budget=1, order=order,
        )
        _assert_view_eq(got, expect)

