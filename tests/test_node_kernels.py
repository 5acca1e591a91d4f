"""Fused traversal-node kernels wired into the engine.

The acceptance contract of ``FactorizedEngine(use_node_kernels=...)``
(ISSUE 10): the fused ``segment_view`` / ``segment_blocks`` /
device-grouping paths are drop-in — fused ≡ unfused cofactors at 1e-12
over random acyclic schemas, ``passes``/``node_visits`` counters unchanged,
grouped key layouts byte-identical — plus the two satellite fixes:
``_segment_sum``'s ``jax.ops.segment_sum`` fallback equivalence and the
``_merge_views``/``_group_rows`` canonical sorted-key layout surviving
delta folds after multi-key appends.
"""

import numpy as np
import pytest

from repro.core import VERSIONS, linear_regression
from repro.core.categorical import cat_cofactors_factorized
from repro.core.factorize import (
    AggregateQuery,
    FactorizedEngine,
    cofactors_factorized,
)
from repro.core.regression import RegressionConfig
from repro.core.relation import Relation
from repro.core.store import Store
from repro.data.synthetic import (
    figure1_schema,
    many_cat_schema,
    random_acyclic_schema,
)

CONT = ["x", "y"]


def _pair(bundle, **kw):
    """Fused + unfused engines over the same bundle (cache off so both
    actually traverse)."""
    cols = bundle.features + [bundle.label]
    mk = dict(backend="jax", use_view_cache=False, **kw)
    return (
        FactorizedEngine(
            bundle.store, bundle.vorder, cols, use_node_kernels=False, **mk
        ),
        FactorizedEngine(
            bundle.store, bundle.vorder, cols, use_node_kernels=True, **mk
        ),
    )


def _assert_cof_close(a, b, atol=1e-10):
    np.testing.assert_allclose(
        np.asarray(a.matrix()), np.asarray(b.matrix()), rtol=1e-12, atol=atol
    )


# ---------------------------------------------------------------------------
# fused ≡ unfused over random schemas, counters unchanged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 7, 13, 42])
def test_fused_matches_unfused_random_schema(seed):
    bundle = random_acyclic_schema(seed, n_branches=2, max_fanout=4,
                                   max_rows=12)
    eng_u, eng_f = _pair(bundle)
    cof_u, cof_f = eng_u.cofactors(), eng_f.cofactors()
    _assert_cof_close(cof_u, cof_f)
    # identical traversal structure: fusion changes dispatches, not visits
    assert eng_u.passes == eng_f.passes
    assert eng_u.node_visits == eng_f.node_visits


def test_fused_matches_numpy_oracle():
    bundle = figure1_schema()
    cols = bundle.features + [bundle.label]
    oracle = cofactors_factorized(
        bundle.store, bundle.vorder, cols, backend="numpy",
        use_view_cache=False,
    )
    fused = cofactors_factorized(
        bundle.store, bundle.vorder, cols, backend="jax",
        use_node_kernels=True, use_view_cache=False,
    )
    # jax runs fp32; oracle fp64
    np.testing.assert_allclose(
        np.asarray(fused.matrix()), oracle.matrix(), rtol=5e-4, atol=1e-3
    )


def test_fused_grouped_keys_byte_identical():
    """GROUP BY queries: fused grouping must produce the SAME group rows
    in the SAME order — key arrays byte-identical, blocks at 1e-12."""
    b = many_cat_schema(n_cat=3, domain=8, n_rows=500, seed=3)
    queries = [
        AggregateQuery("base", (), 2),
        AggregateQuery("g1", ("c0",), 1),
        AggregateQuery("g2", ("c1", "c2"), 1),
    ]
    eng_u, eng_f = _pair(
        type("B", (), {
            "store": b.store, "vorder": b.vorder,
            "features": CONT[:1], "label": CONT[1],
        })()
    )
    out_u = eng_u.run_batch(queries)
    out_f = eng_f.run_batch(queries)
    for name in ("base", "g1", "g2"):
        bu, bf = out_u[name], out_f[name]
        assert list(bu.keys) == list(bf.keys)
        for a in bu.keys:
            np.testing.assert_array_equal(bu.keys[a], bf.keys[a])
        np.testing.assert_allclose(
            np.asarray(bu.count), np.asarray(bf.count),
            rtol=1e-12, atol=1e-8,
        )
        if bu.lin is not None:
            np.testing.assert_allclose(
                np.asarray(bu.lin), np.asarray(bf.lin),
                rtol=1e-12, atol=1e-8,
            )


def test_fused_device_grouping_matches_host():
    """Force the device sort-based grouping path (gated off on CPU by
    default) — ids, group order, and results must match the host path."""
    b = many_cat_schema(n_cat=2, domain=16, n_rows=600, seed=5)
    cols = CONT
    kw = dict(backend="jax", use_view_cache=False)
    eng_host = FactorizedEngine(
        b.store, b.vorder, cols, use_node_kernels=True, **kw
    )
    assert not eng_host.device_grouping  # CPU container default
    eng_dev = FactorizedEngine(
        b.store, b.vorder, cols, use_node_kernels=True, **kw
    )
    eng_dev.device_grouping = True
    out_h = eng_host.run_batch([AggregateQuery("g", ("c0", "c1"), 2)])["g"]
    out_d = eng_dev.run_batch([AggregateQuery("g", ("c0", "c1"), 2)])["g"]
    for a in out_h.keys:
        np.testing.assert_array_equal(out_h.keys[a], out_d.keys[a])
    np.testing.assert_allclose(
        np.asarray(out_h.quad), np.asarray(out_d.quad), rtol=1e-6, atol=1e-5
    )


def test_device_grouping_exact_for_wide_group_codes():
    """Forced device grouping over views whose GROUP BY codes pass 2³²:
    keys ``a`` (70,000 values) and ``b`` (65,536 values) pack to
    ``a·65536 + b``, so rows ``i`` and ``i + 65536`` share ``b`` and their
    codes differ by exactly 2³² — one group if the codes were cut to
    int32.  ``a`` is a feature above that GROUP BY, so a merge would drop
    a row's ``a`` value; the fused engine must equal the fp64 oracle."""
    from repro.core.relation import group_key
    from repro.core.variable_order import VariableOrder

    n, nb = 70_000, 65_536
    rel = Relation.from_columns(
        "R",
        {"a": np.arange(n), "b": np.arange(n) % nb},
        {"x": np.random.default_rng(3).standard_normal(n)},
        {"a": n, "b": nb},
    )
    store = Store([rel])
    leaf = VariableOrder("x", [VariableOrder.leaf("R")])
    vorder = VariableOrder.intercept(
        [VariableOrder("a", [VariableOrder("b", [leaf])])]
    )
    codes = group_key([rel.keys["a"], rel.keys["b"]], [n, nb])
    assert codes[nb] - codes[0] == 2**32  # equal once cut to int32
    fused = FactorizedEngine(
        store, vorder, ["a", "x"], backend="jax", use_view_cache=False
    )
    fused.device_grouping = True
    oracle = FactorizedEngine(store, vorder, ["a", "x"], backend="numpy")
    np.testing.assert_allclose(
        fused.cofactors().matrix(), oracle.cofactors().matrix(),
        rtol=1e-4,  # f32 sums of a² reach 1e14; a merge errs by ~10%
    )


def test_default_on_for_jax_backend_only():
    b = figure1_schema()
    cols = b.features + [b.label]
    assert FactorizedEngine(b.store, b.vorder, cols,
                            backend="jax").use_node_kernels
    assert not FactorizedEngine(b.store, b.vorder, cols,
                                backend="numpy").use_node_kernels
    # explicit request on numpy backend is ignored (kernels are jnp-only)
    assert not FactorizedEngine(
        b.store, b.vorder, cols, backend="numpy", use_node_kernels=True
    ).use_node_kernels


def test_regression_config_plumbing():
    """use_node_kernels threads linear_regression → engine; theta parity."""
    import dataclasses

    b = figure1_schema()
    res_u = linear_regression(
        b.store, b.vorder, b.features, b.label,
        dataclasses.replace(VERSIONS["closed"], use_node_kernels=False),
    )
    res_f = linear_regression(
        b.store, b.vorder, b.features, b.label,
        dataclasses.replace(VERSIONS["closed"], use_node_kernels=True),
    )
    np.testing.assert_allclose(res_f.theta, res_u.theta, rtol=1e-5,
                               atol=1e-6)


def test_fused_categorical_matches_unfused():
    b = many_cat_schema(n_cat=3, domain=8, n_rows=400, seed=9)
    cat = [f"c{i}" for i in range(3)]
    kw = dict(use_view_cache=False)
    cu = cat_cofactors_factorized(
        b.store, b.vorder, CONT, cat, use_node_kernels=False, **kw
    )
    cf = cat_cofactors_factorized(
        b.store, b.vorder, CONT, cat, use_node_kernels=True, **kw
    )
    np.testing.assert_allclose(
        np.asarray(cf.matrix()), np.asarray(cu.matrix()),
        rtol=1e-12, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# satellite 1: jax.ops.segment_sum fallback equivalence
# ---------------------------------------------------------------------------

def test_segment_sum_fallback_equivalence():
    """The jax-backend `_segment_sum` (now jax.ops.segment_sum) ≡ the
    numpy np.add.at path, for every block rank the traversal produces."""
    b = figure1_schema()
    cols = b.features + [b.label]
    eng_j = FactorizedEngine(b.store, b.vorder, cols, backend="jax",
                             use_node_kernels=False, use_view_cache=False)
    eng_n = FactorizedEngine(b.store, b.vorder, cols, backend="numpy",
                             use_view_cache=False)
    rng = np.random.default_rng(0)
    n, g = 257, 9
    seg = rng.integers(0, g, n).astype(np.int32)
    for shape in [(n,), (n, 4), (n, 3, 3)]:
        data = rng.standard_normal(shape).astype(np.float32)
        out_j = np.asarray(eng_j._segment_sum(data, seg, g))
        out_n = eng_n._segment_sum(data, seg, g)
        np.testing.assert_allclose(out_j, out_n, rtol=1e-6, atol=1e-5)


# ---------------------------------------------------------------------------
# satellite 2: canonical key order survives delta folds
# ---------------------------------------------------------------------------

def _two_branch_bundle(n_rows=300, seed=11):
    """A schema whose ROOT view is multi-keyed via two intercept children —
    the shape where first-seen (join) key order used to diverge from
    _merge_views' sorted regroup order."""
    return many_cat_schema(n_cat=3, domain=6, n_rows=n_rows, seed=seed)


def test_cached_views_sorted_key_layout():
    """Every persisted multi-key view uses the canonical sorted-key
    layout, before AND after a delta fold."""
    b = _two_branch_bundle()
    cat = ["c0", "c1", "c2"]
    cat_cofactors_factorized(b.store, b.vorder, CONT, cat)

    def assert_canonical():
        seen_multi = 0
        for _key, entry in b.store.view_cache.items():
            keys = list(entry.view.keys)
            assert keys == sorted(keys), keys
            seen_multi += len(keys) > 1
        return seen_multi

    assert assert_canonical() > 0  # the fixture does cache multi-key views

    rng = np.random.default_rng(1)
    fact = b.store.get("Fact")
    keys = {a: rng.integers(0, int(fact.domains[a]), 40).astype(np.int32)
            for a in fact.keys}
    values = {a: rng.normal(0, 2.0, 40) for a in fact.values}
    b.store.append("Fact", Relation.from_columns("delta", keys, values))
    b.store.flush()
    assert assert_canonical() > 0


def test_delta_fold_preserves_layout_after_multikey_append():
    """Regression for the _merge_views/_group_rows key-order asymmetry:
    a delta fold after an append touching a multi-key relation must leave
    cached views in the same layout a fresh compute produces — same key
    dict order, same group rows, values at 1e-12."""
    b = _two_branch_bundle()
    cat = ["c0", "c1", "c2"]
    warm = cat_cofactors_factorized(b.store, b.vorder, CONT, cat)
    rng = np.random.default_rng(2)
    fact = b.store.get("Fact")
    keys = {a: rng.integers(0, int(fact.domains[a]), 60).astype(np.int32)
            for a in fact.keys}
    values = {a: rng.normal(0, 2.0, 60) for a in fact.values}
    b.store.append("Fact", Relation.from_columns("delta", keys, values))

    stats = {}
    folded = cat_cofactors_factorized(b.store, b.vorder, CONT, cat,
                                      stats=stats)
    fresh = cat_cofactors_factorized(b.store, b.vorder, CONT, cat,
                                     use_view_cache=False)
    assert stats["node_visits"] == 0  # served from folded cache entries
    np.testing.assert_allclose(
        np.asarray(folded.matrix()), np.asarray(fresh.matrix()),
        rtol=1e-12, atol=1e-6,
    )
    assert warm.matrix().shape == fresh.matrix().shape


# ---------------------------------------------------------------------------
# property test: fused ≡ unfused over random acyclic schemas
# ---------------------------------------------------------------------------

try:  # property tests ride along only where hypothesis is installed;
    # the deterministic seeds above stay unconditional
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover
    settings = None

if settings is not None:
    SET = settings(
        max_examples=25,
        deadline=None,
        derandomize=True,
        suppress_health_check=[HealthCheck.too_slow],
    )

    schema_params = st.builds(
        random_acyclic_schema,
        seed=st.integers(0, 10_000),
        n_branches=st.integers(1, 3),
        max_fanout=st.integers(1, 5),
        max_rows=st.integers(1, 15),
    )

    @SET
    @given(bundle=schema_params)
    def test_fused_equals_unfused_property(bundle):
        eng_u, eng_f = _pair(bundle)
        cof_u, cof_f = eng_u.cofactors(), eng_f.cofactors()
        _assert_cof_close(cof_u, cof_f)
        assert eng_u.node_visits == eng_f.node_visits

    @SET
    @given(bundle=schema_params)
    def test_fused_device_grouping_property(bundle):
        eng_u, eng_f = _pair(bundle)
        eng_f.device_grouping = True
        _assert_cof_close(eng_u.cofactors(), eng_f.cofactors())
