"""Fused vs unfused traversal-node throughput, roofline-audited.

Two legs.  ``traversal_nodes`` isolates the engine's per-node hot path —
extend-with-feature + GROUP BY over an [N]-row degree-2 view — and times
the fused ``segment_view`` dispatch (``FactorizedEngine._extend_and_group``)
against the unfused pair (``_extend_with_feature`` + ``_aggregate_out``)
on identical inputs, reporting ``node_fusion_speedup`` (compare.py-gated)
plus the roofline accounting from ``launch.roofline.traversal_node_terms``:
predicted bandwidth-bound speedup, achieved GB/s, and — on a chip with
published peaks only — the achieved fraction of the memory bound.
``traversal_end_to_end`` times whole ``cofactors()``
traversals over the paper's Figure-1 schema at scale with the node kernels
on vs off.

On this CPU container the fused path is the jitted XLA formulation of the
same one-dispatch fusion (Pallas interpret timing is Python-level and
meaningless off-TPU; kernel correctness is covered by tests/test_kernels).
The unfused baseline already includes the ``jax.ops.segment_sum`` fallback
upgrade, so the speedup is fusion, not a strawman.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.factorize import FactorizedEngine, _View
from repro.core.relation import Relation
from repro.core.store import Store
from repro.core.variable_order import VariableOrder
from repro.data.synthetic import figure1_schema
from repro.launch.mesh import HW
from repro.launch.roofline import traversal_node_terms

from .common import emit, timeit

NODE_SHAPES = (  # (n_rows, k feats below, groups) — the degree-2 hot path
    (65536, 4, 256),
    (262144, 4, 1024),
    (262144, 8, 1024),
    (524288, 8, 2048),
)


def _node_fixture(n: int, k: int, g: int, seed: int = 0):
    """A store whose fact relation has ``n`` rows grouped into ``g`` keys,
    plus a synthetic degree-2 view with ``k`` features already below the
    node — the state the engine is in when it reaches a feature node."""
    rng = np.random.default_rng(seed)
    gids = rng.integers(0, g, n).astype(np.int32)
    rel = Relation.from_columns(
        "R", {"g": gids}, {"x": rng.standard_normal(n)}
    )
    store = Store([rel])
    vorder = VariableOrder.intercept(
        [
            VariableOrder(
                "g", [VariableOrder("x", [VariableOrder.leaf("R")])]
            )
        ]
    )
    kw = dict(backend="jax", use_view_cache=False)
    eng_u = FactorizedEngine(store, vorder, ["x"], use_node_kernels=False, **kw)
    eng_f = FactorizedEngine(store, vorder, ["x"], use_node_kernels=True, **kw)
    view = _View(
        keys={"g": gids, "x": eng_u.encoded[("R", "x")]},
        c=jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        l=jnp.asarray(rng.standard_normal((n, k)).astype(np.float32)),
        q=jnp.asarray(rng.standard_normal((n, k, k)).astype(np.float32)),
        feats=[f"z{i}" for i in range(k)],
        degree=2,
    )
    return eng_u, eng_f, view


def run_nodes(shapes=NODE_SHAPES, repeats: int = 5) -> list:
    rows = []
    for n, k, g in shapes:
        eng_u, eng_f, view = _node_fixture(n, k, g)

        def unfused():
            v = eng_u._aggregate_out(
                eng_u._extend_with_feature(view, "x", 2),
                "x",
                frozenset(),
                2,
            )
            return (v.c, v.l, v.q)

        def fused():
            v = eng_f._extend_and_group(view, "x", frozenset(), 2)
            return (v.c, v.l, v.q)

        for a, b in zip(unfused(), fused()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4
            )
        t_u = timeit(unfused, repeats=repeats)
        t_f = timeit(fused, repeats=repeats)
        terms = traversal_node_terms(n, k, g, degree=2, dtype_bytes=4)
        rows.append(
            {
                "n_rows": n,
                "k": k,
                "groups": g,
                "unfused_s": t_u,
                "fused_s": t_f,
                "node_fusion_speedup": t_u / t_f,
                "predicted_speedup": terms.predicted_speedup,
                "device_kind": terms.device_kind,
                "achieved_gbs": terms.achieved_gbs(t_f),
                # a share of the HBM bound exists only on a chip with
                # published peaks; anywhere else it was not measured
                "bw_bound_fraction": (
                    terms.achieved_fraction(t_f)
                    if terms.device_kind in HW
                    else None
                ),
            }
        )
    emit("traversal_nodes", rows)
    return rows


def run_end_to_end(
    scales=((20, 20, 20, 10), (50, 40, 30, 20)), repeats: int = 3
) -> list:
    """Whole-traversal cofactors over Figure 1 at scale, kernels on/off."""
    rows = []
    for n_loc, n_prod, n_sales, n_comp in scales:
        bundle = figure1_schema(
            n_locations=n_loc,
            n_products_per_loc=n_prod,
            n_sales_per_product=n_sales,
            n_competitors_per_loc=n_comp,
        )
        feats = bundle.features + [bundle.label]
        kw = dict(backend="jax", use_view_cache=False)
        eng_u = FactorizedEngine(
            bundle.store, bundle.vorder, feats, use_node_kernels=False, **kw
        )
        eng_f = FactorizedEngine(
            bundle.store, bundle.vorder, feats, use_node_kernels=True, **kw
        )
        a, b = eng_u.cofactors(), eng_f.cofactors()
        np.testing.assert_allclose(a.quad, b.quad, rtol=1e-5, atol=1e-4)
        assert eng_u.node_visits == eng_f.node_visits
        t_u = timeit(eng_u.cofactors, repeats=repeats)
        t_f = timeit(eng_f.cofactors, repeats=repeats)
        rows.append(
            {
                "sales_rows": n_loc * n_prod * n_sales,
                "unfused_s": t_u,
                "fused_s": t_f,
                "traversal_speedup": t_u / t_f,
            }
        )
    emit("traversal_end_to_end", rows)
    return rows


def main(smoke: bool = False) -> None:
    if smoke:
        run_nodes(shapes=((8192, 4, 64),), repeats=3)
        run_end_to_end(scales=((8, 6, 5, 4),), repeats=2)
    else:
        run_nodes()
        run_end_to_end()


if __name__ == "__main__":
    main()
