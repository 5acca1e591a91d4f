#!/usr/bin/env python3
"""Smoke run of the factorized-learning path on a TPU, at Favorita scale.

    python3 chip_smoke.py              # one chip: data, fit, categorical, service
    python3 chip_smoke.py --chips 4    # four chips: the sharded cofactors only

Drives the system through the entry points a user calls — ``Store`` →
``FactorizedEngine`` with the fused Pallas node kernels →
``linear_regression`` and ``FactorizedService`` — on a ``favorita_like``
star schema at Favorita's published key cardinalities, and checks every
result against a plain fp64 reference built on the host from the
materialized join.  Everything runs in this one process; no process is
started.  Each phase prints its lines; any failure raises and the exit code
is non-zero.  The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU the script exits non-zero before any phase and prints no
result.

Tolerances.  Device sums accumulate in f32 with ``Precision.HIGHEST``
dots.  The largest f32 error is the top of the traversal, where 1,684
dates fold into one row: about 1e-6 of an entry's Cauchy-Schwarz scale
sqrt(C_ii·C_jj), and about as much on the fitted values relative to the
label's spread.  The limits (1e-5) leave tenfold headroom over that.  A
single bf16 pass rounds each folded value to 8 bits (2⁻⁹ ≈ 2e-3), which
over those 1,684 dates leaves roughly 2e-3/√1684 ≈ 5e-5: the fit phase
reruns the traversal with the node kernels' dots in one bf16 pass
(``segment_view.BF16_PASS``) and requires that run to miss the limits.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Favorita (Kaggle "Corporación Favorita Grocery Sales Forecasting"):
# train.csv holds 125,497,040 sales rows over these published cardinalities.
FAVORITA_ROWS = 125_497_040
N_DATES, N_STORES, N_ITEMS = 1684, 54, 4100

COF_TOL = 1e-5  # max |C - R|_ij / sqrt(R_ii R_jj)
PRED_TOL = 1e-5  # ||Z (θ - θ_ref)|| / ||y - ȳ||: fitted values
SERVICE_TRAINS = (  # (tenant, features): overlapping subsets
    ("tenant-a", ("date", "store_nbr", "onpromotion")),
    ("tenant-a", ("date", "item_nbr", "onpromotion")),
    ("tenant-b", ("store_nbr", "item_nbr", "onpromotion")),
    ("tenant-b", ("date", "store_nbr", "item_nbr", "onpromotion")),
)


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# -- checks -------------------------------------------------------------------


def cof_error(c, r) -> float:
    """Largest entry error of a Gram matrix, each entry measured against
    its Cauchy-Schwarz scale sqrt(R_ii·R_jj)."""
    import numpy as np

    c, r = np.asarray(c, np.float64), np.asarray(r, np.float64)
    d = np.sqrt(np.maximum(np.diag(r), 1e-300))
    return float(np.max(np.abs(c - r) / np.outer(d, d)))


def pred_error(theta, theta_ref, r) -> float:
    """How far the fitted values Z·θ lie from Z·θ_ref over the reference
    data, relative to the label's spread: ||Z (θ - θ_ref)|| / ||y - ȳ||,
    from the reference Gram ``r`` ([intercept, features, label]) alone.
    Unlike θ itself it is blind to directions the data cannot tell apart
    (e.g. intercept vs. a shift of every one-hot coefficient), which only
    the ridge pins."""
    import numpy as np

    t = slice(0, r.shape[0] - 1)  # trainable: all but the label's -1
    d = (np.asarray(theta) - np.asarray(theta_ref))[t]
    spread = r[-1, -1] - r[0, -1] ** 2 / r[0, 0]
    return float(np.sqrt(d @ r[t, t] @ d / spread))


def ridge_solve(r, ridge: float):
    """Plain fp64 normal equations with θ_label = -1 pinned."""
    import numpy as np

    p = r.shape[0]
    a = r[: p - 1, : p - 1] + ridge * np.eye(p - 1)
    return np.concatenate([np.linalg.solve(a, r[: p - 1, p - 1]), [-1.0]])


def gram64(z_of, n: int, chunk: int = 1 << 20):
    """Σ zᵀz in fp64 over row chunks ``z_of(lo, hi)`` (bounded memory)."""
    acc = None
    for lo in range(0, n, chunk):
        z = z_of(lo, min(n, lo + chunk))
        g = z.T @ z
        acc = g if acc is None else acc + g
    return acc


def check(name: str, err: float, tol: float, bf16_err=None) -> None:
    """``err`` within ``tol``; a bf16 run's error, where given, outside."""
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3e} exceeds {tol:.0e}")
    if bf16_err is not None and not bf16_err > tol:
        raise AssertionError(
            f"{name}: a bf16 pass gives {bf16_err:.3e}, inside the {tol:.0e} "
            "limit — the limit would not catch bf16 accumulation"
        )


def design(joined, cols, factors):
    """[1 | cols] of the materialized join, scaled when ``factors`` is
    given, fp64."""
    import numpy as np

    from repro.core.cofactor import design_matrix

    x = design_matrix(joined, cols, scale=factors)
    return np.concatenate([np.ones((x.shape[0], 1)), x], axis=1)


# -- the kernel probe ---------------------------------------------------------


class KernelProbe:
    """Counts the engine's node-kernel and device-grouping dispatches while
    active, and keeps the arguments of its largest fused-node call so the
    program that call ran can be compiled again and inspected."""

    NAMES = ("segment_view", "segment_blocks", "group_ids_device")

    def __init__(self):
        from repro.kernels import ops

        self.ops = ops
        self.calls = collections.Counter()
        self.largest = None
        self._orig = {n: getattr(ops, n) for n in self.NAMES}

    def _wrap(self, name):
        fn = self._orig[name]

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            self.calls[name] += 1
            if name == "segment_view" and (
                self.largest is None
                or args[0].shape[0] > self.largest[0][0].shape[0]
            ):
                self.largest = (args, kw)
            return fn(*args, **kw)

        return wrapped

    def __enter__(self):
        for name in self.NAMES:
            setattr(self.ops, name, self._wrap(name))
        return self

    def __exit__(self, *exc):
        for name, fn in self._orig.items():
            setattr(self.ops, name, fn)
        return False

    def node_program_text(self) -> str:
        """Optimized HLO of the largest fused node, compiled as it ran."""
        import jax

        (c, x, l, q, seg, num), kw = self.largest
        degree, order = kw["degree"], kw.get("order")
        sv = self._orig["segment_view"]

        def node(c, x, l, q, seg, order):
            return sv(c, x, l, q, seg, num, degree=degree, order=order)

        return jax.jit(node).lower(c, x, l, q, seg, order).compile().as_text()


def require_compiled_kernels(probe: KernelProbe) -> int:
    """The node kernels ran as compiled Pallas: dispatched at all, through
    the ``pallas`` implementation, with a Mosaic custom call in the node
    program.  Returns the size of the largest node."""
    ops = probe.ops
    if ops.default_impl() != "pallas" or not ops.on_tpu():
        raise AssertionError("node kernels would not run as compiled Pallas")
    for name in ("segment_view", "group_ids_device"):
        if not probe.calls[name]:
            raise AssertionError(f"the traversal dispatched no {name}")
    if "tpu_custom_call" not in probe.node_program_text():
        raise AssertionError("the fused node program holds no tpu_custom_call")
    return int(probe.largest[0][0].shape[0])


@contextlib.contextmanager
def bf16_node_kernels():
    """The node kernels with their one-hot dots in a single bf16 pass (bf16
    operands, f32 accumulation) for as long as the block runs."""
    from repro.kernels import segment_view as sv

    calls = (sv.segment_view_kernel_call, sv.segment_reduce_kernel_call)
    sv.BF16_PASS = True
    for f in calls:
        f.clear_cache()  # retrace with the flag read at trace time
    try:
        yield
    finally:
        sv.BF16_PASS = False
        for f in calls:
            f.clear_cache()


# -- phases -------------------------------------------------------------------


def phase_device(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(
        "device",
        jax=jax.__version__,
        platform=d.platform,
        kind=repr(d.device_kind),
        count=len(devs),
    )
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX sees {d.platform}); nothing run"
        )
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but {len(devs)} found")
    return devs


def phase_data(rows: int, seed: int):
    from repro.data.synthetic import favorita_like

    log(
        "data",
        cut=f"{rows} of Favorita's {FAVORITA_ROWS} sales rows",
        why="the fp64 reference join and one-hot design are built on the "
        "host, and degree-2 fact-leaf views are [N,k,k] f32 on the device; "
        "key cardinalities are the published ones",
    )
    t = time.perf_counter()
    total = N_DATES * N_STORES * N_ITEMS
    bundle = favorita_like(
        n_dates=N_DATES,
        n_stores=N_STORES,
        n_items=N_ITEMS,
        sales_fraction=(rows + 0.5) / total,
        seed=seed,
    )
    store = bundle.store
    n = store.get("SalesF").num_rows
    if n != rows:
        raise AssertionError(f"generated {n} sales rows, asked for {rows}")
    gen = time.perf_counter() - t
    t = time.perf_counter()
    joined = store.materialize_join()
    log(
        "data",
        sales_rows=n,
        dates=store.attr_domain("date"),
        stores=store.attr_domain("store_nbr"),
        items=store.attr_domain("item_nbr"),
        transactions_rows=store.get("Transactions").num_rows,
        join_rows=joined.num_rows,
        generate_s=f"{gen:.2f}",
        join_s=f"{time.perf_counter() - t:.2f}",
    )
    if joined.num_rows != n:
        raise AssertionError("every sale joins exactly one row per dimension")
    return bundle, joined


def phase_fit(bundle, joined) -> None:
    """Continuous fit: cofactors through the engine, then closed-form and
    BGD regressions, all against the fp64 reference; then the same
    traversal with bf16 node kernels, which must miss the limits."""
    import numpy as np

    from repro.core.factorize import FactorizedEngine
    from repro.core.gd import bgd_cofactor
    from repro.core.regression import VERSIONS, linear_regression
    from repro.core.scaling import compute_scale_factors

    store, vorder = bundle.store, bundle.vorder
    feats, label = bundle.features, bundle.label
    cols = feats + [label]
    factors = compute_scale_factors(store, feats, label)
    ridge = VERSIONS["closed"].ridge

    t = time.perf_counter()
    z = design(joined, cols, factors)
    ref = z.T @ z
    del z
    ref_s = time.perf_counter() - t
    want = {  # θ (scaled units) each version must reach from ``ref``
        "closed": ridge_solve(ref, ridge),
        "v1": bgd_cofactor(ref, VERSIONS["v1"].gd()).theta,  # same BGD
    }

    def engine():
        return FactorizedEngine(store, vorder, cols, backend="jax", scale=factors)

    t = time.perf_counter()
    with KernelProbe() as probe:
        eng = engine()
        if not (eng.use_node_kernels and eng.device_grouping):
            raise AssertionError("engine runs without node kernels on device")
        cof = eng.cofactors().matrix()
    cof_s = time.perf_counter() - t
    largest = require_compiled_kernels(probe)
    t = time.perf_counter()
    with bf16_node_kernels():
        cof_bf = engine().cofactors().matrix()
    bf_s = time.perf_counter() - t
    err, err_bf = cof_error(cof, ref), cof_error(cof_bf, ref)
    check("cofactors", err, COF_TOL, err_bf)
    log(
        "fit",
        step="cofactors",
        seconds=f"{cof_s:.2f}",
        node_kernels=dict(probe.calls),
        largest_node_rows=largest,
        tpu_custom_call=True,
        cof_err=f"{err:.3e}",
        bf16_cof_err=f"{err_bf:.3e}",
        tol=f"{COF_TOL:.0e}",
        bf16_run_s=f"{bf_s:.2f}",
        reference_s=f"{ref_s:.2f}",
        peak_bytes_in_use=peak_bytes(),
    )

    for version in ("closed", "v1"):
        cfg = VERSIONS[version]
        t = time.perf_counter()
        with KernelProbe() as probe:
            res = linear_regression(store, vorder, feats, label, cfg)
        secs = time.perf_counter() - t
        if not probe.calls["segment_view"]:
            raise AssertionError(f"{version}: no fused node ran")
        if version == "closed":
            theta_bf = ridge_solve(cof_bf, cfg.ridge)
        else:
            theta_bf = bgd_cofactor(cof_bf, cfg.gd()).theta
        err = pred_error(res.theta_conv, want[version], ref)
        err_bf = pred_error(theta_bf, want[version], ref)
        check(f"fit {version}", err, PRED_TOL, err_bf)
        rel = np.max(np.abs(res.theta_conv - want[version]))
        log(
            "fit",
            step=version,
            seconds=f"{secs:.2f}",
            cofactor_s=f"{res.seconds_cofactor:.2f}",
            solve_s=f"{res.seconds_gd:.2f}",
            iterations=res.iterations,
            theta=np.array2string(
                res.theta, precision=6, separator=",", max_line_width=10**4
            ),
            pred_err=f"{err:.3e}",
            bf16_pred_err=f"{err_bf:.3e}",
            theta_rel_err=f"{rel / np.max(np.abs(want[version])):.3e}",
            tol=f"{PRED_TOL:.0e}",
            peak_bytes_in_use=peak_bytes(),
        )


def phase_categorical(bundle, joined) -> None:
    """store_nbr as a 54-way categorical: sparse grouped cofactors against
    the dense one-hot design matrix."""
    import numpy as np

    from repro.core.categorical import onehot_design_matrix
    from repro.core.regression import VERSIONS, linear_regression

    store, vorder = bundle.store, bundle.vorder
    feats, label = bundle.features, bundle.label
    cat = ("store_nbr",)
    cont = [f for f in feats if f not in cat]
    doms = {c: store.attr_domain(c) for c in cat}
    cfg = dataclasses.replace(VERSIONS["closed"], categorical=cat)

    t = time.perf_counter()
    with KernelProbe() as probe:
        res = linear_regression(store, vorder, feats, label, cfg)
    secs = time.perf_counter() - t
    if not probe.calls["segment_view"]:
        raise AssertionError("categorical: no fused node ran")

    t = time.perf_counter()
    y = joined.column(label).astype(np.float64)

    def rows(lo, hi):
        x, _ = onehot_design_matrix(
            joined.select(np.arange(lo, hi)), cont, list(cat), doms
        )
        return np.concatenate([np.ones((hi - lo, 1)), x, y[lo:hi, None]], axis=1)

    ref = gram64(rows, joined.num_rows)
    ref_s = time.perf_counter() - t
    err = pred_error(res.theta, ridge_solve(ref, cfg.ridge), ref)
    check("categorical", err, PRED_TOL)
    log(
        "categorical",
        categories=doms["store_nbr"],
        theta_len=len(res.theta),
        seconds=f"{secs:.2f}",
        node_kernels=dict(probe.calls),
        pred_err=f"{err:.3e}",
        tol=f"{PRED_TOL:.0e}",
        reference_s=f"{ref_s:.2f}",
        peak_bytes_in_use=peak_bytes(),
    )


def new_sales(store, fraction: float, seed: int):
    """About ``fraction`` more SalesF rows at (date, store, item) keys not
    yet sold, drawn like ``favorita_like`` draws its facts."""
    import numpy as np

    from repro.core.relation import Relation

    rng = np.random.default_rng(seed)
    sales = store.get("SalesF")
    have = (
        sales.keys["date"].astype(np.int64) * N_STORES
        + sales.keys["store_nbr"]
    ) * N_ITEMS + sales.keys["item_nbr"]
    want = int(sales.num_rows * fraction)
    flat = np.unique(rng.integers(0, N_DATES * N_STORES * N_ITEMS, 2 * want))
    flat = rng.permutation(flat[~np.isin(flat, have)])[:want]
    date = (flat // (N_STORES * N_ITEMS)).astype(np.int32)
    shop = (flat // N_ITEMS % N_STORES).astype(np.int32)
    item = (flat % N_ITEMS).astype(np.int32)
    stores, items = store.get("Stores"), store.get("Items")
    cluster = np.zeros(N_STORES)
    cluster[stores.keys["store_nbr"]] = stores.values["cluster"]
    perishable = np.zeros(N_ITEMS)
    perishable[items.keys["item_nbr"]] = items.values["perishable"]
    promo = rng.integers(0, 2, size=len(flat)).astype(np.float64)
    unit_sales = (
        5.0 + 0.05 * date + 2.0 * cluster[shop] + 3.0 * perishable[item]
        + 4.0 * promo + rng.normal(0, 1.0, size=len(flat))
    )
    return Relation.from_columns(
        "SalesF",
        {"date": date, "store_nbr": shop, "item_nbr": item},
        {"unit_sales": unit_sales, "onpromotion": promo},
        {"date": N_DATES, "store_nbr": N_STORES, "item_nbr": N_ITEMS},
    )


def phase_service(bundle, joined, seed: int) -> None:
    """Two tenants train over overlapping feature subsets through the
    threaded service on the device, an append of ~1% more sales lands,
    they train again and read the cofactors; every θ is checked against a
    closed-form fp64 fit of the same catalog state."""
    import numpy as np

    from repro.core.scaling import compute_scale_factors
    from repro.serve.factorized import FactorizedService

    store, vorder = bundle.store, bundle.vorder
    feats, label = bundle.features, bundle.label
    cols = feats + [label]

    def reference(joined):
        """Per subset: the Gram of the ridge problem the service solves
        (scaled with this catalog state's factors) and its fp64 θ."""
        z = design(joined, cols, compute_scale_factors(store, feats, label))
        full = z.T @ z
        out = {}
        for _, sub in SERVICE_TRAINS:
            idx = [0] + [1 + cols.index(f) for f in sub] + [len(cols)]
            r = full[np.ix_(idx, idx)]
            out[sub] = (r, ridge_solve(r, 0.006))
        return out

    def train_all(svc):
        tickets = [svc.train(t, vorder, list(s), label) for t, s in SERVICE_TRAINS]
        return [tk.result(timeout=600) for tk in tickets]

    t = time.perf_counter()
    ref_before = reference(joined)
    ref_s = time.perf_counter() - t
    t = time.perf_counter()
    svc = FactorizedService(store, backend="jax").start()
    try:
        with KernelProbe() as probe:
            before = train_all(svc)
            delta = new_sales(store, 0.01, seed + 1)
            merged = svc.append("tenant-a", "SalesF", delta).result(timeout=600)
            after = train_all(svc)
            cof = svc.cofactors("tenant-b", vorder, cols).result(timeout=600)
    finally:
        svc.stop()
    secs = time.perf_counter() - t
    if not probe.calls["segment_view"]:
        raise AssertionError("service: no fused node ran")
    rows_after = joined.num_rows + delta.num_rows
    if merged.num_rows != rows_after or cof.count != rows_after:
        raise AssertionError(
            f"the append is not visible: {merged.num_rows} merged rows, "
            f"cofactor count {cof.count}, want {rows_after}"
        )

    t = time.perf_counter()
    joined_after = store.materialize_join()
    ref_after = reference(joined_after)
    z = design(joined_after, cols, None)
    cof_err = cof_error(cof.matrix(), z.T @ z)
    del z
    ref_s += time.perf_counter() - t
    check("service cofactors", cof_err, COF_TOL)
    worst = 0.0
    for results, refs in ((before, ref_before), (after, ref_after)):
        for (_, sub), res in zip(SERVICE_TRAINS, results):
            r, want = refs[sub]
            err = pred_error(res.theta_conv, want, r)
            check(f"service {sub}", err, PRED_TOL)
            worst = max(worst, err)
    if svc.quarantined():
        raise AssertionError(f"service quarantined {svc.quarantined()}")
    info = svc.cache_info()
    log(
        "service",
        trains=2 * len(SERVICE_TRAINS),
        appended_rows=delta.num_rows,
        cofactor_count=int(cof.count),
        seconds=f"{secs:.2f}",
        node_kernels=dict(probe.calls),
        worst_pred_err=f"{worst:.3e}",
        cof_err=f"{cof_err:.3e}",
        tol=f"{PRED_TOL:.0e}",
        coalesced_batches=info["coalesced_batches"],
        fold_failures=info["fold_failures"],
        reference_s=f"{ref_s:.2f}",
        peak_bytes_in_use=peak_bytes(),
    )


def phase_sharded(bundle, joined, devs) -> None:
    """Rows of the fact join spread over a 4-device ``data`` mesh through
    ``sharded_cofactors`` / ``sharded_cat_cofactors``, against the same
    calls on a one-device mesh and the fp64 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.categorical import cat_cofactors_from_arrays
    from repro.core.distributed import (
        sharded_cat_cofactors,
        sharded_cofactors,
        sharded_gram,
    )
    from repro.core.scaling import compute_scale_factors

    store, feats, label = bundle.store, bundle.features, bundle.label
    cols = feats + [label]
    u = design(joined, cols, compute_scale_factors(store, feats, label))
    x, ref = u[:, 1:], u.T @ u

    def mesh(n):
        return jax.make_mesh(
            (n,), ("data",), axis_types=(AxisType.Auto,), devices=devs[:n]
        )

    four, one = mesh(4), mesh(1)
    # the layout sharded_cofactors builds, placed here so its shards show
    u_dev = jax.device_put(
        u.astype(np.float32), NamedSharding(four, P("data", None))
    )
    shards = [(str(s.device), s.data.shape) for s in u_dev.addressable_shards]
    if len({d for d, _ in shards}) != 4:
        raise AssertionError(f"rows are not spread over four devices: {shards}")
    err_gram = cof_error(sharded_gram(u_dev, four, ("data",)), ref)
    # the same Gram in one bf16 pass (bf16 operands, f32 sums), to show
    # the limit would catch it
    bf16 = jax.jit(
        lambda a: jnp.matmul(a.T, a, preferred_element_type=jnp.float32)
    )
    err_bf = cof_error(bf16(u_dev.astype(jnp.bfloat16)), ref)
    check("sharded_gram", err_gram, COF_TOL, err_bf)

    t = time.perf_counter()
    c4 = sharded_cofactors(x, cols, four).matrix()
    s4 = time.perf_counter() - t
    t = time.perf_counter()
    c1 = sharded_cofactors(x, cols, one).matrix()
    s1 = time.perf_counter() - t
    err41, err4 = cof_error(c4, c1), cof_error(c4, ref)
    check("sharded cofactors vs reference", err4, COF_TOL)
    check("sharded cofactors 4 vs 1 chip", err41, COF_TOL)
    log(
        "sharded",
        step="cofactors",
        shards=shards,
        four_chip_s=f"{s4:.2f}",
        one_chip_s=f"{s1:.2f}",
        err_4_vs_1=f"{err41:.3e}",
        err_vs_ref=f"{err4:.3e}",
        gram_err=f"{err_gram:.3e}",
        bf16_gram_err=f"{err_bf:.3e}",
        tol=f"{COF_TOL:.0e}",
        peak_bytes_in_use=peak_bytes(),
    )

    cat = ["store_nbr"]
    cont = [c for c in cols if c not in cat]
    xc = x[:, [cols.index(c) for c in cont]]
    ids = joined.column("store_nbr").astype(np.int64)[:, None]
    doms = {"store_nbr": store.attr_domain("store_nbr")}
    t = time.perf_counter()
    k4 = sharded_cat_cofactors(xc, ids, cont, cat, doms, four).matrix()
    s4 = time.perf_counter() - t
    t = time.perf_counter()
    k1 = sharded_cat_cofactors(xc, ids, cont, cat, doms, one).matrix()
    s1 = time.perf_counter() - t
    host = cat_cofactors_from_arrays(xc, ids, cont, cat, doms).matrix()
    err41, err4 = cof_error(k4, k1), cof_error(k4, host)
    check("sharded categorical vs host fp64", err4, COF_TOL)
    check("sharded categorical 4 vs 1 chip", err41, COF_TOL)
    log(
        "sharded",
        step="categorical",
        categories=doms["store_nbr"],
        four_chip_s=f"{s4:.2f}",
        one_chip_s=f"{s1:.2f}",
        err_4_vs_1=f"{err41:.3e}",
        err_vs_host_fp64=f"{err4:.3e}",
        tol=f"{COF_TOL:.0e}",
        peak_bytes_in_use=peak_bytes(),
    )


def run(args, devs) -> None:
    """Every phase of the selected path, in order; any failure raises."""
    t = time.perf_counter()
    bundle, joined = phase_data(args.rows, args.seed)
    if args.chips == 4:
        phase_sharded(bundle, joined, devs)
    else:
        phase_fit(bundle, joined)
        phase_categorical(bundle, joined)
        phase_service(bundle, joined, args.seed)
    log("total", seconds=f"{time.perf_counter() - t:.2f}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", type=int, default=10_000_000,
                        help="Favorita sales rows to generate")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded four-chip path")
    args = parser.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache(ROOT)
    devs = phase_device(args.chips)
    run(args, devs)
    d = devs[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devs)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
