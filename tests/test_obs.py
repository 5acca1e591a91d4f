"""Spans and process counters (``repro.obs``): the registry matches the
source, a traced service train yields every span nested as the layers
nest, the copy counters count what a traversal moves, lowerings are put
down to the span that caused them, and tracing changes no answer."""

import ast
import glob
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core.factorize import FactorizedEngine
from repro.core.relation import Relation
from repro.core.store import Store
from repro.core.variable_order import VariableOrder
from repro.data.synthetic import favorita_like
from repro.kernels import ops
from repro.kernels.segment_view import bucket
from repro.serve.factorized import FactorizedService

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src", "repro")

#: each span's immediate parent on its thread in a service train
PARENTS = {
    "repro.service.submit": {None},
    "repro.service.cycle": {None},
    "repro.service.batch": {"repro.service.cycle"},
    "repro.service.solve": {"repro.service.batch"},
    "repro.store.append": {"repro.service.cycle"},
    "repro.store.fold": {"repro.engine.init", "repro.service.cycle"},
    "repro.engine.init": {"repro.service.batch"},
    "repro.engine.node": {"repro.service.batch", "repro.engine.node"},
    "repro.engine.join": {"repro.engine.node"},
    "repro.engine.gather": {"repro.engine.node"},
    "repro.engine.feature": {"repro.engine.node"},
    "repro.engine.group": {"repro.engine.node"},
    "repro.engine.group_key": {"repro.engine.group", "repro.kernel.group_ids"},
    "repro.kernel.group_ids": {"repro.engine.group"},
    "repro.kernel.segment_view": {"repro.engine.node"},
    "repro.kernel.segment_blocks": {"repro.engine.node"},
    "repro.kernel.pack": {"repro.kernel.segment_view", "repro.kernel.segment_blocks"},
}


def _span_literals():
    """Every ``obs.span("...")`` name literal under ``src/repro``."""
    names = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        for node in ast.walk(ast.parse(open(path).read())):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "span"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "obs"
            ):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant), f"{path}: non-literal span"
                names.add(arg.value)
    return names


def test_registry_matches_the_spans_in_the_source():
    used = _span_literals()
    assert used == set(obs.SPANS)
    assert set(PARENTS) == set(obs.SPANS)
    assert all(name.startswith("repro.") for name in obs.SPANS)


@pytest.fixture
def steered(monkeypatch):
    """Node kernels through Pallas (interpret mode) and device grouping,
    as on the chip."""
    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    monkeypatch.setattr(ops, "fast_device_grouping", lambda: True)


def _host_spans(path):
    """``[(thread, start, end, name, stats)]`` of the ``repro.`` spans."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((i, e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return out


def _parent(spans, sp):
    """The innermost span enclosing ``sp`` on its thread, or None."""
    around = [
        o for o in spans
        if o is not sp and o[0] == sp[0] and o[1] <= sp[1] and sp[2] <= o[2]
    ]
    return max(around, key=lambda o: (o[1], -o[2]))[3] if around else None


def _favorita_train(traced: bool):
    """One append and one train through a threaded jax service over a tiny
    Favorita-shaped store; returns (θ, spans or None, cache_info)."""
    b = favorita_like(n_dates=6, n_stores=3, n_items=4, seed=3)
    svc = FactorizedService(b.store, backend="jax").start()
    trace_dir = tempfile.mkdtemp() if traced else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    try:
        extra = Relation.from_columns("Oil", {"date": [5]}, {"dcoilwtico": [51.5]})
        svc.append("tenant-a", "Oil", extra).result(timeout=300)
        res = svc.train("tenant-a", b.vorder, b.features, b.label).result(timeout=300)
        info = svc.cache_info()
    finally:
        svc.stop()  # every cycle ends inside the trace
        if traced:
            jax.profiler.stop_trace()
    spans = None
    if traced:
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        spans = _host_spans(path[0])
    return res.theta, spans, info


def test_traced_service_train_yields_nested_spans_and_same_theta(steered):
    theta, spans, info = _favorita_train(traced=True)
    names = {s[3] for s in spans}
    assert names == set(obs.SPANS)
    for sp in spans:
        assert _parent(spans, sp) in PARENTS[sp[3]], (sp, _parent(spans, sp))
    # the train's request id links its spans across client and drain threads
    train = [s for s in spans if s[3] == "repro.service.submit"
             and s[4]["kind"] == "train"]
    (submit,) = train
    seq = submit[4]["request"]
    batch = [s for s in spans if s[3] == "repro.service.batch"]
    assert [str(s[4]["requests"]) for s in batch] == [str(seq)]
    (solve,) = [s for s in spans if s[3] == "repro.service.solve"]
    assert solve[4]["request"] == seq and solve[0] == batch[0][0] != submit[0]
    node = [s for s in spans if s[3] == "repro.kernel.segment_view"]
    assert all({"rows", "k", "degree", "groups"} <= set(s[4]) for s in node)
    assert info["queue_waits"] == 1 and info["queue_wait_s"] >= 0
    process = info["process"]
    assert process["h2d_bytes"] > 0 and process["d2h_bytes"] > 0
    assert process["dispatches"]["segment_view"] >= len(node)
    untraced, _, _ = _favorita_train(traced=False)
    assert np.array_equal(theta, untraced)


def _two_relations(n_r=37, n_s=23):
    """R(a | x) and S(a | y) sharing key a: x, y grouped per a, then joined."""
    rng = np.random.default_rng(0)
    ar, as_ = rng.integers(0, 9, n_r), rng.integers(3, 14, n_s)
    store = Store([
        Relation.from_columns("R", {"a": ar}, {"x": rng.normal(size=n_r)}),
        Relation.from_columns("S", {"a": as_}, {"y": rng.normal(size=n_s)}),
    ])
    vorder = VariableOrder.intercept([
        VariableOrder("a", [
            VariableOrder("x", [VariableOrder.leaf("R")]),
            VariableOrder("y", [VariableOrder.leaf("S")]),
        ])
    ])
    return store, vorder, len(set(ar) & set(as_))


def test_copy_counters_match_a_hand_sized_traversal():
    store, vorder, joined = _two_relations()
    n_r, n_s = store.get("R").num_rows, store.get("S").num_rows
    eng = FactorizedEngine(store, vorder, ["x", "y"], backend="jax",
                           use_view_cache=False)
    eng.device_grouping = True
    before = obs.snapshot()
    eng.cofactors()
    after = obs.snapshot()
    # per leaf: its feature column up (f32), its grouping key column up
    # (int16, its domain fits; padded) with its int32 radix, the sort
    # order (int32) and group starts (bool) down
    # node a: six takes (c, l, q per side) upload the join's int32 indices
    # and the one-group regroup uploads its int32 ids; then the root's
    # count, 2 sums and 2x2 products come down as f32
    h2d = sum(4 * n + 2 * bucket(n, 1024) + 4 for n in (n_r, n_s))
    h2d += 7 * 4 * joined
    d2h = sum(5 * bucket(n, 1024) for n in (n_r, n_s)) + 4 * (1 + 2 + 4)
    assert after["h2d_bytes"] - before["h2d_bytes"] == h2d
    assert after["d2h_bytes"] - before["d2h_bytes"] == d2h


@pytest.mark.parametrize("device", [True, False])
def test_group_row_counters_count_each_path(device):
    """``group_rows_device`` counts the rows grouped with the key packed on
    the device, ``group_rows_host`` those ``group_key`` packed: each leaf
    groups its rows by ``a`` once, and the root's empty GROUP BY packs no
    key."""
    store, vorder, _ = _two_relations()
    rows = store.get("R").num_rows + store.get("S").num_rows
    eng = FactorizedEngine(store, vorder, ["x", "y"], backend="jax",
                           use_view_cache=False)
    eng.device_grouping = device
    before = obs.snapshot()
    eng.cofactors()
    after = obs.snapshot()
    got = {w: after[f"group_rows_{w}"] - before[f"group_rows_{w}"]
           for w in ("device", "host")}
    assert got == ({"device": rows, "host": 0} if device
                   else {"device": 0, "host": rows})


def test_device_arrays_pass_through_uncounted():
    x = jnp.arange(5, dtype=jnp.float32)
    h = np.arange(5, dtype=np.float32)
    before = obs.snapshot()
    assert obs.to_device(x) is x
    assert obs.to_host(h) is h
    assert obs.snapshot()["h2d_bytes"] == before["h2d_bytes"]
    assert obs.snapshot()["d2h_bytes"] == before["d2h_bytes"]
    np.testing.assert_array_equal(obs.to_host(obs.to_device(h)), h)
    after = obs.snapshot()
    assert after["h2d_bytes"] - before["h2d_bytes"] == h.nbytes
    assert after["d2h_bytes"] - before["d2h_bytes"] == h.nbytes


def test_lowering_is_put_down_to_the_innermost_span():
    def lowered(snap, where):
        return snap["lowered"].get(where, 0)

    before = obs.snapshot()
    with obs.span("repro.engine.node", node="t", degree=2):
        with obs.span("repro.engine.gather", rows=3):
            jax.jit(lambda v: v * 3.0 + 1.0)(jnp.ones(7))
    jax.jit(lambda v: v - 2.0)(jnp.ones(5))
    # a group count new to the process compiles the fused node anew
    n, groups = 211, 197
    ops.segment_view(
        jnp.ones(n), jnp.ones(n), jnp.ones((n, 1)), jnp.ones((n, 1, 1)),
        jnp.arange(n, dtype=jnp.int32) % groups, groups, impl="xla",
    )
    after = obs.snapshot()
    gather, node = "repro.engine.gather", "repro.engine.node"
    assert lowered(after, gather) == lowered(before, gather) + 1
    assert lowered(after, node) == lowered(before, node)
    assert lowered(after, "none") > lowered(before, "none")
    assert lowered(after, "repro.kernel.segment_view") > lowered(
        before, "repro.kernel.segment_view")


def test_span_self_time_subtracts_child_spans():
    before = obs.snapshot()["span_self_s"]
    with obs.span("repro.service.batch", requests=(1, 2)):
        with obs.span("repro.engine.init"):
            time.sleep(0.05)
    after = obs.snapshot()["span_self_s"]
    outer = after["repro.service.batch"] - before.get("repro.service.batch", 0.0)
    inner = after["repro.engine.init"] - before.get("repro.engine.init", 0.0)
    assert inner >= 0.05 and 0 <= outer < 0.05


def test_queue_waits_grow_by_one_per_request():
    b = favorita_like(n_dates=4, n_stores=2, n_items=3, seed=1)
    svc = FactorizedService(b.store)
    before = svc.cache_info()
    tickets = [
        svc.train(f"t{i}", b.vorder, b.features, b.label) for i in range(3)
    ]
    svc.append("t0", "Oil", Relation.from_columns(
        "Oil", {"date": [3]}, {"dcoilwtico": [50.0]}))
    svc.run()
    assert all(t.done for t in tickets)
    after = svc.cache_info()
    assert after["queue_waits"] - before["queue_waits"] == 3
    assert after["queue_wait_s"] >= before["queue_wait_s"]
    assert after["queue_wait_max_s"] <= after["queue_wait_s"]


def test_counters_lose_no_update_across_threads():
    """More threads than cores count dispatches, copies and span time at
    once, with the interpreter switching threads as often as it can."""
    import sys
    import threading

    threads, each = len(os.sched_getaffinity(0)) + 2, 200
    host = np.ones(3, np.float32)
    before = obs.snapshot()
    interval = sys.getswitchinterval()

    def work():
        for _ in range(each):
            with obs.span("repro.kernel.pack", rows=3, width=8):
                obs.dispatch("segment_blocks")
                obs.to_device(host)

    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    after = obs.snapshot()
    n = threads * each
    grown = after["dispatches"]["segment_blocks"] - before["dispatches"].get(
        "segment_blocks", 0)
    assert grown == n
    assert after["h2d_bytes"] - before["h2d_bytes"] == n * host.nbytes
