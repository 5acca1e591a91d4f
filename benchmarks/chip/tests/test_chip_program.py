"""The program's spans beside the benchmark's: adding ``repro.`` spans to a
trace leaves the benchmark's own reduction as it was, the program-span
reduction (``program_trace.py``) subtracts child spans and names idle
time, and the readers of the program's counters read the CPU rehearsal."""

import json
import os
import types

import jax
import pytest

from benchmarks.chip import program_trace, trace
from benchmarks.chip.tests.conftest import CELLS, SEED, plan
from benchmarks.chip.tests.test_chip_trace import SPANS, synthetic

KIND = "TPU v5 lite"
#: program spans of the synthetic window: a node step around run 7's
#: launch (its pack the innermost), a node around the group-key gap
PROGRAM = [
    ("drain", 8, 22, "repro.engine.node", {}),
    ("drain", 10, 20, "repro.kernel.segment_view",
     {"rows": 64, "k": 2, "degree": 2, "groups": 4}),
    ("drain", 11, 14, "repro.kernel.pack", {"rows": 64, "width": 8}),
    ("drain", 44, 82, "repro.engine.node", {}),
    ("drain", 45, 80, "repro.engine.group_key", {"rows": 64}),
]


def with_program(ev, spans):
    out = dict(ev)
    out["host"] = list(ev["host"]) + [sp[:4] + (None,) for sp in spans]
    return out


def recorded():
    path = os.path.join(os.path.dirname(__file__), "data", "tpu_v5e_node_trace.json")
    with open(path) as f:
        return json.load(f)


def around(ev, inside, name):
    """A program span just around every host span named ``inside``."""
    return [(t, s - 1, e + 1, name, {}) for t, s, e, n, _ in ev["host"] if n == inside]


def test_program_spans_leave_the_benchmark_reduction_unchanged():
    ev = synthetic()
    assert trace.reduce_events(with_program(ev, PROGRAM), SPANS) == \
        trace.reduce_events(ev, SPANS)


def test_program_spans_leave_the_recorded_trace_reduction_unchanged():
    ev = recorded()
    names = SPANS | {"engine.init", "store.flush", "ops.segment_blocks",
                     "relation.sort_merge_join"}
    spans = (around(ev, "ops.segment_view", "repro.kernel.segment_view")
             + around(ev, "ops.group_ids_device", "repro.kernel.group_ids")
             + around(ev, "engine.run_batch", "repro.service.batch"))
    assert spans
    assert trace.reduce_events(with_program(ev, spans), names) == \
        trace.reduce_events(ev, names)


def test_host_self_time_subtracts_child_spans():
    got = program_trace.self_seconds(PROGRAM)
    assert got["repro.engine.node"] == pytest.approx((14 - 10 + 38 - 35) * 1e-9)
    assert got["repro.kernel.segment_view"] == pytest.approx((10 - 3) * 1e-9)
    assert got["repro.kernel.pack"] == pytest.approx(3e-9)
    assert got["repro.engine.group_key"] == pytest.approx(35e-9)


#: run 7 as a TPU launches it: the drain thread hands the program over
#: inside the pack span; a runtime thread enqueues it after the node's
#: spans have closed, tied back by the profiler's flow ids
FLOWS = [
    ("drain", 12, 12.5, "PJRT_LoadedExecutable_Execute linkage", {"_p": 101}),
    ("main", 13, 16, "PJRT_LoadedExecutable_Execute", {"_c": 101}),
    ("main", 13.5, 15, "tpu::System::Execute", {"_p": 102}),
    ("runtime", 22.5, 24, "tpu::System::Execute=>IssueSequencedEvent", {"_c": 102}),
    ("runtime", 23, 23.5, "DoEnqueueProgram", {"_p": 7}),
]


def deferred():
    """The synthetic window with run 7 launched as ``FLOWS`` says, as
    ``(events, host events with stats)``."""
    ev = synthetic()
    ev["host"] = [h for h in ev["host"] if h[4] != 7]
    ev["host"] += [(t, s, e, n, st.get("_p")) for t, s, e, n, st in FLOWS]
    flows = {x[3] for x in FLOWS}
    host = [(t, s, e, n, {} if tie is None else {"_p": tie})
            for t, s, e, n, tie in ev["host"] if n not in flows]
    host += FLOWS + PROGRAM
    return with_program(ev, PROGRAM), host


def test_launches_follow_the_flows_back_to_the_asking_thread():
    _, host = deferred()
    placed = {pid: (t, s) for t, s, _, _, pid in program_trace.launches(host)}
    assert placed[7] == ("drain", 12) and placed[8] == ("drain", 31)


def test_program_reduction_of_a_constructed_window():
    ev, host = deferred()
    # the benchmark's own tie finds run 7's enqueue at 23, after the pack
    # and node spans closed, on a thread with no span
    assert "repro.kernel.pack" not in trace.reduce_events(
        ev, {sp[3] for sp in PROGRAM})["device_s_by_span"]
    out = program_trace.reduce_program(ev, host, SPANS, KIND)
    assert out["trains"] == 1
    # run 7 was asked for inside the pack span; run 8 (31..32) in none
    assert out["device_ms"]["repro.kernel.pack"] == pytest.approx(36e-6)
    assert out["device_ms"]["none"] == pytest.approx(16e-6)
    bench = out["benchmark_span_device_ms"]
    assert bench["ops.segment_view"] == pytest.approx(36e-6)
    assert bench["ops.group_ids_device"] == pytest.approx(6e-6)
    # idle [0, 14] at 7 is under no program span: engine.run_batch has it;
    # [44, 90] at 67 is under the group key's span
    assert out["idle_ms"]["repro.engine.group_key"] == pytest.approx(46e-6)
    assert out["idle_outside_ms"] == {"engine.run_batch": pytest.approx(14e-6)}
    assert out["idle_under_program_share"] == pytest.approx(46 / 60)
    assert out["host_self_ms"]["repro.engine.group_key"] == pytest.approx(35e-6)
    assert 0 < out["node_roofline_pct"] < 100
    assert out["grouping_device_ms"] == 0.0


def program_metrics(workload):
    """The cell's per-layer metrics that read the program's counters and
    spans, by ``BENCHMARK.json``."""
    return {m["name"] for m in plan(workload)["per_layer"]
            if m["source"] in ("program_counter", "program_span")}


@pytest.mark.parametrize("workload", CELLS)
def test_program_counter_readers_read_the_cpu_rehearsal(steered, workload):
    args = types.SimpleNamespace(seed=SEED, seconds=1.5, trace=1)
    out = steered.run_cell(args, jax.devices(), plan(workload))
    assert out["correct"]
    got = {name: m["value"] for name, m in out["metrics"].items()}
    assert program_metrics(workload) <= set(got)
    # by quantity, the part of a metric's name before its mix
    by_quantity = {name.split(".")[0]: v for name, v in got.items()}
    assert by_quantity.get("lowered", 0) == 0  # nothing compiled in the window
    for quantity in ("transfer_mb", "host_group_key_ms"):
        assert by_quantity.get(quantity, 1) > 0, quantity


@pytest.mark.parametrize("workload", CELLS)
def test_readers_of_a_program_without_counters_read_nothing(workload):
    from benchmarks.chip.run import load_module

    run = types.SimpleNamespace(
        records=[{"ok": True}], service_before={"passes": 1},
        service_after={"passes": 2},
    )
    names = program_metrics(workload)
    assert names
    for name in names:
        assert load_module("metrics", name + ".py").value(run) is None
