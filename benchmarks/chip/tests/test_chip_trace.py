"""The trace reduction on small traces: busy time is a union of intervals,
device time goes to the span that launched it, idle gaps are named by the
span the host was in."""

import json
import os

import pytest

from benchmarks.chip import trace

SPANS = {"engine.run_batch", "ops.segment_view", "ops.group_ids_device",
         "client.train", "relation.group_key"}


def synthetic():
    """A window 0..100 ns on one device: ops of two programs overlap
    (run 7 launched inside ops.segment_view, run 8 inside
    ops.group_ids_device); the host sits in relation.group_key over the
    long gap."""
    host = [
        ("main", 0, 100, trace.WINDOW, None),
        ("client", 0, 100, "client.train", None),
        ("drain", 5, 95, "engine.run_batch", None),
        ("drain", 10, 20, "ops.segment_view", None),
        ("drain", 12, 13, "launch", 7),
        ("drain", 30, 35, "ops.group_ids_device", None),
        ("drain", 31, 32, "launch", 8),
        ("drain", 45, 80, "relation.group_key", None),
    ]
    device = [
        ("/device:TPU:0", 14, 30, "fusion.1", 7),
        ("/device:TPU:0", 20, 40, "custom-call.2", 7),  # overlaps fusion.1
        ("/device:TPU:0", 38, 44, "sort.3", 8),  # overlaps custom-call.2
        ("/device:TPU:0", 90, 120, "copy.4", None),  # runs past the window
    ]
    return {"host": host, "device": device}


def test_busy_is_a_union_not_a_sum():
    out = trace.reduce_events(synthetic(), SPANS)
    # [14, 44] and [90, 100] -> 40 ns busy; the plain sum would be 16+20+6+10
    assert out["busy_s"] == pytest.approx(40e-9)
    assert out["window_s"] == pytest.approx(100e-9)


def test_device_time_goes_to_the_launching_span():
    out = trace.reduce_events(synthetic(), SPANS)
    by_span = out["device_s_by_span"]
    assert by_span["ops.segment_view"] == pytest.approx(36e-9)
    assert by_span["ops.group_ids_device"] == pytest.approx(6e-9)
    assert by_span["none"] == pytest.approx(10e-9)


def test_node_kernels_are_found_by_span_not_by_name():
    ev = synthetic()
    ev["device"] = [(d, s, e, "anything.%d" % i, r)
                    for i, (d, s, e, _, r) in enumerate(ev["device"])]
    out = trace.reduce_events(ev, SPANS)
    assert out["device_s_by_span"]["ops.segment_view"] == pytest.approx(36e-9)


def test_gaps_are_named_by_the_enclosing_span():
    out = trace.reduce_events(synthetic(), SPANS)
    gaps = out["breakdown"]["idle_gaps"]
    # [44, 90]: relation.group_key is innermost at 67, its middle;
    # [0, 14]: engine.run_batch (opened at 5) at 7
    assert gaps[0][0] == "relation.group_key"
    assert gaps[0][1] == pytest.approx(46e-9)
    assert gaps[1] == ["engine.run_batch", pytest.approx(14e-9)]
    assert out["idle_s_by_span"]["relation.group_key"] == pytest.approx(46e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["copy.4"] == pytest.approx(10e-9)  # clipped to the window


def test_no_window_span_is_an_error():
    ev = synthetic()
    ev["host"] = [h for h in ev["host"] if h[3] != trace.WINDOW]
    with pytest.raises(ValueError):
        trace.reduce_events(ev, SPANS)


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e around one small Favorita traversal
    (the harness's spans; ``events()`` of its xplane, host events cut to
    the spans and the ``DoEnqueueProgram`` launches).  Every device
    operation is tied to the span that launched its program."""
    path = os.path.join(os.path.dirname(__file__), "data", "tpu_v5e_node_trace.json")
    with open(path) as f:
        ev = json.load(f)
    out = trace.reduce_events(ev, SPANS | {"engine.init", "store.flush",
                                           "ops.segment_blocks", "relation.sort_merge_join"})
    by_span = out["device_s_by_span"]
    total = sum(by_span.values())
    assert by_span.get("none", 0.0) < 1e-3 * total
    assert by_span["ops.segment_view"] > 0 and by_span["ops.group_ids_device"] > 0
    assert 0 < out["busy_s"] <= total + 1e-12
    assert out["busy_s"] < out["window_s"]
