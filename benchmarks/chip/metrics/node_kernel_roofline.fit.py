"""node_kernel_roofline.fit: the node steps' share of their roofline, %.

The least time the chip could take for every node step of the window
(``work.node_step`` from each step's shapes, against ``peaks.py``) over
the device time of the operations launched inside the benchmark's spans
around ``ops.segment_view`` and ``ops.segment_blocks``."""

from benchmarks.chip import peaks, probe, work


def value(run):
    if run.trace is None or not run.nodes:
        return None
    device_s = sum(run.trace["device_s_by_span"].get(s, 0.0) for s in probe.NODE_SPANS)
    if device_s <= 0:
        return None
    peak = peaks.peaks(run.kind)
    least = 0.0
    for n in run.nodes:
        w = work.node_step(n["rows"], n["k"], n["degree"], n["groups"],
                           feature=n["span"] == "ops.segment_view")
        least += work.roofline_seconds(w, peak)[0]
    return 100.0 * least / device_s
