"""From the profiler's trace of a window to device busy time, device time by
benchmark span, the device operations that took most time and the idle
gaps by what the host was doing.

:func:`events` reads an ``.xplane.pb`` into plain tuples and
:func:`reduce_events` does the arithmetic on them, so the arithmetic can be
checked on a small recorded trace without the profiler.

- Busy time is the union of the intervals in which an operation ran on a
  device (overlapping operations count once), clipped to the window span,
  averaged over the devices that ran any.
- A device operation belongs to the benchmark span that was innermost on
  the host thread that launched its program (or, where that thread had
  none open, on any thread at the launch).  The profiler ties the launch
  to the program on the device (see :func:`events`).  Node kernels are
  found this way, by the span around ``ops.segment_view`` /
  ``ops.segment_blocks``, never by a kernel's internal name.
- An idle gap is named by the innermost benchmark span open on any host
  thread at its middle (``none`` where there is none).
"""

from __future__ import annotations

import bisect

WINDOW = "bench.window"
CLIENT_SPANS = ("client.train", "client.append")
TOP = 10  # entries of each breakdown list


def options():
    """Profiler options: host annotations on, the Python tracer off (it
    would record every Python call of the host path)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def events(path: str) -> dict:
    """The trace at ``path`` as ``{"device": [(device, start_ns, end_ns,
    name, launch)], "host": [(thread, start_ns, end_ns, name, launch)]}``.

    Device operations are the ``XLA Ops`` lines of ``/device:`` planes.
    ``launch`` ties a device operation to the host event that launched its
    program: the profiler gives the launching host event a producer id
    (stat ``_p``) and the ``XLA Modules`` event of the program on the
    device the same consumer id (``_c``); an operation takes the id of the
    module it starts in.  Where a trace carries no such ids it falls back
    to ``run_id`` (the CPU's)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                for e in line.events:
                    st = _stats(e)
                    tie = st.get("_c", st.get("run_id"))
                    item = (e.start_ns, e.start_ns + e.duration_ns, e.name, tie)
                    (modules if line.name == "XLA Modules" else ops).append(item)
            modules.sort()
            for start, end, name, tie in ops:
                if tie is None:
                    tie = _enclosing_run(modules, start)
                dev.append((plane.name, start, end, name, tie))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}/{i}/{line.name}"
                for e in line.events:
                    st = _stats(e)
                    tie = st.get("_p", st.get("run_id"))
                    host.append((thread, e.start_ns, e.start_ns + e.duration_ns, e.name, tie))
    return {"device": dev, "host": host}


def _enclosing_run(modules, t):
    i = bisect.bisect_right(modules, (t, float("inf"))) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][3]
    return None


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _innermost(spans, t):
    """The span with the latest start among sorted ``spans`` open at ``t``."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    while i >= 0:
        s, e, name = spans[i]
        if e > t:
            return spans[i]
        i -= 1
    return None


def reduce_events(ev: dict, span_names) -> dict:
    """Busy and window seconds, device seconds by span, and the breakdown
    (see the module docstring) of the events ``ev``."""
    span_names = set(span_names)
    windows = [(s, e) for _, s, e, name, _ in ev["host"] if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w0, w1 = windows[0]

    by_thread: dict = {}
    launches = []
    for thread, s, e, name, rid in ev["host"]:
        if name in span_names:
            by_thread.setdefault(thread, []).append((s, e, name))
        elif rid is not None:
            launches.append((thread, s, rid))
    for spans in by_thread.values():
        spans.sort()
    all_spans = sorted(sp for spans in by_thread.values() for sp in spans)
    run_span = {}
    for thread, t, rid in launches:
        hit = _innermost(by_thread.get(thread, []), t) or _innermost(all_spans, t)
        if hit is not None:
            run_span.setdefault(rid, hit[2])

    per_device: dict = {}
    op_time: dict = {}
    span_s: dict = {}
    for device, s, e, name, rid in ev["device"]:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        per_device.setdefault(device, []).append((s, e))
        secs = (e - s) * 1e-9
        op_time[name] = op_time.get(name, 0.0) + secs
        span = run_span.get(rid, "none")
        span_s[span] = span_s.get(span, 0.0) + secs
    merged = {d: union(iv) for d, iv in per_device.items()}
    busy = [sum(e - s for s, e in iv) * 1e-9 for iv in merged.values()]
    busy_s = sum(busy) / len(busy) if busy else 0.0

    gaps = []
    for iv in merged.values() or [[]]:
        edges = [w0] + [x for pair in iv for x in pair] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                hit = _innermost(all_spans, (s + e) / 2)
                gaps.append(((e - s) * 1e-9, hit[2] if hit else "none"))
        break  # gaps of the first device that ran anything
    gaps.sort(reverse=True)
    idle_by_span: dict = {}
    for secs, name in gaps:
        idle_by_span[name] = idle_by_span.get(name, 0.0) + secs
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": (w1 - w0) * 1e-9,
        "device_s_by_span": span_s,
        "idle_s_by_span": idle_by_span,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for s, n in gaps[:TOP]],
        },
    }


def reduce(path: str, span_names) -> dict:
    """:func:`reduce_events` of the trace file at ``path``."""
    return reduce_events(events(path), span_names)
