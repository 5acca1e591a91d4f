#!/usr/bin/env python3
"""The program's own spans in one traced run of a cell.

    python3 benchmarks/chip/program_trace.py --workload favorita.fit \\
        --seed 7 --seconds 51

Runs ``run.py`` with ``--trace 1`` and reads its trace a second way: by
the ``repro.`` spans the program opens (``repro.obs.SPANS``), beside the
benchmark's own spans around the program's functions.  ``run.py``'s
result line on standard output is unchanged; the last line of standard
error is ``[program-trace] {json}`` with, per train:

- ``host_self_ms``: host self time by program span (its duration less
  that of the program spans inside it on the same thread);
- ``device_ms``: device time by the innermost program span open where
  its program was asked for (``none`` outside any), found by following
  the profiler's flows back from the program's enqueue (``launches``), and
  ``benchmark_span_device_ms`` the same by the benchmark's spans;
- ``idle_ms``: device idle time by the innermost program span open on any
  host thread at the gap's middle, and ``idle_outside_ms`` the idle time
  under no program span, by the benchmark span it fell under;
- ``grouping_device_ms`` and ``node_roofline_pct``: the device time of
  ``repro.kernel.group_ids``, and the node steps' roofline share from the
  ``repro.kernel.segment_view`` / ``segment_blocks`` spans' shape
  attributes over the device time of those spans and ``repro.kernel.pack``
  — the program-span readings of ``grouping_device_ms.fit`` and
  ``node_kernel_roofline.fit``.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PREFIX = "repro."
NODE_STEPS = ("repro.kernel.segment_view", "repro.kernel.segment_blocks")


def host_events(path: str) -> list:
    """Every host event of the trace at ``path`` as ``[(thread, start_ns,
    end_ns, name, stats)]``, threads named as ``trace.events`` names them."""
    from jax.profiler import ProfileData

    from benchmarks.chip.trace import _stats

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}/{line.name}"
            for e in line.events:
                out.append((thread, e.start_ns, e.start_ns + e.duration_ns,
                            e.name, _stats(e)))
    return out


def _enclosing(items, t):
    """The item with the latest start among sorted ``(start, end, ...)``
    items open at ``t``."""
    i = bisect.bisect_right(items, (t, float("inf"))) - 1
    while i >= 0:
        if items[i][1] > t:
            return items[i]
        i -= 1
    return None


def launches(host) -> list:
    """Every flow producer of ``host`` as a launch event ``(thread, t, t,
    "launch", id)`` placed where its chain of flows starts.

    On a TPU the Python thread only hands a program over
    (``PJRT_LoadedExecutable_Execute linkage``); a runtime thread enqueues
    it later (``DoEnqueueProgram``, whose producer id the program's
    ``XLA Modules`` event consumes).  Walking back from a producer to the
    consumer event around it on its thread, then to that flow's producer,
    and so on, reaches the thread and time where the program was asked
    for: there the program spans open say which step launched it."""
    producers = {}
    consumers: dict = {}
    for thread, s, e, _, st in host:
        if "_p" in st:
            producers[st["_p"]] = (thread, s)
        if "_c" in st:
            consumers.setdefault(thread, []).append((s, e, st["_c"]))
    for items in consumers.values():
        items.sort()
    out = []
    for pid, (thread, t) in producers.items():
        for _ in range(16):  # chains are three flows long; bound a cycle
            hit = _enclosing(consumers.get(thread, []), t)
            if hit is None or hit[2] not in producers:
                break
            thread, t = producers[hit[2]]
        out.append((thread, t, t, "launch", pid))
    return out


def self_seconds(spans) -> dict:
    """Host self seconds by span name: each span's duration less the
    durations of the spans directly inside it on its thread."""
    out: dict = {}
    by_thread: dict = {}
    for thread, s, e, name, _ in spans:
        by_thread.setdefault(thread, []).append((s, -e, name))
    for items in by_thread.values():
        stack = []  # open spans, outermost first: [start, end, name, child_ns]
        for s, neg_e, name in sorted(items) + [(float("inf"), 0, None)]:
            while stack and stack[-1][1] <= s:
                start, end, done, child = stack.pop()
                out[done] = out.get(done, 0.0) + (end - start - child) * 1e-9
                if stack:
                    stack[-1][3] += end - start
            if name is not None:
                stack.append([s, -neg_e, name, 0])
    return out


def _window(ev):
    from benchmarks.chip import trace as btrace

    return next((s, e) for _, s, e, n, _ in ev["host"] if n == btrace.WINDOW)


def idle_outside(ev, names, others) -> dict:
    """Idle seconds of the window under no span of ``names``, by the
    innermost span of ``others`` open at each gap's middle."""
    from benchmarks.chip import trace as btrace

    w0, w1 = _window(ev)
    ours = sorted((s, e, n) for _, s, e, n, _ in ev["host"] if n in names)
    theirs = sorted((s, e, n) for _, s, e, n, _ in ev["host"] if n in others)
    per_device: dict = {}
    for device, s, e, *_ in ev["device"]:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            per_device.setdefault(device, []).append((s, e))
    busy = btrace.union(next(iter(per_device.values()), []))
    edges = [w0] + [x for pair in busy for x in pair] + [w1]
    out: dict = {}
    for s, e in zip(edges[::2], edges[1::2]):
        mid = (s + e) / 2
        if e > s and btrace._innermost(ours, mid) is None:
            hit = btrace._innermost(theirs, mid)
            name = hit[2] if hit else "none"
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def reduce_program(ev, host, others, kind: str) -> dict:
    """The per-train program-span readings (see the module docstring) of
    one window: ``ev`` its events (``trace.events``), ``host`` its host
    events with their stats (``host_events``), ``others`` the benchmark's
    span names, ``kind`` the device kind."""
    from benchmarks.chip import peaks, work
    from benchmarks.chip import trace as btrace

    w0, w1 = _window(ev)
    spans = [h for h in host if h[3].startswith(PREFIX)]
    names = {sp[3] for sp in spans}
    window = [(None, w0, w1, btrace.WINDOW, None)]
    launched = launches(host)
    red = btrace.reduce_events(
        {"device": ev["device"],
         "host": window + [sp[:4] + (None,) for sp in spans] + launched},
        names,
    )
    theirs = [h for h in ev["host"] if h[3] in others]
    bench = btrace.reduce_events(
        {"device": ev["device"], "host": window + theirs + launched}, others
    )
    inside = [sp for sp in spans if w0 <= sp[1] < w1]
    trains = sum(1 for _, s, _, n, _ in ev["host"]
                 if n == btrace.CLIENT_SPANS[0] and w0 <= s < w1)
    per = 1e3 / max(trains, 1)
    idle = sum(red["idle_s_by_span"].values())
    under = idle - red["idle_s_by_span"].get("none", 0.0)
    peak = peaks.peaks(kind)
    least = sum(
        work.roofline_seconds(
            work.node_step(st["rows"], st["k"], st["degree"], st["groups"],
                           feature=name == NODE_STEPS[0]),
            peak,
        )[0]
        for _, _, _, name, st in inside
        if name in NODE_STEPS
    )
    dev = red["device_s_by_span"]
    node_s = sum(dev.get(n, 0.0) for n in NODE_STEPS + ("repro.kernel.pack",))
    return {
        "trains": trains,
        "idle_under_program_share": under / idle if idle > 0 else None,
        "host_self_ms": {k: v * per for k, v in self_seconds(inside).items()},
        "device_ms": {k: v * per for k, v in dev.items()},
        "benchmark_span_device_ms": {
            k: v * per for k, v in bench["device_s_by_span"].items()
        },
        "idle_ms": {k: v * per for k, v in red["idle_s_by_span"].items()},
        "idle_outside_ms": {
            k: v * per for k, v in idle_outside(ev, names, others).items()
        },
        "grouping_device_ms": dev.get("repro.kernel.group_ids", 0.0) * per,
        "node_roofline_pct": 100.0 * least / node_s if node_s > 0 else None,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import run as harness
    from benchmarks.chip import trace as btrace

    found = {}
    reduce = btrace.reduce

    def reduce_both(path, names):
        import jax

        ev = btrace.events(path)
        found.update(reduce_program(ev, host_events(path), set(names),
                                    jax.devices()[0].device_kind))
        return btrace.reduce_events(ev, names)

    btrace.reduce = reduce_both
    try:
        rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        btrace.reduce = reduce
    print(f"[program-trace] {json.dumps(found)}", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
