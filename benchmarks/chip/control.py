#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's numbers over
many seeds, and the control's.

    python3 benchmarks/chip/control.py --workload favorita.fit \\
        --seeds 100 101 102 --bf16-seeds 100 101 102 --seconds 5

Runs the cell as ``run.py`` does (set-up, a short window, the comparison)
once per seed in one process, so compilation is paid once.  With
``--bf16-seeds`` it then runs those seeds again with the node kernels'
one-hot dots in a single bf16 pass (``repro.kernels.segment_view.
BF16_PASS``, the program's own lower-precision path): the control, which
has to come out not correct.  Prints one JSON line per run and exits 1
when a sound run is not correct or a control run is.  The benchmark's own
runs never run it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"),
                os.path.dirname(os.path.dirname(HERE))]

from benchmarks.chip import run as harness  # noqa: E402


@contextlib.contextmanager
def bf16_node_kernels():
    """The node kernels with their one-hot dots in one bf16 pass (bf16
    operands, f32 accumulation) for as long as the block runs."""
    from repro.kernels import segment_view as sv

    calls = (sv.segment_view_kernel_call, sv.segment_reduce_kernel_call)
    sv.BF16_PASS = True
    for f in calls:
        f.clear_cache()  # retraced with the flag read at trace time
    try:
        yield
    finally:
        sv.BF16_PASS = False
        for f in calls:
            f.clear_cache()


def readings(workload, seeds, bf16_seeds, seconds, devices=None, plan=None):
    """One result per run: ``(seed, control, result)``; sound runs first."""
    if plan is None:
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
            plan = harness.cell_plan(json.load(f), workload)
    if devices is None:
        devices = harness.require_chip(plan["cell"]["chips"])
    out = []
    for control, group in ((False, seeds), (True, bf16_seeds)):
        for seed in group:
            args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
            ctx = bf16_node_kernels() if control else contextlib.nullcontext()
            with ctx:
                res = harness.run_cell(args, devices, plan)
            out.append((seed, control, res))
            print(json.dumps({"seed": seed, "control": control,
                              "correct": res["correct"],
                              "checks": res["checks"],
                              "metrics": res["metrics"]}), flush=True)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--bf16-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        plan = harness.cell_plan(json.load(f), args.workload)
    devices = harness.require_chip(plan["cell"]["chips"])
    harness.compile_cache()
    out = readings(args.workload, args.seeds, args.bf16_seeds, args.seconds,
                   devices, plan)
    ok = all(res["correct"] != control for _, control, res in out)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
