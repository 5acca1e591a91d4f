#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload favorita.fit --seed 7 \\
        --seconds 51 --trace 0

The cell, its configuration, its traffic mix and its metrics are read by
name from ``BENCHMARK.json`` at the checkout's root; each lives in a file
of its own under this directory:

- ``configs/<config>.json`` the deployment's sizes, variable order, join
  edges and cuts, and ``configs/<config>.py`` its generator;
- ``mixes/<traffic>.json`` the mix's parameters, with the names of the
  driver that sends it (``drivers/<driver>.py``) and of the check that
  compares its answers (``checks/<check>.py``);
- ``endtoend/<metric>.py`` and ``metrics/<metric>.py`` one reader per
  metric;
- ``limits/<cell>.json`` the limits of the numbers ``correct`` compares;
- ``tests/rehearsal/<config>.json`` the tiny sizes the CPU tests lay over
  the configuration's (a run here never reads them).

Set-up generates the data from ``--seed``, builds the ``Store`` and a
threaded ``FactorizedService(backend="jax")`` and runs the mix's warm
steps; the window then runs the mix for ``--seconds``.  Once it has closed
every train it answered is compared with the fp64 reference
(``reference.py``).  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, are the last lines of
standard error and the last key of that object.

The run needs a TPU with as many chips as the cell asks for: without one
it exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NO_CHIP = 3


def load_json(*parts) -> dict:
    """The JSON file ``parts`` under this directory (or an absolute path)."""
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """The module in the file ``parts`` under this directory, by path (a
    metric's name may hold dots)."""
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts).replace(".", "_").replace("/", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_plan(bench: dict, workload: str) -> dict:
    """The cell ``workload`` with its configuration entry and the metrics
    it reports."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]

    def reported(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": {c["name"]: c for c in bench["configs"]}[cell["config"]],
        "end_to_end": reported(bench["end_to_end"]),
        "per_layer": reported(bench["per_layer"]),
    }


def require_chip(chips: int):
    """The devices, or exit ``NO_CHIP`` when JAX finds no TPU or fewer
    chips than the cell asks for."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    print(
        f"[device] platform={platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)} jax={jax.__version__}",
        file=sys.stderr,
        flush=True,
    )
    if platform != "tpu":
        print(f"run.py: no TPU (JAX sees {platform}); nothing run", file=sys.stderr)
        raise SystemExit(NO_CHIP)
    if len(devs) < chips:
        print(f"run.py: the cell asks for {chips} chips, {len(devs)} found",
              file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return devs[:chips]


def catalog(cfg: dict, data: dict, view_cache_bytes: int):
    """The program's ``Store`` over the generated relations and the
    configuration's variable order."""
    from repro.core.store import Store
    from repro.core.variable_order import VariableOrder

    def node(spec):
        if isinstance(spec, str):
            return VariableOrder.leaf(spec[1:])
        name, children = spec
        kids = [node(c) for c in children]
        return VariableOrder.intercept(kids) if name == "T" else VariableOrder(name, kids)

    store = Store(
        [relation_of(name, r) for name, r in data["relations"].items()],
        view_cache_bytes=view_cache_bytes,
    )
    return store, node(cfg["vorder"])


def relation_of(name: str, arrays: dict):
    """The program's ``Relation`` of generated ``{"keys", "values"}`` arrays."""
    from repro.core.relation import Relation

    return Relation.from_columns(name, arrays["keys"], arrays["values"])


class CompileCount:
    """Programs traced and lowered (``lowered``: a shape new to the
    process) and compiled by XLA (``compiled``: not found in the compile
    cache) while ``on`` is set."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.on, self.lowered, self.compiled = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if self.on and name == self.LOWER:
            self.lowered += 1

    def _event(self, name, **kw):
        if self.on and name == self.MISS:
            self.compiled += 1


def compile_cache() -> str:
    """The program's persistent compile cache at the checkout's fixed path,
    keeping every program (JAX keeps by default only those that took a
    second or more to compile), so that a checkout's first run of a cell
    compiles and the later ones load."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    path = use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(tag: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{tag}] {body}", file=sys.stderr, flush=True)


def run_cell(args, devices, plan: dict) -> dict:
    """Set-up, the window, the comparison; returns the result object."""
    import jax
    from jax.profiler import TraceAnnotation

    from benchmarks.chip import probe
    from benchmarks.chip import trace as btrace
    from repro.kernels import ops
    from repro.serve.factorized import FactorizedService

    cell = plan["cell"]
    cfg = load_json(ROOT, plan["config"]["file"])
    generator = load_module("configs", cell["config"] + ".py")
    mix = load_json("mixes", cell["traffic"] + ".json")
    limits = load_json("limits", cell["name"] + ".json")
    if not (ops.default_impl() == "pallas" and ops.fast_device_grouping()):
        raise RuntimeError("node kernels or device grouping would be off here")

    t = time.perf_counter()
    data = generator.generate(cfg, args.seed)
    store, vorder = catalog(cfg, data, mix["view_cache_bytes"])
    log("setup", step="data", seconds=f"{time.perf_counter() - t:.2f}",
        rows={n: len(next(iter(r["keys"].values()))) for n, r in data["relations"].items()})

    svc = FactorizedService(store, backend="jax").start()
    hooks = probe.Hooks()
    if args.trace:
        hooks.spans()
    hooks.results(svc)
    driver = load_module("drivers", mix["driver"] + ".py")
    loop = driver.Driver(svc, mix, cfg, data, generator, vorder, hooks, relation_of)
    compiles = CompileCount()
    try:
        t = time.perf_counter()
        warm = loop.run(steps=mix["warm_steps"])
        bad = [r for r in warm if not r["ok"]]
        if bad:
            raise RuntimeError(f"a warm-up request failed: {bad[0].get('error')}")
        if not hooks.engines or not all(u and g for u, g in hooks.engines):
            raise RuntimeError(f"an engine ran without node kernels or device "
                               f"grouping: {hooks.engines}")
        log("setup", step="warm", seconds=f"{time.perf_counter() - t:.2f}",
            steps=len(warm), view_cache_bytes=store.view_cache.bytes,
            view_cache_budget=mix["view_cache_bytes"])
        hooks.nodes.clear()
        hooks.calls.clear()
        before = (store.node_visits, store.passes)
        info0 = svc.cache_info()
        setup_s = time.perf_counter() - T_START
        compiles.on = True
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
        if trace_dir:
            jax.profiler.start_trace(trace_dir, profiler_options=btrace.options())
        t0 = time.perf_counter()
        with TraceAnnotation(btrace.WINDOW):
            records = loop.run(seconds=args.seconds)
        t_end = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
        compiles.on = False
        visits, passes = store.node_visits - before[0], store.passes - before[1]
        info1 = svc.cache_info()
        stats = devices[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        svc.stop()
        hooks.close()
    log("window", seconds=f"{t_end - t0:.2f}", steps=len(records),
        node_visits=visits, passes=passes, lowered_in_window=compiles.lowered,
        compiled_in_window=compiles.compiled,
        step_s=",".join(f"{r['done'] - r['start']:.2f}" for r in records if r["ok"]),
        memory_peak_bytes=peak)

    reduced = None
    if trace_dir:
        t = time.perf_counter()
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        reduced = btrace.reduce(paths[0], set(probe.SPANS) | set(btrace.CLIENT_SPANS))
        log("trace", seconds=f"{time.perf_counter() - t:.2f}",
            xplane_bytes=os.path.getsize(paths[0]), busy_s=reduced["busy_s"],
            window_s=reduced["window_s"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    appended = list(loop.appended)
    del svc, store, loop
    gc.collect()
    checks = load_module("checks", mix["check"] + ".py").compare(
        cfg, data, appended, records, limits)
    run = types.SimpleNamespace(
        records=records, t0=t0, t_end=t_end, setup_s=setup_s, trace=reduced,
        nodes=list(hooks.nodes), calls=dict(hooks.calls), node_visits=visits,
        lowered=compiles.lowered, compiled=compiles.compiled,
        kind=devices[0].device_kind, service_before=info0, service_after=info1,
        cfg=cfg, mix=mix,
    )
    kind = "per_layer" if args.trace else "end_to_end"
    folder = "metrics" if args.trace else "endtoend"
    metrics = {}
    for m in plan[kind]:
        value = load_module(folder, m["name"] + ".py").value(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for r in records if not r["ok"])
    attempted = sum(1 + len(r["appends"]) for r in records)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        plan = cell_plan(json.load(f), args.workload)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    devices = require_chip(plan["cell"]["chips"])
    compile_cache()
    out = run_cell(args, devices, plan)
    for name, c in out["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
