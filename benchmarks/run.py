"""Benchmark runner: ``PYTHONPATH=src python -m benchmarks.run [--smoke]
[--suite NAME ...]``.

One module per paper table/figure (+ substrate benches):

  table2_factorized_versions   — Table 2 (v1–v6, fact vs noPre)
  figure9_engines              — Fig. 9 (in-memory vs row-engine proxy)
  figure23_aggregates          — Figs. 2–3 (COUNT / SUM over factorization)
  union_commutativity_scaling  — Prop. 4.1 as the distribution rule
  incremental_retrain_after_append — retrain cost after appends (AC/DC)
  streaming_ingest             — lazy vs eager append p50/p99 latency +
                                 retrain staleness under sustained writes
  categorical_vs_onehot        — sparse categorical cofactors vs one-hot
  view_cache_cold_warm_append  — persistent view cache: warm batches +
                                 retrain-after-append vs invalidate-all
  serve_coalescing             — multi-tenant service: coalesced vs
                                 private traversals under Zipfian overlap,
                                 plus degraded-mode throughput retention
                                 under injected faults (fault-rate sweep)
  polynomial_extension         — §6 outlook (beyond-paper degree-d)
  traversal_nodes / _end_to_end — fused vs unfused traversal nodes with
                                 roofline-audited bandwidth fractions
  kernel_hotspots              — hot-aggregate arithmetic intensity
  lm_smoke_steps               — assigned-arch step timings (smoke, CPU)

``--smoke`` runs every selected suite at tiny fixed-seed sizes (< 2 min
total) — the CI benchmark-smoke job's mode.  ``--suite NAME`` (repeatable)
filters to named suites; an unknown name errors listing the valid ones.
JSON mirrors land in benchmarks/results/, plus a ``summary.json`` with
per-suite status.

Exit code is non-zero when ANY suite raises (each failure prints its full
traceback); CI gates on it.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import traceback

from .common import RESULTS_DIR, stopwatch

#: slug (the --suite name) -> (display title, bench module)
SUITES = [
    ("factorized", "table2 (factorized versions)", "bench_factorized"),
    ("engines", "figure9 (engine comparison)", "bench_engines"),
    ("aggregates", "figures2-3 (aggregates)", "bench_aggregates"),
    ("scaling", "union commutativity scaling", "bench_scaling"),
    ("incremental", "incremental retrain after append", "bench_incremental"),
    ("ingest", "streaming ingest producer/consumer", "bench_ingest"),
    ("categorical", "categorical vs one-hot", "bench_categorical"),
    ("view_cache", "view cache cold/warm/append", "bench_view_cache"),
    ("serve", "multi-tenant serve coalescing", "bench_serve"),
    ("polynomial", "polynomial extension", "bench_polynomial"),
    ("traversal", "fused traversal nodes (roofline)", "bench_traversal"),
    ("kernels", "kernel hotspots", "bench_kernels"),
    ("lm", "lm smoke steps", "bench_lm"),
]


def suite_names() -> list:
    return [slug for slug, _, _ in SUITES]


def default_suites(only=None):
    """(title, fn) pairs for the selected suites (all when ``only`` is
    falsy).  Unknown names raise ValueError listing the valid slugs —
    before any bench module is imported."""
    if only:
        unknown = sorted(set(only) - set(suite_names()))
        if unknown:
            raise ValueError(
                f"unknown suite(s) {', '.join(unknown)} — valid suites: "
                f"{', '.join(suite_names())}"
            )
        selected = [s for s in SUITES if s[0] in set(only)]
    else:
        selected = SUITES
    return [
        (title, importlib.import_module(f".{mod}", __package__).main)
        for _, title, mod in selected
    ]


def run_suites(suites, smoke: bool = False) -> int:
    """Run each (name, fn) suite; fn takes ``smoke``.  Failures never stop
    the sweep but always fail the run: every exception is reported with a
    full traceback, recorded in summary.json, and turned into exit code 1."""
    summary = []
    for name, fn in suites:
        print(f"\n#### {name}")
        try:
            with stopwatch() as sw:
                fn(smoke=smoke)
            print(f"#### {name}: ok ({sw.seconds:.1f}s)")
            summary.append(
                {"suite": name, "status": "ok", "seconds": sw.seconds}
            )
        except Exception:
            traceback.print_exc()
            print(f"#### {name}: FAILED ({sw.seconds:.1f}s)")
            summary.append(
                {
                    "suite": name,
                    "status": "failed",
                    "seconds": sw.seconds,
                    "error": traceback.format_exc(limit=20),
                }
            )
    failures = sum(1 for s in summary if s["status"] != "ok")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "summary.json"), "w") as f:
        json.dump({"smoke": smoke, "suites": summary}, f, indent=2)
    print(
        f"\n[benchmarks] {len(summary) - failures}/{len(summary)} suites ok"
        + (" (smoke)" if smoke else "")
    )
    return 1 if failures else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="tiny fixed-seed sizes for CI gating (< 2 min total)",
    )
    parser.add_argument(
        "--suite",
        action="append",
        metavar="NAME",
        help="run only the named suite (repeatable); one of: "
        + ", ".join(suite_names()),
    )
    args = parser.parse_args(argv)
    try:
        suites = default_suites(args.suite)
    except ValueError as err:
        print(f"[benchmarks] {err}", file=sys.stderr)
        return 2
    return run_suites(suites, smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
