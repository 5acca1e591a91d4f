"""host_join_ms.fit: host milliseconds per train in the program's
``repro.engine.join`` span (join keys and the sort-merge join of two
views, on the host), as ``repro.obs`` times its spans."""

from benchmarks.chip.counters import span_ms_per_train


def value(run):
    return span_ms_per_train(run, "repro.engine.join")
