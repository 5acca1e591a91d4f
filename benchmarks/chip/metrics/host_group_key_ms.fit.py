"""host_group_key_ms.fit: host milliseconds per train in the program's
``repro.engine.group_key`` span (the packed GROUP BY key of every node
step, on the host), as ``repro.obs`` times its spans."""

from benchmarks.chip.counters import span_ms_per_train


def value(run):
    return span_ms_per_train(run, "repro.engine.group_key")
