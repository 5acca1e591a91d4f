"""The program's own counters over the window: what the threaded service's
``cache_info()`` reports at both ends of it (``run.service_before`` /
``run.service_after``), the process counters of ``repro.obs`` under
``"process"`` among them.  A program that does not report a counter reads
``None``, never 0."""


def delta(run, *keys):
    """The growth over the window of the counter at ``keys`` (a path into
    ``cache_info()``), or ``None`` where the program does not report it."""
    before, after = run.service_before, run.service_after
    for key in keys:
        if not isinstance(after, dict) or key not in after:
            return None
        before = before.get(key, 0) if isinstance(before, dict) else 0
        after = after[key]
    return after - before


def trains(run) -> int:
    """Trains answered in the window."""
    return sum(1 for r in run.records if r["ok"])


def span_ms_per_train(run, span: str):
    """Host self milliseconds of the program span ``span`` per train."""
    secs = delta(run, "process", "span_self_s", span)
    if secs is None or not trains(run):
        return None
    return 1e3 * secs / trains(run)
