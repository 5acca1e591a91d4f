"""Per-layer metric readers: ``<metric name>.py`` holds ``value(run)``,
which returns the metric or ``None`` where the run holds nothing to read."""


def idle_share(run):
    t = run.trace
    if t is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
