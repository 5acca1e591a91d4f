"""transfer_mb.fit: megabytes copied between host and device per train,
both ways (``h2d_bytes`` + ``d2h_bytes`` of ``repro.obs``)."""

from benchmarks.chip.counters import delta, trains


def value(run):
    up = delta(run, "process", "h2d_bytes")
    down = delta(run, "process", "d2h_bytes")
    if up is None or down is None or not trains(run):
        return None
    return (up + down) / 1e6 / trains(run)
