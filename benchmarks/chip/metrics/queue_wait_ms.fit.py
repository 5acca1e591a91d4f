"""queue_wait_ms.fit: mean milliseconds a read waited in the service's
queue, from its submission to the drain cycle that popped it."""

from benchmarks.chip.counters import delta


def value(run):
    waited = delta(run, "queue_wait_s")
    waits = delta(run, "queue_waits")
    if waited is None or not waits:
        return None
    return 1e3 * waited / waits
