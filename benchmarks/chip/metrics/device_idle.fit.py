"""device_idle.fit: % of the traced window in which no operation ran on
the device (1 - the union of device-op intervals over the window)."""

from benchmarks.chip.metrics import idle_share


def value(run):
    return idle_share(run)
