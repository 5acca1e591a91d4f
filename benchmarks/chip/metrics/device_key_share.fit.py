"""device_key_share.fit: the share of grouped rows whose GROUP BY key was
packed on the device, Δ``group_rows_device`` / (Δ``group_rows_device`` +
Δ``group_rows_host``) of ``repro.obs``, in percent."""

from benchmarks.chip.counters import delta


def value(run):
    device = delta(run, "process", "group_rows_device")
    host = delta(run, "process", "group_rows_host")
    if device is None or host is None or device + host == 0:
        return None
    return 100.0 * device / (device + host)
