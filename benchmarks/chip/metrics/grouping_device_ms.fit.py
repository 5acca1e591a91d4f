"""grouping_device_ms.fit: device milliseconds per train of the
operations launched inside the benchmark's spans around
``ops.group_ids_device``."""


def value(run):
    trains = [r for r in run.records if r["ok"]]
    if run.trace is None or not trains or not run.calls.get("ops.group_ids_device"):
        return None
    device_s = run.trace["device_s_by_span"].get("ops.group_ids_device", 0.0)
    if device_s <= 0:  # no device operation found in the spans: nothing to read
        return None
    return 1e3 * device_s / len(trains)
