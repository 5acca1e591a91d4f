"""train_rows_per_s: joined fact rows covered by every train started in
the window, over the time from the window's start to the end of the last
of them (which runs to completion: nothing is cut or sampled)."""


def value(run):
    trains = [r for r in run.records if r["ok"] and r.get("count") is not None]
    if not trains:
        return None
    end = max(r["done"] for r in trains)
    return sum(r["count"] for r in trains) / (end - run.t0)
