"""The ridge check: every train answered in the window against the fp64
reference of the catalog state it had to read.

A mix names its check (``"check": "ridge"``); a later kind of request is
a check file of its own beside this one, with the same ``compare``.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip.reference import Reference, fitted_gap


def compare(cfg: dict, data: dict, appended: list, records: list, limits: dict):
    """The numbers ``correct`` rests on, each ``(value, limit)``: the widest
    fitted-value gap of a train's θ, the largest gap of its cofactor count
    from the reference join's row count, and the requests never answered."""
    t = time.perf_counter()
    ref = Reference(cfg, data["relations"])
    for batch in appended:
        ref.append(batch)
    scaled = {}
    theta_gap, count_gap, unanswered = 0.0, 0.0, 0
    for rec in records:
        if not rec["ok"] or rec.get("count") is None:
            unanswered += 1
            continue
        key = (rec["state"], tuple(rec["features"]))
        state = ref.states[rec["state"]]
        if key not in scaled:
            scaled[key] = ref.scaled(state, rec["features"])
        r, a, theta_ref = scaled[key]
        gap = fitted_gap(np.asarray(rec["theta"]), theta_ref, r, a)
        theta_gap = max(theta_gap, gap if np.isfinite(gap) else float("inf"))
        count_gap = max(count_gap, abs(rec["count"] - state.count))
    print(f"[reference] seconds={time.perf_counter() - t:.2f} "
          f"states={len(ref.states)} trains={len(records)}", file=sys.stderr, flush=True)
    return {
        "theta_gap": (theta_gap, limits["theta_gap"]["limit"]),
        "count_gap": (count_gap, limits["count_gap"]["limit"]),
        "unanswered": (unanswered, limits["unanswered"]["limit"]),
    }
