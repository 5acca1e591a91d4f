"""lowered.fit: programs lowered in the window (a shape new to the
process), over every program span the lowering happened under.  0 is a
reading: no program compiled inside the window."""


def value(run):
    before, after = (
        info.get("process", {}).get("lowered")
        for info in (run.service_before, run.service_after)
    )
    if after is None:
        return None
    return sum(after.values()) - sum((before or {}).values())
