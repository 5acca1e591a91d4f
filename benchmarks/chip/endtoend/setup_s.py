"""setup_s: seconds from process start to the first timed request (data
generation, the store, the service and the warm steps; compilation too
where the compile cache is cold)."""


def value(run):
    return run.setup_s
