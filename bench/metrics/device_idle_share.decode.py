"""Device: 1 - (union of device operation intervals / traced window)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
