"""Share of the traced stretch in which no operation ran on the device:
1 - (union of device-op intervals) / (stretch)."""


def read(run):
    if not run.trace:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
