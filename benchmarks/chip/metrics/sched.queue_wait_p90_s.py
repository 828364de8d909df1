"""90th percentile of the engine's own queue wait (admission - submit,
the program's ``queue_wait_s`` span) over the window's finished
requests."""
from benchmarks.chip.arith import percentile


def read(run):
    return percentile([r["queue_wait_s"] for r in run.done], 90)
