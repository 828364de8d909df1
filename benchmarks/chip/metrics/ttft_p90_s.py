"""90th percentile over the window's finished requests of due time to
first token back on the host."""
from benchmarks.chip.arith import percentile, ttft_s


def read(run):
    return percentile([ttft_s(r) for r in run.done], 90)
