"""90th percentile over the window's finished requests of
(result time - first-token time) / (outputs - 1), in ms."""
from benchmarks.chip.arith import percentile, tpot_s


def read(run):
    v = percentile([t for t in map(tpot_s, run.done) if t is not None], 90)
    return None if v is None else 1e3 * v
