"""Output tokens of all requests that fell inside the window, over the
window's length."""
from benchmarks.chip.arith import tokens_in_window


def read(run):
    return sum(tokens_in_window(r, run.w0, run.w1)
               for r in run.records.values()) / (run.w1 - run.w0)
