"""Device idle time in the traced stretch whose innermost host span is an
executor span (``exec.prefill``, ``exec.dispatch``, ``exec.sync``,
``exec.make_state``), over the stretch."""
from benchmarks.chip import program_trace


def read(run):
    return program_trace.idle_frac(run, "exec.")
