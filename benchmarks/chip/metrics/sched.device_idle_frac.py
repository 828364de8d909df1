"""Device idle time in the traced stretch whose innermost host span is a
scheduler span (``sched.admit``, ``sched.form_groups``), over the
stretch."""
from benchmarks.chip import program_trace


def read(run):
    return program_trace.idle_frac(run, "sched.")
