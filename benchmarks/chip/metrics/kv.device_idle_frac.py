"""Device idle time in the traced stretch whose innermost host span is a
KV manager span (``kv.alloc``, ``kv.write_prefill``), over the stretch."""
from benchmarks.chip import program_trace


def read(run):
    return program_trace.idle_frac(run, "kv.")
