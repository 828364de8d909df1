"""Device time of the prefill K/V scatter (``jit_kv_write_prefill``, the
whole-slab copy around it included) over device busy time in the traced
stretch."""
from benchmarks.chip import program_trace


def read(run):
    return program_trace.module_frac(run, program_trace.KV_WRITE)
