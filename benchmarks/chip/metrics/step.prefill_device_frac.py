"""Device time of the prefill programs (``jit_chain_prefill``,
``jit_block_prefill``) over device busy time in the traced stretch."""
from benchmarks.chip import program_trace


def read(run):
    return program_trace.module_frac(run, program_trace.PREFILL)
