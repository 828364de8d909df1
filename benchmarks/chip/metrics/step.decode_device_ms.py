"""Mean device time of one decode megastep (a ``jit_chain_decode`` or
``jit_chain_decode_spec`` module event wholly inside the traced
stretch), in ms."""
from benchmarks.chip import program_trace


def read(run):
    r = program_trace.of_run(run)
    if r is None:
        return None
    d = [t for m in program_trace.DECODE
         for t in r["module_events"].get(m, ())]
    return 1e3 * sum(d) / len(d) if d else None
