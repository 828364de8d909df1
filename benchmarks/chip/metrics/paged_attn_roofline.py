"""Paged attention's share of its roofline over the traced stretch.

Numerator: the least time for the decode attention the traced steps
asked for, the larger of FLOPs / peak and bytes / peak bandwidth, where
a lane with ``kv`` cached tokens attends over kv + 1 and moves their
live K and V plus q and out, in every attention hop.  Pages the kernel
happens to read beyond that do not count.  Denominator: the summed
device time of the kernel's events.  Decode attention is memory bound at
these shapes (1 to 4 FLOPs a byte against the chip's 240).  The FLOPs
and bytes of one hop come from the configuration's architecture module
(``decode_attn_work`` in ``archs/<model_type>.py``)."""
from benchmarks.chip.arith import roofline_s


def read(run):
    if run.peaks is None:
        return None
    if not run.trace or not run.trace["kernel_s"].get("paged_attention"):
        return None
    a, b = run.trace["host_window"]
    cfg, arch = run.cell.cfg, run.cell.arch
    flops = nbytes = 0.0
    for s in run.steps:
        if s["t0"] >= a and s["t1"] <= b:
            for _, kv, hops in s["lanes"]:
                f, n = arch.decode_attn_work(cfg, kv + 1)
                flops += hops * f
                nbytes += hops * n
    if not flops:
        return None
    t, _ = roofline_s(flops, nbytes, run.peaks)
    return 100.0 * t / run.trace["kernel_s"]["paged_attention"]
