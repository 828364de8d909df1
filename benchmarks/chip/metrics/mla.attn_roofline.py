"""The MLA paged decode kernel's share of its roofline over the traced
stretch: ``paged_attn_roofline``'s definition over the events of
``mla_paged_attention``.

Numerator: the least time for the decode attention the traced steps
asked for, the larger of FLOPs / peak and bytes / peak bandwidth, where
a lane with ``kv`` cached tokens attends over kv + 1 in every attention
hop, with the FLOPs and bytes of the architecture module's
``decode_attn_work`` (absorbed MLA: scores over the latent row and the
value product over its first kv_lora_rank columns, for every head; the
latent rows read plus q and out).  Denominator: the summed device time
of the kernel's events (``%mla_paged_attention``), read from the traced
run's profile."""
from benchmarks.chip import xplane
from benchmarks.chip.arith import roofline_s

KERNEL = "mla_paged_attention"
_CACHE = {}


def kernel_s(run):
    """Summed device time of the kernel's events in the traced stretch;
    None for a run with no profile."""
    path = (run.trace or {}).get("file")
    if not path:
        return None
    if path not in _CACHE:
        r = xplane.reduce(xplane.load(path), kernels=(KERNEL,))
        _CACHE[path] = r["kernel_s"][KERNEL]
    return _CACHE[path]


def read(run):
    if run.peaks is None:
        return None
    t = kernel_s(run)
    if not t:
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
    least, _ = roofline_s(flops, nbytes, run.peaks)
    return 100.0 * least / t
