"""KV pages reserved over pages holding cached tokens, averaged over the
window's steps: the pools' used pages against ceil(kv_len / page) for
every attention hop of every resident request."""
import math


def read(run):
    ratios = []
    for s in run.steps:
        used = sum(h * max(1, math.ceil(kv / s["page_size"]))
                   for _, kv, h in s["lanes"])
        if used:
            ratios.append(s["reserved_pages"] / used)
    return sum(ratios) / len(ratios) if ratios else None
