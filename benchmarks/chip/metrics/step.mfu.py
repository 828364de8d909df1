"""The whole step's share of the chip's bf16 peak over the window: model
FLOPs of the prompt and decode tokens processed, over the summed wall
time of ``engine.step()`` calls (host clock) x peak.  A decode token
costs 2 x the matmul parameters of its chain plus attention over its
context; a prompt costs that for every token with causal attention, and
the head once.  The counts come from the configuration's architecture
module (``archs/<model_type>.py``)."""


def read(run):
    if run.peaks is None:
        return None
    if not run.steps:
        return None
    cfg, arch = run.cell.cfg, run.cell.arch
    flops = wall = 0.0
    for s in run.steps:
        wall += s["t1"] - s["t0"]
        for app, kv, hops in s["lanes"]:
            flops += arch.decode_token_flops(cfg, app, [kv + 1] * hops)
    for r in run.records.values():
        if r["t_first"] is not None and run.w0 <= r["t_first"] < run.w1:
            flops += arch.prefill_flops(cfg, r["app"], r["prompt_len"])
    return 100.0 * flops / (wall * run.peaks["bf16_flops"]) if wall else None
