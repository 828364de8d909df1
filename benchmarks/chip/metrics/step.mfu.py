"""The whole step's share of the chip's bf16 peak over the window: model
FLOPs of the prompt and decode tokens processed, over the summed wall
time of ``engine.step()`` calls (host clock) x peak.  A decode token
costs 2 x the matmul parameters of its chain plus attention over its
context; a prompt costs that for every token with causal attention, and
the head once."""
from benchmarks.chip.arith import decode_token_flops, prefill_flops
from benchmarks.chip.model import chain_matmul_params


def read(run):
    if run.peaks is None:
        return None
    if not run.steps:
        return None
    cfg = run.cell.cfg
    params = {t["name"]: chain_matmul_params(cfg, t) for t in cfg["tenants"]}
    head = cfg["hidden_size"] * cfg["vocab_size"]
    flops = wall = 0.0
    for s in run.steps:
        wall += s["t1"] - s["t0"]
        for app, kv, hops in s["lanes"]:
            flops += decode_token_flops(cfg, params[app], [kv + 1] * hops)
    for r in run.records.values():
        if r["t_first"] is not None and run.w0 <= r["t_first"] < run.w1:
            flops += prefill_flops(cfg, params[r["app"]], head, r["prompt_len"],
                                   cfg["num_hidden_layers"])
    return 100.0 * flops / (wall * run.peaks["bf16_flops"]) if wall else None
