"""The on-chip benchmark's harness on the CPU, at a tiny size.

The run loop is driven end to end with the Pallas kernel in interpret
mode; the chip check is skipped by calling ``measure`` directly."""
from __future__ import annotations

import dataclasses
import json
import math
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import arith, control, harness, model, reference  # noqa: E402
from benchmarks.chip import run as bench_run  # noqa: E402
from benchmarks.chip import traffic  # noqa: E402
from benchmarks.chip.archs import llama  # noqa: E402
from benchmarks.chip.metrics import reader  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2 ** 40 + 3  # wider than 32 bits: seeds may be
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=256)


OPEN_MIX = {"loop": "open", "rate_rps": 4.0, "apps": {"pick": "zipf", "s": 1.0},
            "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                       "min": 8, "max": 48},
            "output": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                       "min": 2, "max": 12},
            "warmup_s": 1.0, "drain_cap_s": 60.0, "traced_s": 1.0}


def tiny_cell() -> harness.Cell:
    """Mistral's configuration file at test widths, with one tenant of
    every kind, under an open-loop mix at test lengths."""
    cfg = model.load_config("mistral-7b-v0.3-l8")
    cfg.update(TINY)
    keep = ("base", "lora-0", "adapter-0", "bitfit-0", "fpft-0")
    cfg["tenants"] = [t for t in cfg["tenants"] if t["name"] in keep]
    for t in cfg["tenants"]:
        if t["kind"] == "fpft":
            t["layers"] = [1]
    cfg["engine"] = dict(max_active=8, max_block_batch=4, page_size=16,
                         num_pages=200)
    return harness.Cell("tiny", cfg, json.loads(json.dumps(OPEN_MIX)))


def measure(cell, impl="ref", seconds=2.0):
    import jax

    return bench_run.measure(cell, seed=SEED, seconds=seconds, trace=False,
                             bench=BENCH, device=jax.devices()[0],
                             attn_impl=impl, log=lambda m: None)


# -- the run loop -----------------------------------------------------------


def closed_cell(mix: str, **kw) -> harness.Cell:
    """The tiny cell under one of the closed-loop mixes, at test lengths."""
    cell = tiny_cell()
    cell.mix = traffic.load_mix(ROOT / f"benchmarks/chip/traffic/{mix}.json")
    cell.mix.update(kw)
    cell.mix["prompt"].update(median=24, min=8, max=48)
    return cell


def test_cell_loop_runs_and_is_correct_in_interpret_mode():
    out = measure(tiny_cell(), impl="interpret")
    assert out["correct"], out["compared"]
    assert out["attempted"] == 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "ttft_p90_s", "tpot_p50_ms",
                                   "tpot_p90_ms", "out_tok_s"}
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu"


def test_replayed_closed_loop_runs_in_lockstep_and_is_correct():
    cell = closed_cell("longdoc-replay", clients=8)
    cell.mix["output"].update(min=6, max=6)
    out = measure(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["out_tok_s"]["value"] > 0


def test_clients_out_of_step_compile_nothing_in_the_window():
    """One client per app, each with its own output length: the clients
    drift apart, and set-up has still warmed every shape the window uses."""
    cell = closed_cell("multiapp-closed", clients=5)
    cell.mix["output"].update(median=6, min=2, max=12)
    run, _, engine = harness.run_cell(cell, seed=SEED, seconds=3.0,
                                      attn_impl="ref", log=lambda m: None)
    harness.free(engine)
    assert run.compiles == 0
    assert len({r["out_len"] for r in run.sample}) > 1
    assert len(run.done) == run.attempted > 0
    # admissions fall between other clients' decode steps
    assert len({round(r["t_submit"], 6) for r in run.sample}) > 1


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_the_pool_holds_every_replayed_client_at_once(cell):
    """The pool holds at least ``max_active`` whole-lifetime reservations
    at the engine's longest context on every attention hop (the engine's
    own default size), so no admission waits for pages, and every client
    fits ``max_active``."""
    c = harness.load_cell(cell, ROOT / "BENCHMARK.json")
    mix, e = c.mix, c.cfg["engine"]
    hops = c.cfg["num_hidden_layers"]
    pages = math.ceil(traffic.longest_context(mix) / e["page_size"])
    assert e["num_pages"] >= e["max_active"] * hops * pages + 1
    assert mix["clients"] <= e["max_active"]


def _break(monkeypatch, fault: str):
    """Plant one fault in the timed path."""
    import repro.serving.executor as ex
    from repro.serving.kv_pool import KVPool

    if fault == "token_altered":
        real = ex.chain_decode_fused

        def altered(*a, **k):
            nxt, *rest = real(*a, **k)
            return ((nxt + 1) % 256, *rest)

        monkeypatch.setattr(ex, "chain_decode_fused", altered)
    elif fault == "kv_unwritten":
        monkeypatch.setattr(KVPool, "write_prefill", lambda *a, **k: None)


@pytest.mark.parametrize("fault", ["token_altered", "kv_unwritten"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    out = measure(tiny_cell())
    assert not out["correct"]
    gap = out["compared"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_float8_control_is_not_correct():
    """The control (the reference in float8) in the program's place: the
    gap of its first choices is over the limit the program keeps to."""
    cell = tiny_cell()
    served = []
    for seed in (1, 2, 3):
        served.append(control.read_seed(cell, seed, 2.0, attn_impl="ref",
                                        log=lambda m: None))
    limit = cell.cfg["check"]["widest_logit_gap"]
    assert all(g["served"] <= limit and g["correct"] for g in served)
    assert max(g["control"] for g in served) > limit
    assert not all(g["control_correct"] for g in served)


def test_the_sample_holds_the_longest_request_and_every_tenant_kind():
    cfg = model.load_config("mistral-7b-v0.3-l8")
    kinds = {t["name"]: t["kind"] for t in cfg["tenants"]}
    done = [{"rid": i, "app": a, "prompt_len": 100 + i, "n_out": 64}
            for i, a in enumerate(list(kinds) * 3)]
    for seed in (1, 2, SEED):
        got = reference.pick_sample(done, seed, 128, kinds)
        assert got[0]["rid"] == len(done) - 1
        assert {kinds[r["app"]] for r in got} == set(kinds.values())
        assert len({r["rid"] for r in got}) == len(got)


# -- found by name ------------------------------------------------------------


# the llama layer under width keys of its own: only its module reads them
TOY_KEYS = {"width": "hidden_size", "ffn_width": "intermediate_size",
            "heads": "num_attention_heads", "kv_groups": "num_key_value_heads",
            "head_width": "head_dim", "depth": "num_hidden_layers"}
TOY_ARCH = f"""from benchmarks.chip.archs import llama

KEYS = {TOY_KEYS!r}


def _llama(cfg):
    return {{KEYS.get(k, k): v for k, v in cfg.items()}}


def make_weights(cfg, seed):
    return llama.make_weights(_llama(cfg), seed)


def build_zoo(cfg, weights, log=None):
    return llama.build_zoo(_llama(cfg), weights, log)


def logits(cfg, weights, app, seq, positions, low=False):
    return llama.logits(_llama(cfg), weights, app, seq, positions, low)


def decode_token_flops(cfg, app, contexts):
    return llama.decode_token_flops(_llama(cfg), app, contexts)


def prefill_flops(cfg, app, prompt_len):
    return llama.prefill_flops(_llama(cfg), app, prompt_len)


def decode_attn_work(cfg, context):
    return llama.decode_attn_work(_llama(cfg), context)
"""


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A configuration of a new ``model_type`` is one more file under
    ``archs/``: no llama key is read outside it, from ``load_cell``
    through ``build``, the run loop, the reference check and ``step.mfu``."""
    here = tmp_path / "chip"
    for d in ("configs", "traffic", "metrics", "archs"):
        (here / d).mkdir(parents=True)
    tiny = tiny_cell().cfg
    to_toy = {v: k for k, v in TOY_KEYS.items()}
    cfg = {to_toy.get(k, k): v for k, v in tiny.items()}
    cfg["model_type"] = "toy"
    (here / "configs/new-model.json").write_text(json.dumps(cfg))
    (here / "configs/unknown.json").write_text(json.dumps(
        dict(cfg, model_type="nowhere")))
    (here / "archs/toy.py").write_text(TOY_ARCH)
    (here / "traffic/new-mix.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 1.0, "apps": {"pick": "even"},
         "prompt": {"dist": "uniform", "min": 8, "max": 16},
         "output": {"dist": "uniform", "min": 2, "max": 4}}))
    (here / "metrics/new.metric.py").write_text(
        "def read(run):\n    return run.seconds * 2\n")
    bench = dict(BENCH, workloads=[
        {"name": f"{c}.new-mix", "config": c, "traffic": "new-mix",
         "chips": 1, "why": "x"} for c in ("new-model", "unknown")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("new-model.new-mix", tmp_path / "BENCHMARK.json",
                             here=here)
    assert cell.cfg["name"] == "new-model" and cell.mix["rate_rps"] == 1.0
    assert Path(cell.arch.__file__) == (here / "archs/toy.py").resolve()
    assert not set(TOY_KEYS.values()) & set(cell.cfg)
    run = harness.RunData(cell=cell, seconds=3.0)
    assert reader("new.metric", here / "metrics")(run) == 6.0
    with pytest.raises(KeyError, match=re.escape(str(here / "archs/nowhere.py"))):
        harness.load_cell("unknown.new-mix", tmp_path / "BENCHMARK.json",
                          here=here)

    quiet = lambda m: None  # noqa: E731
    weights, _, engine = harness.build(cell, SEED, "ref", log=quiet)
    drv = harness.Driver(engine, cell, SEED, run, log=quiet)
    drv.sample_steps = True
    t0 = drv.clock()
    drv.schedule("window", t0, run.seconds)
    drv.loop(lambda now: now >= t0 + 120 or (
        now >= t0 + run.seconds and len(run.done) == run.attempted))
    harness.free(engine)
    run.w0, run.w1 = t0, t0 + run.seconds
    assert len(run.done) == run.attempted == 3
    got = reference.check(cell, weights, run.done, log=quiet)
    assert got["served"] <= cfg["check"]["widest_logit_gap"]
    run.peaks = arith.peaks("TPU v5 lite")
    twin = dataclasses.replace(run, cell=harness.Cell("twin", tiny, cell.mix))
    mfu = reader("step.mfu")(run)
    assert mfu > 0 and mfu == reader("step.mfu")(twin)


def test_every_named_metric_and_file_exists():
    names = [m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]]
    for n in names:
        assert callable(reader(n))
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"], ROOT / "BENCHMARK.json")
        assert cell.cfg["tenants"][0]["kind"] == "foundation"


# -- arithmetic ---------------------------------------------------------------


def test_percentile_and_spread_by_hand():
    assert arith.percentile([4, 1, 3, 2], 50) == 2.5
    assert arith.percentile([10, 20], 90) == 19.0
    assert arith.percentile([], 90) is None
    # quartiles of 1..9 by statistics.quantiles: 2.5, 5, 7.5
    assert arith.spread(list(range(1, 10))) == pytest.approx(1.0)


def test_ttft_tpot_and_tokens_in_window_by_hand():
    rec = {"t_due": 1.0, "t_first": 1.5, "t_done": 3.5, "n_out": 5}
    assert arith.ttft_s(rec) == 0.5
    assert arith.tpot_s(rec) == 0.5  # 2 s over 4 gaps
    assert arith.tpot_s(dict(rec, n_out=1)) is None
    # first token at 1.5 in; the other 4 spread over 1.5..3.5, half in
    assert arith.tokens_in_window(rec, 1.0, 2.5) == pytest.approx(3.0)
    assert arith.tokens_in_window(rec, 0.0, 9.0) == pytest.approx(5.0)


def test_flops_bytes_and_roofline_by_hand():
    mistral = model.load_config("mistral-7b-v0.3-l8")
    deepseek = model.load_config("deepseek-llm-7b-l6")
    assert llama.layer_params(mistral) == 218_112_000
    assert llama.layer_params(deepseek) == 202_383_360
    # one token over 1000 cached: 4 * 32 heads * 128 * 1000
    assert llama.attn_flops(mistral, 1000) == 16_384_000
    # K and V of 8 heads x 128 x 1000 tokens plus q and out, in bf16
    assert llama.decode_attn_bytes(mistral, 1000) == (
        2 * 8 * 128 * 1000 + 2 * 32 * 128) * 2
    pk = arith.peaks("TPU v5 lite")
    t, bound = arith.roofline_s(16_384_000, 4_112_384, pk)
    assert bound == "memory" and t == pytest.approx(4_112_384 / 819e9)
    with pytest.raises(KeyError):
        arith.peaks("TPU v9")
    # a prompt of 2 tokens: body 2 x 2 x (P - head), causal attention over
    # 1 + 2 positions in each layer, the head once
    P, head = 1000, 100
    got = arith.prefill_flops(P, head, 2, 8, llama.attn_flops(mistral, 1))
    assert got == 2 * 900 * 2 + 8 * 4 * 32 * 128 * 3 + 2 * 100
    # the module's own counts: the same definitions over a tenant's chain
    base = {"name": "base", "kind": "foundation"}
    P = llama.chain_matmul_params(mistral, base)
    assert P == 8 * (218_112_000 - 2 * 4096) + 4096 * 32768
    assert llama.prefill_flops(mistral, "base", 2) == arith.prefill_flops(
        P, 4096 * 32768, 2, 8, 16_384)
    assert llama.decode_token_flops(mistral, "base", [1000] * 8) == (
        2 * P + 8 * 16_384_000)
    assert llama.decode_attn_work(mistral, 1000) == (
        16_384_000, (2 * 8 * 128 * 1000 + 2 * 32 * 128) * 2)


def test_traffic_gives_every_seed_the_same_sizes_in_another_order():
    mix = dict(OPEN_MIX, rate_rps=2.0, prompt={
        "dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32,
        "max": 2048})
    tenants = [t["name"] for t in model.load_config(
        "mistral-7b-v0.3-l8")["tenants"]]
    a = traffic.open_schedule(mix, tenants, seed=SEED, phase="window",
                              seconds=30)
    b = traffic.open_schedule(mix, tenants, seed=SEED + 1, phase="window",
                              seconds=30)
    assert len(a) == len(b) == round(mix["rate_rps"] * 30)
    for f in ("prompt_len", "out_len", "app"):
        assert sorted(getattr(r, f) for r in a) == sorted(
            getattr(r, f) for r in b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert all(32 <= r.prompt_len <= 2048 for r in a)
    assert a[-1].due < 30 and a[0].due == 0.0
    # Zipf(1) over the tenants: base takes 1 / H(n) of the requests
    share = sum(r.app == "base" for r in a) / len(a)
    n = len(tenants)
    assert share == pytest.approx(1 / sum(1 / k for k in range(1, n + 1)),
                                  abs=1.5 / len(a))
    toks = traffic.prompt_tokens(a[0], SEED, 32768)
    assert toks.shape == (a[0].prompt_len,) and toks.max() < 32768
    np.testing.assert_array_equal(toks, traffic.prompt_tokens(a[0], SEED,
                                                              32768))


def test_lognormal_quantiles_have_the_stated_median():
    v = traffic.lengths({"dist": "lognormal", "median": 512, "sigma": 0.8,
                         "min": 32, "max": 2048}, 101)
    assert v[50] == 512 and v.min() >= 32 and v.max() <= 2048
    assert math.isclose(np.median(v), 512)


# -- the chip check -----------------------------------------------------------


def test_the_harness_refuses_to_run_off_a_tpu(capsys):
    rc = bench_run.main(["--workload", BENCH["workloads"][0]["name"],
                         "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


# -- the trace reduction --------------------------------------------------------

DATA = Path(__file__).resolve().parent / "data"


def _sweep(intervals):
    """Busy intervals by a +1/-1 sweep (independent of xplane.union)."""
    intervals = [(a, b) for a, b in intervals if b > a]
    edges = sorted([(a, 1) for a, b in intervals]
                   + [(b, -1) for a, b in intervals])
    out, depth, start = [], 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            start = t
        depth += step
        if depth == 0:
            out.append((start, t))
    return out


def test_trace_reduction_on_a_recorded_chip_trace():
    """A 0.5 s slice of a recorded ``--trace 1`` run of the DeepSeek
    cell on a TPU v5 lite: its device operations and the run loop's
    ``bench.*`` host spans."""
    import gzip

    from benchmarks.chip import xplane

    with gzip.open(DATA / "deepseek_l6_trace_slice.json.gz", "rt") as f:
        planes = json.load(f)
    r = xplane.reduce(planes)
    host = planes["/host:CPU"]["python3"]
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    ops = planes["/device:TPU:0"]["XLA Ops"]
    busy = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
            if s + d > lo and s < hi]
    merged = _sweep(busy)
    assert r["window_s"] == pytest.approx(hi - lo)
    assert r["busy_s"] == pytest.approx(sum(b - a for a, b in merged))
    assert 0 <= 1 - r["busy_s"] / r["window_s"] < 1
    kern = sum(d for n, s, d in ops
               if n.startswith("%paged_attention") and s + d > lo and s < hi)
    assert kern > 0
    assert r["kernel_s"]["paged_attention"] == pytest.approx(kern)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = sorted((edges[i + 1] - edges[i], edges[i])
                  for i in range(0, len(edges), 2) if edges[i + 1] > edges[i])
    gaps = gaps[::-1][:10]
    assert [g for _, g in r["idle_gaps"]] == pytest.approx([g for g, _ in gaps])
    for (name, _), (length, start) in zip(r["idle_gaps"], gaps):
        mid = start + length / 2
        assert any(n == name and s <= mid <= s + d for n, s, d in host)
    assert len(r["device_ops"]) == 10
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]


def test_trace_load_reads_the_profilers_file(tmp_path):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import xplane

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.engine_step"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    planes = xplane.load(path)
    spans = [e for evs in planes[xplane.HOST_PLANE].values() for e in evs
             if e[0] == "bench.engine_step"]
    assert len(spans) == 1 and spans[0][2] > 0
