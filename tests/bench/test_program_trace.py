"""The program-level trace reduction (``benchmarks/chip/program_trace.py``)
and the per-layer metrics that read it, on hand-built planes, on a slice
recorded on the chip, and against the run loop's own KV sampling."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness, model, program_trace, traffic, xplane  # noqa: E402
from benchmarks.chip.metrics import reader  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 40 + 5
NEW = ("step.decode_device_ms", "step.prefill_device_frac",
       "kv.write_device_frac", "sched.device_idle_frac",
       "kv.device_idle_frac", "exec.device_idle_frac")

DEV, HOST = "/device:TPU:0", "/host:CPU"


def hand_planes():
    """A 10 s stretch: four programs, four idle gaps, nested spans."""
    return {
        DEV: {
            "XLA Modules": [("jit_chain_decode(11)", 1.0, 2.0),
                            ("jit_kv_write_prefill(12)", 4.0, 1.0),
                            ("jit_chain_decode(11)", 6.0, 2.0),
                            ("jit_chain_prefill(13)", 9.5, 1.0)],
            "XLA Ops": [("%fusion.1", 1.0, 2.0), ("%copy.2", 4.0, 1.0),
                        ("%fusion.1", 6.0, 2.0), ("%fusion.3", 9.5, 1.0)],
        },
        HOST: {"python3": [
            ("bench.engine_step", 0.0, 10.0),
            ("engine.step", 0.5, 9.0),
            ("exec.dispatch", 0.5, 0.5),
            ("sched.form_groups", 3.0, 0.5),
            ("kv.write_prefill", 3.5, 0.5),
            ("exec.sync", 5.0, 0.5),
            ("$engine.py:1 step", 5.0, 3.0),  # the Python tracer's: ignored
        ]},
    }


def test_idle_goes_to_the_innermost_span_and_modules_sum():
    r = program_trace.reduce(hand_planes())
    assert r["window_s"] == 10.0 and r["busy_s"] == pytest.approx(5.5)
    # idle [0,1] [3,4] [5,6] [8,9.5]: cut at span edges
    assert r["idle_s"] == pytest.approx({
        "bench.engine_step": 0.5, "exec.dispatch": 0.5,
        "sched.form_groups": 0.5, "kv.write_prefill": 0.5,
        "exec.sync": 0.5, "engine.step": 2.0})
    assert sum(r["idle_s"].values()) == pytest.approx(10.0 - 5.5)
    # clipped to the stretch; the prefill event is not wholly inside
    assert r["module_s"] == pytest.approx({
        "jit_chain_decode": 4.0, "jit_kv_write_prefill": 1.0,
        "jit_chain_prefill": 0.5})
    assert r["module_events"] == {"jit_chain_decode": [2.0, 2.0],
                                  "jit_kv_write_prefill": [1.0]}
    assert "$engine.py:1 step" not in r["spans"]


def test_a_span_that_starts_with_its_parent_owns_its_idle():
    planes = {DEV: {"XLA Ops": [("%a", 2.0, 1.0)]},
              HOST: {"t": [("bench.engine_step", 0.0, 3.0),
                           ("engine.step", 0.0, 2.9),
                           ("sched.admit", 0.0, 1.0)]}}
    r = program_trace.reduce(planes)
    assert r["idle_s"] == pytest.approx({"sched.admit": 1.0,
                                         "engine.step": 1.0})


def test_a_programs_device_time_leaves_out_the_gaps_inside_it():
    """A module event spans the idle between its own operations; only
    the operations count, so the programs' shares of busy sum to 1."""
    planes = {DEV: {"XLA Modules": [("jit_kv_write_prefill(1)", 1.0, 2.0),
                                    ("jit_chain_decode(2)", 3.0, 1.0)],
                    "XLA Ops": [("%copy", 1.0, 0.5), ("%scatter", 2.5, 0.5),
                                ("%fusion", 3.0, 1.0)]},
              HOST: {"t": [("bench.engine_step", 0.0, 5.0)]}}
    r = program_trace.reduce(planes)
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["module_s"] == pytest.approx({"jit_kv_write_prefill": 1.0,
                                           "jit_chain_decode": 1.0})
    assert r["module_events"] == {"jit_kv_write_prefill": [1.0],
                                  "jit_chain_decode": [1.0]}
    assert r["idle_s"] == pytest.approx({"bench.engine_step": 3.0})


def _run(monkeypatch, planes, name):
    monkeypatch.setattr(program_trace.xplane, "load", lambda path: planes)
    return SimpleNamespace(trace={"file": name}, counters={})


def test_the_new_readers_on_hand_built_planes(monkeypatch):
    run = _run(monkeypatch, hand_planes(), "hand-built")
    got = {n: reader(n)(run) for n in NEW}
    assert got == pytest.approx({
        "step.decode_device_ms": 2000.0,
        "step.prefill_device_frac": 0.5 / 5.5,
        "kv.write_device_frac": 1.0 / 5.5,
        "sched.device_idle_frac": 0.05,
        "kv.device_idle_frac": 0.05,
        "exec.device_idle_frac": 0.1})


def test_a_program_without_names_or_spans_reads_nothing(monkeypatch):
    """A build with unnamed programs (``jit_fn``) and no program spans,
    as before them: every new reader returns None and none raises."""
    planes = hand_planes()
    planes[DEV]["XLA Modules"] = [(f"jit_fn({i})", s, d) for i, (_, s, d)
                                  in enumerate(planes[DEV]["XLA Modules"])]
    planes[HOST]["python3"] = [e for e in planes[HOST]["python3"]
                               if e[0].startswith("bench.")]
    run = _run(monkeypatch, planes, "unnamed")
    assert [reader(n)(run) for n in NEW] == [None] * len(NEW)
    assert reader("kv.reserved_over_live")(run) is None
    untraced = SimpleNamespace(trace=None, counters={})
    assert [reader(n)(untraced) for n in NEW] == [None] * len(NEW)


def _sweep(intervals):
    """Union of intervals by a +1/-1 sweep (independent of xplane.union)."""
    edges = sorted([(a, 1) for a, b in intervals if b > a]
                   + [(b, -1) for a, b in intervals if b > a])
    out, depth, start = [], 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            start = t
        depth += step
        if depth == 0:
            out.append((start, t))
    return out


def test_the_reduction_on_a_slice_recorded_on_the_chip():
    """One admission step (0.24 s) of a ``--trace 1`` run of the Mistral
    cell on a TPU v5 lite: the device's module and operation lines (each
    operation's name cut to its HLO name) and the host's program and
    ``bench.*`` spans.  Idle is recomputed here gap by gap, each piece
    given to the covering span that started last."""
    with gzip.open(DATA / "mistral_l8_program_slice.json.gz", "rt") as f:
        planes = json.load(f)
    r = program_trace.reduce(planes)
    host = [(n, s, s + d) for n, s, d in planes[HOST]["python3"]]
    bench = [h for h in host if h[0].startswith("bench.")]
    lo, hi = min(s for _, s, _ in bench), max(e for _, _, e in bench)
    busy = _sweep([(max(s, lo), min(s + d, hi)) for _, s, d
                   in planes[DEV]["XLA Ops"] if s + d > lo and s < hi])
    assert r["busy_s"] == pytest.approx(sum(b - a for a, b in busy))
    # a program's device time: the busy time inside its module events
    want = {}
    for n, s, d in planes[DEV]["XLA Modules"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            m = n.split("(")[0]
            want[m] = want.get(m, 0.0) + sum(
                max(0.0, min(b, y) - max(a, x)) for x, y in busy)
    assert r["module_s"] == pytest.approx(want)
    assert {"jit_chain_decode", "jit_chain_prefill",
            "jit_kv_write_prefill"} <= set(want)
    # the programs' shares of busy time sum to at most 1
    assert sum(r["module_s"].values()) <= r["busy_s"] * (1 + 1e-9)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = {}
    for a, b in zip(edges[::2], edges[1::2]):
        cuts = sorted({a, b} | {x for _, s, e in host for x in (s, e)
                                if a < x < b})
        for c, d in zip(cuts, cuts[1:]):
            mid = (c + d) / 2
            over = [h for h in host if h[1] <= mid < h[2]]
            owner = (max(over, key=lambda h: (h[1], -h[2]))[0]
                     if over else program_trace.NO_SPAN)
            idle[owner] = idle.get(owner, 0.0) + d - c
    assert r["idle_s"] == pytest.approx(idle, abs=1e-9)
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert {"engine.step", "exec.dispatch", "sched.admit"} <= set(r["spans"])
    # both readings agree with the benchmark's own reduction
    base = xplane.reduce(planes)
    assert r["window_s"] == pytest.approx(base["window_s"])
    assert r["busy_s"] == pytest.approx(base["busy_s"])


def test_reserved_over_live_matches_the_run_loops_own_sampling():
    """On a CPU run, the program's two KV counters grow by exactly the
    pages ``Driver._lanes()`` sums before the same steps."""
    cfg = model.load_config("mistral-7b-v0.3-l8")
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=256)
    cfg["tenants"] = [t for t in cfg["tenants"]
                      if t["name"] in ("base", "lora-0", "adapter-0")]
    cfg["engine"] = dict(max_active=4, max_block_batch=4, page_size=16,
                         num_pages=200)
    mix = traffic.load_mix(ROOT / "benchmarks/chip/traffic/"
                           "multiapp-closed.json")
    mix.update(clients=3)
    mix["prompt"].update(median=24, min=8, max=48)
    mix["output"].update(median=6, min=2, max=12)
    cell = harness.Cell("tiny", cfg, mix)
    _, _, engine = harness.build(cell, SEED, "ref", log=lambda m: None)
    run = harness.RunData(cell=cell, seconds=1.0)
    drv = harness.Driver(engine, cell, SEED, run, log=lambda m: None)
    drv.start_clients(drv.clock())
    drv.loop(lambda now: min(drv.answered) >= 1)  # past the first admissions
    run.counters["start"] = harness._counters(engine)
    drv.sample_steps = True
    drv.loop(lambda now: min(drv.answered) >= 3)
    run.counters["end"] = harness._counters(engine)
    harness.free(engine)
    a, b = run.counters["start"], run.counters["end"]
    steps = run.steps
    assert steps
    reserved = sum(s["reserved_pages"] for s in steps)
    live = sum(h * max(1, -(-kv // s["page_size"]))
               for s in steps for _, kv, h in s["lanes"])
    assert b["kv_page_steps_reserved"] - a["kv_page_steps_reserved"] == reserved
    assert b["kv_page_steps_live"] - a["kv_page_steps_live"] == live
    assert reader("kv.reserved_over_live")(run) == pytest.approx(
        reserved / live)
