"""Observability plane (DESIGN.md §8): typed metrics registry, per-request
trace spans, Chrome export, and the cross-layer invariants — span chains
stay contiguous under preemption churn, and registry totals reconcile
with what the engine actually returned."""
import json

import numpy as np
import pytest

from repro.observability import (
    MetricsRegistry,
    Tracer,
    chrome_trace,
    percentiles_of,
)
from repro.observability.trace import RequestTrace

# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    reg.inc("steps")
    reg.inc("steps", 4)
    reg.set_gauge("active", 7)
    for v in (1.0, 3.0, 2.0):
        reg.observe("lat", v)
    assert reg.counter("steps").value == 5
    assert reg.gauge("active").value == 7
    h = reg.histogram("lat")
    assert h.count == 3 and h.total == 6.0
    assert h.min == 1.0 and h.max == 3.0 and h.mean == 2.0


def test_counters_view_is_live_mapping():
    reg = MetricsRegistry()
    reg.inc("a", 2)
    view = reg.counters_view()
    assert view["a"] == 2 and dict(view) == {"a": 2}
    reg.inc("a")          # live: later increments show through
    reg.inc("b", 9)       # live: new counters appear
    assert view["a"] == 3 and sorted(view) == ["a", "b"]
    with pytest.raises(TypeError):
        view["a"] = 0     # read-only


def test_histogram_percentiles_and_summary():
    reg = MetricsRegistry()
    for v in range(1, 101):
        reg.observe("h", float(v))
    h = reg.histogram("h")
    assert h.percentile(50) == 51.0  # nearest-rank over 1..100
    assert h.percentile(95) == 95.0
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert MetricsRegistry().histogram("empty").summary()["count"] == 0


def test_percentiles_of_nearest_rank():
    out = percentiles_of([5.0, 1.0, 3.0], qs=(50, 95))
    assert out[50] == 3.0 and out[95] == 5.0
    assert percentiles_of([], qs=(50,)) == {50: 0.0}


def test_snapshot_roundtrips_json(tmp_path):
    reg = MetricsRegistry()
    reg.inc("c", 3)
    reg.set_gauge("g", 1.5)
    reg.observe("h", 2.0)
    path = tmp_path / "metrics.json"
    reg.write(str(path))
    snap = json.loads(path.read_text())
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1


# ---------------------------------------------------------------------------
# trace span derivation
# ---------------------------------------------------------------------------


def _trace(events):
    tr = RequestTrace(rid=0)
    for name, t in events:
        tr.event(name, t)
    return tr


def test_span_chain_simple_lifecycle():
    tr = _trace([("submit", 0.0), ("admit", 1.0), ("prefill", 2.0),
                 ("decode_step", 2.5), ("finish", 3.0)])
    spans = [(s.name, s.t0, s.t1) for s in tr.spans()]
    assert spans == [("queued", 0.0, 1.0), ("prefill", 1.0, 2.0),
                     ("decode", 2.0, 3.0)]


def test_span_chain_with_preemption():
    tr = _trace([("submit", 0.0), ("admit", 1.0), ("prefill", 2.0),
                 ("preempt", 3.0), ("readmit", 5.0), ("finish", 7.0)])
    assert [s.name for s in tr.spans()] == \
        ["queued", "prefill", "decode", "preempted", "decode"]
    # contiguous by construction: each span starts where the last ended
    spans = tr.spans()
    assert all(a.t1 == b.t0 for a, b in zip(spans, spans[1:]))


def test_span_chain_gen_len_zero_uses_run_phase():
    tr = _trace([("submit", 0.0), ("admit", 1.0), ("finish", 1.0)])
    assert [s.name for s in tr.spans()] == ["queued", "run"]


def test_chrome_trace_structure():
    tracer = Tracer(clock=lambda: 0.0)
    tracer.event(3, "submit", t=0.0, app="chat")
    tracer.event(3, "admit", t=1.0)
    tracer.event(3, "prefill", t=1.5)
    tracer.event(3, "spill", t=2.0, kv_bytes=64)
    tracer.event(3, "finish", t=3.0)
    tracer.global_span("engine_step", 0.5, 1.0, active=1)
    doc = chrome_trace(tracer)
    ev = doc["traceEvents"]
    assert {e["ph"] for e in ev} == {"M", "X", "i"}
    xs = [e for e in ev if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)
    assert {e["name"] for e in xs} >= {"engine_step", "queued"}
    # non-boundary lifecycle events render as instants, not spans
    assert [e["name"] for e in ev if e["ph"] == "i"] == ["spill"]
    json.dumps(doc)  # loadable artifact


def test_tracer_evicts_finished_traces_first():
    tracer = Tracer(clock=lambda: 0.0, max_traces=4)
    for rid in range(4):
        tracer.event(rid, "submit")
        if rid < 3:
            tracer.event(rid, "finish")
    tracer.event(99, "submit")  # overflow triggers eviction
    assert 99 in tracer.traces
    assert 3 in tracer.traces  # unfinished trace survives


# ---------------------------------------------------------------------------
# cost_analysis_dict: jax version drift (list-of-dict vs dict)
# ---------------------------------------------------------------------------


class _FakeCompiled:
    def __init__(self, ret):
        self._ret = ret

    def cost_analysis(self):
        return self._ret


def test_cost_analysis_dict_normalizes_both_shapes():
    from repro.launch.hlo_analysis import cost_analysis_dict

    assert cost_analysis_dict(_FakeCompiled([{"flops": 5.0}])) == {"flops": 5.0}
    assert cost_analysis_dict(_FakeCompiled({"flops": 5.0})) == {"flops": 5.0}
    assert cost_analysis_dict(_FakeCompiled([])) == {}


# ---------------------------------------------------------------------------
# engine integration: invariants under preemption churn (slow: compiles)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def demo():
    from repro.serving.demo import build_demo_zoo

    return build_demo_zoo(seed=0)


def _requests(cfg, n, seed=0, gen_len=6):
    from repro.serving.api import ServeRequest

    rng = np.random.RandomState(seed)
    apps = ["base", "vicuna", "app-lora"]
    return [ServeRequest(
        app=apps[i % 3], gen_len=gen_len,
        prompt_tokens=rng.randint(0, cfg.vocab_size,
                                  size=int(rng.randint(8, 20)))
        .astype(np.int32)) for i in range(n)]


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["spill", "recalc"])
def test_trace_invariants_under_preemption_churn(demo, strategy):
    """Every request's span chain stays monotonic and contiguous from
    submit to finish even when it is evicted and readmitted mid-decode,
    and preempt/readmit events pair up exactly."""
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    reqs = _requests(cfg, n=3, seed=31)
    rids = [engine.submit(r) for r in reqs]
    engine.step()
    engine.step()
    assert engine.preempt(rids[0], strategy=strategy)
    results = engine.drain()
    assert sorted(r.rid for r in results) == sorted(rids)
    for res in results:
        tr = res.info["trace"]
        ts = [e["t"] for e in tr["events"]]
        assert ts == sorted(ts), f"rid={res.rid} events not monotonic"
        names = [e["name"] for e in tr["events"]]
        assert names[0] == "submit" and names[-1] == "finish"
        spans = tr["spans"]
        assert spans[0]["name"] == "queued"
        assert all(a["t1"] == b["t0"] for a, b in zip(spans, spans[1:])), \
            f"rid={res.rid} span chain has a gap"
        assert spans[0]["t0"] == ts[0] and spans[-1]["t1"] == ts[-1]
        n_preempt = names.count("preempt")
        assert n_preempt == names.count("readmit")
        if strategy == "spill":
            assert names.count("spill") == names.count("restore")
    victim = next(r for r in results if r.rid == rids[0])
    v_names = [e["name"] for e in victim.info["trace"]["events"]]
    assert v_names.count("preempt") == 1
    assert [s["name"] for s in victim.info["trace"]["spans"]] == \
        ["queued", "prefill", "decode", "preempted", "decode"]


@pytest.mark.slow
def test_metrics_reconcile_with_results(demo):
    """Registry totals are not a parallel fiction: counters must equal
    what ``drain`` actually handed back."""
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    reqs = _requests(cfg, n=4, seed=32, gen_len=5)
    rids = [engine.submit(r) for r in reqs]
    results = engine.drain()
    assert engine.stats["completed"] == len(results) == len(rids)
    assert engine.stats["tokens_emitted"] == sum(len(r.tokens)
                                                 for r in results)
    assert engine.stats["admitted"] == len(rids)
    snap = engine.metrics.snapshot()
    assert snap["histograms"]["ttft_s"]["count"] == len(rids)
    assert snap["histograms"]["latency_s"]["count"] == len(rids)
    assert snap["gauges"]["active"] == 0  # drained
    # per-request info agrees with the trace it carries
    for res in results:
        tr = res.info["trace"]
        t_sub = tr["events"][0]["t"]
        t_fin = tr["events"][-1]["t"]
        assert res.info["latency_s"] == pytest.approx(t_fin - t_sub)
        assert res.info["ttft_s"] is not None and res.info["ttft_s"] >= 0
