"""Continuous batching: parity with sequential generation, KV-pool slot
lifecycle, and the unified Server API over both backends."""
import numpy as np
import pytest

from repro.serving.api import ServeRequest, Server


@pytest.fixture(scope="module")
def demo():
    from repro.serving.demo import build_demo_zoo

    return build_demo_zoo(seed=0)


def _mixed_requests(cfg, n=8, seed=0, gen_lens=(4, 5, 6)):
    rng = np.random.RandomState(seed)
    apps = ["base", "vicuna", "app-lora"]
    reqs = []
    for i in range(n):
        prompt = rng.randint(0, cfg.vocab_size,
                             size=int(rng.randint(8, 20))).astype(np.int32)
        reqs.append(ServeRequest(app=apps[i % 3],
                                 gen_len=gen_lens[i % len(gen_lens)],
                                 prompt_tokens=prompt))
    return reqs


# ---------------------------------------------------------------------------
# parity: batched continuous decode == sequential per-request generation
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_batched_matches_sequential_greedy(demo):
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    reqs = _mixed_requests(cfg, n=8)
    rids = [engine.submit(r) for r in reqs]
    results = {r.rid: r for r in engine.drain()}
    assert sorted(results) == sorted(rids)

    seq = BlockEngine(zoo, max_len=64)
    for req, rid in zip(reqs, rids):
        ref = seq.generate(zoo.chains[req.app], req.prompt_tokens[None],
                           req.gen_len)
        got = results[rid]
        np.testing.assert_array_equal(
            got.tokens, ref.tokens[0],
            err_msg=f"rid={rid} app={req.app} diverged from sequential")
        # probs pass through bf16 matmuls whose accumulation order depends
        # on batch width; tokens must be identical, probs merely close
        np.testing.assert_allclose(got.probs_last, ref.probs_last[0],
                                   rtol=0.05, atol=2e-3)


@pytest.mark.slow
def test_step_granularity_and_interleaved_submission(demo):
    """Requests submitted mid-flight join the running batch and still
    produce the same tokens."""
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    reqs = _mixed_requests(cfg, n=4, seed=1, gen_lens=(6,))
    first = [engine.submit(r) for r in reqs[:2]]
    engine.step()  # decode begins with two requests in flight
    late = [engine.submit(r) for r in reqs[2:]]
    out = {r.rid: r for r in engine.drain()}
    assert sorted(out) == sorted(first + late)

    seq = BlockEngine(zoo, max_len=64)
    for req, rid in zip(reqs, first + late):
        ref = seq.generate(zoo.chains[req.app], req.prompt_tokens[None],
                           req.gen_len)
        np.testing.assert_array_equal(out[rid].tokens, ref.tokens[0])


# ---------------------------------------------------------------------------
# KV pool: slot alloc / free / reuse
# ---------------------------------------------------------------------------


def test_kv_pool_alloc_free_reuse():
    from repro.serving.kv_pool import TRASH_PAGE, KVPool

    pool = KVPool(num_pages=9, page_size=4, kv_heads=2, head_dim=8)
    assert pool.free_pages == 8  # page 0 reserved
    s1 = pool.alloc(rid=1, step=0, tokens=10)  # 3 pages
    s2 = pool.alloc(rid=2, step=0, tokens=4)   # 1 page
    assert len(s1.pages) == 3 and len(s2.pages) == 1
    assert TRASH_PAGE not in s1.pages + s2.pages
    assert pool.used_pages == 4 and pool.free_pages == 4
    assert not pool.can_fit(tokens=24, n_slots=1)  # 6 pages > 4 free

    pool.free(1, 0)
    assert pool.free_pages == 7
    # freed pages are recycled
    s3 = pool.alloc(rid=3, step=0, tokens=12)
    assert set(s3.pages) & set(s1.pages)
    with pytest.raises(MemoryError):
        pool.alloc(rid=4, step=0, tokens=1000)
    pool.free_request(3)
    pool.free_request(2)
    assert pool.free_pages == 8 and not pool.slots


@pytest.mark.slow
def test_engine_pool_recycled_across_requests(demo):
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    for r in _mixed_requests(cfg, n=4, seed=2):
        engine.submit(r)
    engine.drain()
    pools = list(engine.pools.values())
    assert pools and all(p.used_pages == 0 and not p.slots for p in pools)
    # a second wave reuses the same pages
    before = {id(p): p.free_pages for p in pools}
    for r in _mixed_requests(cfg, n=4, seed=3):
        engine.submit(r)
    engine.drain()
    assert all(p.free_pages == before[id(p)] for p in engine.pools.values())
    assert all(p.free_count > 0 for p in engine.pools.values())


@pytest.mark.slow
def test_engine_admission_blocks_on_full_pool(demo):
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = demo
    # pool sized for ~one request per attention step at a time
    engine = BlockEngine(zoo, max_len=32,
                         config=EngineConfig(num_pages=1 + 2 * 4 * 2,
                                             page_size=16))
    reqs = _mixed_requests(cfg, n=3, seed=4, gen_lens=(4,))
    for r in reqs:
        engine.submit(r)
    results = engine.drain()  # admission control must serialize, not crash
    assert len(results) == 3


# ---------------------------------------------------------------------------
# unified Server API over both backends
# ---------------------------------------------------------------------------


def test_both_backends_implement_server(demo):
    from repro.serving.engine import BlockEngine
    from repro.serving.simulator import (
        SchedulerConfig,
        Simulation,
        build_serving_config,
    )

    cfg, zoo = demo
    assert isinstance(BlockEngine(zoo), Server)
    sim = Simulation(build_serving_config(n_apps=4), SchedulerConfig())
    assert isinstance(sim, Server)

    rid = sim.submit(ServeRequest(app="app0", gen_len=4, prompt_len=16))
    results = sim.drain()
    assert [r.rid for r in results] == [rid]
    assert results[0].tokens is None and results[0].latency > 0


def test_simulator_run_equals_submit_drain():
    from repro.serving.request import as_serve_requests, generate_trace
    from repro.serving.simulator import (
        SchedulerConfig,
        Simulation,
        build_serving_config,
    )

    cfg = build_serving_config(n_foundations=2, n_apps=6)
    trace = generate_trace(list(cfg.chains), total_requests=60,
                           duration_s=60, seed=5)
    a = Simulation(cfg, SchedulerConfig())
    m_run = a.run(trace)

    b = Simulation(cfg, SchedulerConfig())
    for req in as_serve_requests(trace):
        b.submit(req)
    results = b.drain()
    m_api = b.metrics()
    assert len(results) == m_run["completed"]
    assert m_api["median_latency"] == pytest.approx(m_run["median_latency"])
    assert m_api["throughput_tokens_s"] == pytest.approx(
        m_run["throughput_tokens_s"])


# ---------------------------------------------------------------------------
# config plumbing: argparse flags generated from the dataclass
# ---------------------------------------------------------------------------


def test_scheduler_config_arg_roundtrip():
    import argparse
    import dataclasses

    from repro.serving.simulator import SchedulerConfig

    ap = argparse.ArgumentParser()
    SchedulerConfig.add_args(ap)
    # defaults roundtrip
    assert SchedulerConfig.from_args(ap.parse_args([])) == SchedulerConfig()
    # every field is reachable from the CLI
    args = ap.parse_args(["--mode", "pm", "--no-adaptive", "--kv-policy",
                          "recalc", "--max-batch", "8", "--seed", "3"])
    cfg = SchedulerConfig.from_args(args)
    assert cfg == SchedulerConfig(mode="pm", adaptive=False,
                                  kv_policy="recalc", max_batch=8, seed=3)
    # bad choices rejected by the generated parser
    with pytest.raises(SystemExit):
        ap.parse_args(["--mode", "bogus"])
    # no hand-declared flag drift: one flag per dataclass field
    flags = {a.dest for a in ap._actions if a.dest != "help"}
    assert flags == {f.name for f in dataclasses.fields(SchedulerConfig)}
