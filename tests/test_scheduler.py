"""Shared scheduler core (serving/scheduler.py): policy ordering, per-block
run queues and preemption — unit tests, cross-backend identity (Simulation
and BlockEngine construct and drive the same Scheduler class), and
token-exact resume after forced KV eviction in the real engine."""
import numpy as np
import pytest

from repro.serving.scheduler import POLICIES, SchedEntry, Scheduler


def _entries(specs):
    """specs: list of (rid, arrival, priority)."""
    return [SchedEntry(rid=r, app="a", arrival=a, priority=p)
            for r, a, p in specs]


# ---------------------------------------------------------------------------
# policy ordering / admission
# ---------------------------------------------------------------------------


def test_fcfs_admits_in_arrival_order():
    s = Scheduler("fcfs")
    for e in _entries([(0, 2.0, 0), (1, 1.0, 9), (2, 1.0, 0), (3, 0.0, 1)]):
        s.submit(e)
    out = [e.rid for e in s.admit(fits=lambda e: True)]
    assert out == [3, 1, 2, 0]  # arrival, then submission order; no priority


def test_priority_admits_high_first_fcfs_within_level():
    s = Scheduler("priority")
    for e in _entries([(0, 0.0, 0), (1, 0.0, 5), (2, 1.0, 5), (3, 0.0, 0)]):
        s.submit(e)
    out = [e.rid for e in s.admit(fits=lambda e: True)]
    assert out == [1, 2, 0, 3]


def test_unknown_policy_rejected():
    with pytest.raises(ValueError):
        Scheduler("sjf")
    assert set(POLICIES) == {"fcfs", "priority"}


def test_head_of_line_blocking_and_incremental_fits():
    """A blocked head blocks everything behind it, and ``fits`` must see
    the resource state updated by each admission (on_admit ordering)."""
    s = Scheduler("fcfs")
    for e in _entries([(0, 0.0, 0), (1, 1.0, 0), (2, 2.0, 0)]):
        s.submit(e)
    budget = {"free": 2}
    placed = []
    out = s.admit(fits=lambda e: budget["free"] > 0,
                  on_admit=lambda e: (placed.append(e.rid),
                                      budget.update(free=budget["free"] - 1)))
    assert [e.rid for e in out] == [0, 1] == placed
    assert s.waiting == 1 and s.peek().rid == 2


def test_max_new_caps_admission():
    s = Scheduler("fcfs")
    for e in _entries([(i, float(i), 0) for i in range(5)]):
        s.submit(e)
    assert len(s.admit(fits=lambda e: True, max_new=2)) == 2
    assert s.waiting == 3


# ---------------------------------------------------------------------------
# preemption-victim selection
# ---------------------------------------------------------------------------


def test_fcfs_never_preempts():
    s = Scheduler("fcfs")
    running = _entries([(0, 0.0, 0), (1, 1.0, 0)])
    for e in running:
        s.submit(e)
    s.admit(fits=lambda e: True)
    incoming = s.submit(SchedEntry(rid=9, app="a", arrival=2.0, priority=99))
    assert s.pick_victim(running, incoming) is None  # priority ignored


def test_priority_picks_lowest_ranked_victim_strictly_below():
    s = Scheduler("priority")
    running = _entries([(0, 0.0, 1), (1, 0.0, 3), (2, 0.0, 5)])
    for e in running:
        e.seq = 0  # normally assigned by submit()
    incoming = SchedEntry(rid=9, app="a", priority=4, seq=1)
    assert s.pick_victim(running, incoming).rid == 0  # lowest priority
    equal = SchedEntry(rid=8, app="a", priority=1, arrival=1.0, seq=2)
    assert s.pick_victim(running, equal) is None  # nothing strictly below


def test_preempt_callback_frees_then_head_admits():
    s = Scheduler("priority")
    low = s.submit(SchedEntry(rid=0, app="a", priority=0))
    s.admit(fits=lambda e: True)
    high = s.submit(SchedEntry(rid=1, app="a", priority=9))
    state = {"free": 0, "running": [low]}

    def preempt(victim):
        state["running"].remove(victim)
        state["free"] += 1
        return True

    out = s.admit(fits=lambda e: state["free"] > 0,
                  running=lambda: state["running"], preempt=preempt,
                  on_admit=lambda e: state.update(free=state["free"] - 1))
    assert [e.rid for e in out] == [high.rid]
    # the victim resumes in order once requeued (keeps its original seq)
    s.submit(low)
    assert s.peek().rid == low.rid


# ---------------------------------------------------------------------------
# per-block run queues
# ---------------------------------------------------------------------------


def test_form_batch_ready_gating_cap_and_owner_priority():
    s = Scheduler("fcfs")
    items = _entries([(i, 0.0, 0) for i in range(5)])
    for i, it in enumerate(items):
        s.enqueue("blk", ready=float(i), item=it)
    assert s.queue_len("blk") == 5
    # only entries with ready <= now are eligible; rid 3 is a returning KV
    # owner and jumps the FIFO order (§5.1 best-effort)
    batch = s.form_batch("blk", now=3.0, max_batch=2,
                         prioritize=frozenset([3]))
    assert [e.rid for e in batch] == [3, 0]
    assert s.queue_len("blk") == 3
    batch = s.form_batch("blk", now=10.0, max_batch=10)
    assert [e.rid for e in batch] == [1, 2, 4]
    assert s.form_batch("blk", now=10.0, max_batch=10) == []
    s.enqueue("other", 0.0, items[0])
    s.drop_queue("other")
    assert s.queue_len("other") == 0


# ---------------------------------------------------------------------------
# cross-backend: both planes construct and drive the same Scheduler class
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def demo():
    from repro.serving.demo import build_demo_zoo

    return build_demo_zoo(seed=0)


def _backends(demo, policy):
    from repro.serving.engine import BlockEngine, EngineConfig
    from repro.serving.simulator import (
        SchedulerConfig,
        Simulation,
        build_serving_config,
    )

    _, zoo = demo
    engine = BlockEngine(zoo, config=EngineConfig(policy=policy))
    sim = Simulation(build_serving_config(n_apps=4),
                     SchedulerConfig(policy=policy))
    return engine, sim


@pytest.mark.parametrize("policy", POLICIES)
def test_backends_construct_same_scheduler_class(demo, policy):
    engine, sim = _backends(demo, policy)
    assert type(engine.scheduler) is Scheduler is type(sim.scheduler)
    assert engine.scheduler.policy == sim.scheduler.policy == policy


@pytest.mark.parametrize("policy", POLICIES)
def test_policy_orders_identically_on_both_backends(demo, policy):
    """The same submission sequence admits in the same order through the
    engine's scheduler and the simulator's scheduler."""
    specs = [(0, 0.0, 0), (1, 0.0, 7), (2, 1.0, 7), (3, 0.5, 2), (4, 0.0, 2)]
    orders = []
    for sched in _backends(demo, policy):
        for e in _entries(specs):
            sched.scheduler.submit(e)
        orders.append([e.rid for e in
                       sched.scheduler.admit(fits=lambda e: True)])
    assert orders[0] == orders[1]
    expected = ([0, 1, 4, 3, 2] if policy == "fcfs" else [1, 2, 4, 3, 0])
    assert orders[0] == expected


# ---------------------------------------------------------------------------
# real-engine preemption: pause under pressure, resume token-exact
# ---------------------------------------------------------------------------


def _requests(cfg, n, seed=0, gen_len=6, **kw):
    from repro.serving.api import ServeRequest

    rng = np.random.RandomState(seed)
    apps = ["base", "vicuna", "app-lora"]
    return [ServeRequest(
        app=apps[i % 3], gen_len=gen_len,
        prompt_tokens=rng.randint(0, cfg.vocab_size,
                                  size=int(rng.randint(8, 20)))
        .astype(np.int32), **kw) for i in range(n)]


def _reference_tokens(zoo, reqs):
    from repro.serving.engine import BlockEngine

    ref = BlockEngine(zoo, max_len=64)
    return [ref.generate(zoo.chains[r.app], r.prompt_tokens[None],
                         r.gen_len).tokens[0] for r in reqs]


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["spill", "recalc"])
def test_forced_preemption_token_exact(demo, strategy):
    """A request evicted mid-decode resumes and matches the unpreempted
    run exactly — for both §5.1 readmission strategies."""
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    reqs = _requests(cfg, n=3, seed=11)
    rids = [engine.submit(r) for r in reqs]
    engine.step()
    engine.step()  # two decode iterations in flight
    assert engine.preempt(rids[0], strategy=strategy)
    assert not engine.preempt(999, strategy=strategy)  # unknown rid
    out = {r.rid: r for r in engine.drain()}
    assert sorted(out) == sorted(rids)
    for req, rid, ref in zip(reqs, rids, _reference_tokens(zoo, reqs)):
        np.testing.assert_array_equal(
            out[rid].tokens, ref,
            err_msg=f"rid={rid} diverged after {strategy} preemption")
    assert out[rids[0]].info["preemptions"] == 1
    assert engine.stats["preemptions"] == 1
    key = "spills" if strategy == "spill" else "recalc_readmits"
    assert engine.stats[key] == 1
    assert all(p.used_pages == 0 for p in engine.pools.values())


@pytest.mark.slow
def test_pressure_preemption_under_priority_policy(demo):
    """A high-priority arrival evicts the resident low-priority request
    when the pool cannot hold both; both finish token-exact."""
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = demo
    # pool sized for exactly one resident request (4 attn steps x 2 pages)
    engine = BlockEngine(zoo, max_len=32,
                         config=EngineConfig(num_pages=9, page_size=16,
                                             policy="priority"))
    low = _requests(cfg, n=1, seed=21, gen_len=8, priority=0)[0]
    high = _requests(cfg, n=1, seed=22, gen_len=4, priority=5)[0]
    rid_low = engine.submit(low)
    engine.step()
    engine.step()  # low is resident and decoding
    rid_high = engine.submit(high)
    out = {r.rid: r for r in engine.drain()}
    assert sorted(out) == sorted([rid_low, rid_high])
    assert out[rid_low].info["preemptions"] >= 1
    assert out[rid_high].info["preemptions"] == 0
    assert engine.stats["preemptions"] >= 1
    for req, rid in ((low, rid_low), (high, rid_high)):
        ref = _reference_tokens(zoo, [req])[0]
        np.testing.assert_array_equal(out[rid].tokens, ref)


@pytest.mark.slow
def test_fcfs_pressure_serializes_without_preemption(demo):
    """Under FCFS the same pressure waits instead of preempting (victims
    are never ranked below an older head)."""
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=32,
                         config=EngineConfig(num_pages=9, page_size=16))
    reqs = _requests(cfg, n=3, seed=23, gen_len=4)
    rids = [engine.submit(r) for r in reqs]
    out = {r.rid: r for r in engine.drain()}
    assert sorted(out) == sorted(rids)
    assert engine.stats["preemptions"] == 0
    assert all(out[r].info["preemptions"] == 0 for r in rids)


# ---------------------------------------------------------------------------
# gen_len=0: completes at admission with empty output
# ---------------------------------------------------------------------------


def test_gen_len_zero_completes_at_admission(demo):
    from repro.serving.api import ServeRequest
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    rng = np.random.RandomState(31)
    prompt = rng.randint(0, cfg.vocab_size, size=12).astype(np.int32)
    rid = engine.submit(ServeRequest(app="base", gen_len=0,
                                     prompt_tokens=prompt))
    res = engine.step()
    assert [r.rid for r in res] == [rid]
    assert res[0].tokens.shape == (0,)
    assert res[0].info["latency_s"] >= 0
    assert engine.stats["prefills"] == 0  # no KV, no compute
    assert engine.step() is None  # quiescent afterwards
