"""Speculative decode in the real engine (paper §5.2, DESIGN.md §2).

The draft-verify megastep (``chain_decode_spec_fused``) must be bitwise
identical to the plain fused path — the verify pass reuses the exact
fused-step computation and the accept rule is verify-exact — so every
test here pins spec-ON token streams against a spec-OFF engine (itself
pinned against the per-hop oracle in test_fused_decode.py).  Forced
accept/reject corner the accept logic; preemption mid-speculation covers
the §5.1 interaction; the rest covers the adaptive gate, the surrogate
cache bound, and engine/simulator stat-name alignment.
"""
import dataclasses

import numpy as np
import pytest

from repro.serving.api import ServeRequest


@pytest.fixture(scope="module")
def demo():
    from repro.serving.demo import build_demo_zoo

    return build_demo_zoo(seed=0)


def _requests(cfg, n, seed=0, gen_lens=(6, 7, 8), apps=("base",), **kw):
    rng = np.random.RandomState(seed)
    return [ServeRequest(
        app=apps[i % len(apps)], gen_len=gen_lens[i % len(gen_lens)],
        prompt_tokens=rng.randint(0, cfg.vocab_size,
                                  size=int(rng.randint(8, 20)))
        .astype(np.int32), **kw) for i in range(n)]


def _serve(engine, reqs):
    rids = [engine.submit(r) for r in reqs]
    out = {r.rid: r for r in engine.drain()}
    assert sorted(out) == sorted(rids)
    return [out[r] for r in rids]


def _engine(zoo, max_len=64, **kw):
    from repro.serving.engine import BlockEngine, EngineConfig

    return BlockEngine(zoo, max_len=max_len, config=EngineConfig(**kw))


def _spec_pair(zoo, max_len=64, **kw):
    return (_engine(zoo, max_len, speculation=True, **kw),
            _engine(zoo, max_len, speculation=False))


# ---------------------------------------------------------------------------
# forced accept: prune_ratio=0 surrogates are the exact model
# ---------------------------------------------------------------------------


def test_forced_accept_token_exact(demo):
    """With prune_ratio=0 the surrogate keeps every FFN channel (identical
    weights, identical order), so every draft equals the verify argmax:
    all attempts hit, multiple tokens commit per step, and the stream is
    token-exact vs the spec-off engine."""
    cfg, zoo = demo
    spec, plain = _spec_pair(zoo, spec_prune_ratio=0.0)
    reqs = _requests(cfg, n=2, seed=7, gen_lens=(8,))
    got = _serve(spec, reqs)
    ref = _serve(plain, reqs)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g.tokens, r.tokens)
    assert spec.stats["spec_attempts"] > 0
    assert spec.stats["spec_hits"] == spec.stats["spec_attempts"]
    assert spec.metrics.gauge("spec_accept_rate").value == 1.0
    # accepting every draft takes fewer engine steps than one-token decode
    assert spec.stats["steps"] < plain.stats["steps"]
    # budget clamp held: exactly gen_len tokens, never an overshoot
    for g, req in zip(got, reqs):
        assert len(g.tokens) == req.gen_len


def test_forced_accept_near_budget_clamp(demo):
    """gen_len barely above the lookahead: the per-lane budget clamp must
    stop perfect drafts from committing past the generation budget."""
    cfg, zoo = demo
    spec, plain = _spec_pair(zoo, spec_prune_ratio=0.0, spec_lookahead=4)
    reqs = _requests(cfg, n=1, seed=11, gen_lens=(4,))
    got = _serve(spec, reqs)
    ref = _serve(plain, reqs)
    np.testing.assert_array_equal(got[0].tokens, ref[0].tokens)
    assert len(got[0].tokens) == 4


# ---------------------------------------------------------------------------
# forced reject: adversarial surrogate whose drafts never match
# ---------------------------------------------------------------------------


def _negate_lm_head(engine, app):
    """Pre-build the app's speculation state, then replace the surrogate
    chain's lm_head with a negated copy: draft argmaxes become the model's
    argmin, so verify rejects (essentially) every draft."""
    from repro.core.blocks import chain_signature

    steps = engine._steps(engine.zoo.chains[app], None)[0]
    sig = chain_signature(steps)
    ss = engine._spec_state(sig, steps)
    head, adapters = ss.sur_steps[-1]
    assert head.kind == "lm_head"
    import jax

    p = dict(head.params)
    p["lm_head"] = jax.tree.map(lambda x: -x, p["lm_head"])
    ss.sur_steps[-1] = (dataclasses.replace(head, id=head.id + "-neg",
                                            params=p), adapters)
    return ss


def test_forced_reject_token_exact(demo):
    """Every draft rejected: each spec step commits exactly one token (the
    verified pending token), output stays token-exact, and the hit counter
    stays at zero."""
    cfg, zoo = demo
    spec, plain = _spec_pair(zoo, spec_min_accept=0.0)  # gate never trips
    _negate_lm_head(spec, "base")
    reqs = _requests(cfg, n=2, seed=13, gen_lens=(6,))
    got = _serve(spec, reqs)
    ref = _serve(plain, reqs)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g.tokens, r.tokens)
    assert spec.stats["spec_attempts"] > 0
    assert spec.stats["spec_hits"] == 0
    # all-reject speculation commits one token per step, like plain decode
    assert spec.stats["steps"] == plain.stats["steps"]


def test_reject_gate_disables_then_retries(demo):
    """The accept-rate EMA disables a signature that keeps missing, and the
    cooldown re-enables it for a fresh trial ``spec_retry_steps`` later."""
    from repro.core.blocks import chain_signature

    cfg, zoo = demo
    spec = _engine(zoo, speculation=True, spec_min_accept=0.5,
                   spec_ema_alpha=0.5, spec_retry_steps=3)
    ss = _negate_lm_head(spec, "base")
    sig = chain_signature(spec._steps(zoo.chains["base"], None)[0])
    reqs = _requests(cfg, n=1, seed=17, gen_lens=(16,))
    spec.submit(reqs[0])
    seen_disabled = False
    while spec.step() is not None:
        if not ss.enabled:
            seen_disabled = True
            assert ss.cooldown > 0 or ss.ema == 1.0
    assert seen_disabled  # ema 1 -> 0.5 -> 0.25 < 0.5 after two misses
    assert spec._spec[sig] is ss


# ---------------------------------------------------------------------------
# mixed workloads: multi-app groups, partial accepts, still exact
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mixed_apps_token_exact(demo):
    """Six mixed-app mixed-gen_len requests at the default prune ratio:
    partial accepts, speculation-aware grouping, membership churn as short
    requests finish — token streams stay identical to spec-off."""
    cfg, zoo = demo
    spec, plain = _spec_pair(zoo)
    reqs = _requests(cfg, n=6, seed=19, gen_lens=(5, 9, 12),
                     apps=("base", "vicuna", "app-lora"))
    got = _serve(spec, reqs)
    ref = _serve(plain, reqs)
    for g, r, req in zip(got, ref, reqs):
        np.testing.assert_array_equal(
            g.tokens, r.tokens,
            err_msg=f"app={req.app} gen_len={req.gen_len} spec diverged")
    assert spec.stats["spec_attempts"] > 0
    assert 0 < spec.stats["spec_hits"] <= spec.stats["spec_attempts"]
    assert not spec.executor.decode_states  # all groups retired at drain


# ---------------------------------------------------------------------------
# preemption mid-speculation (§5.1 x §5.2)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["spill", "recalc"])
def test_preemption_mid_speculation_token_exact(demo, strategy):
    """Preempting a lane whose group has uncommitted spec buffers syncs the
    exact per-lane commit counts to host first; both §5.1 readmit paths
    resume token-exact, and the churn gate pauses speculation."""
    cfg, zoo = demo
    spec, plain = _spec_pair(zoo, spec_churn_steps=2)
    reqs = _requests(cfg, n=3, seed=23, gen_lens=(10, 12, 14))
    rids = [spec.submit(r) for r in reqs]
    spec.step()
    spec.step()  # groups device-resident with buffered spec commits
    assert any(spec.executor.buffered(r) > 0 for r in rids)
    assert spec.preempt(rids[0], strategy=strategy)
    assert spec._spec_churn == 2  # speculation paused after the preemption
    out = {r.rid: r for r in spec.drain()}
    ref = _serve(plain, reqs)
    for rid, r, req in zip(rids, ref, reqs):
        np.testing.assert_array_equal(
            out[rid].tokens, r.tokens,
            err_msg=f"app={req.app} diverged after {strategy} preemption")
    assert out[rids[0]].info["preemptions"] == 1
    key = "spills" if strategy == "spill" else "recalc_readmits"
    assert spec.stats[key] == 1
    assert all(p.used_pages == 0 for p in spec.pools.values())


# ---------------------------------------------------------------------------
# surrogate cache: bounded, keyed, evicts from the zoo
# ---------------------------------------------------------------------------


def test_surrogate_cache_eviction(demo):
    """The zoo's surrogate cache is a bounded LRU keyed by (parent id,
    ratio, prune_kv): hits return the cached id, eviction removes the
    surrogate block from the zoo, and a re-request rebuilds it."""
    _, zoo = demo
    layer_ids = [s.block_id for s in zoo.chains["base"].steps
                 if "w_gate" in zoo.blocks[s.block_id].params]
    assert len(layer_ids) >= 3
    # earlier tests in this module warm the shared zoo's cache; start
    # clean so hits/misses below are deterministic (eviction keeps the
    # cache and the zoo's block table consistent, so this is safe)
    for key, sid in list(zoo._surrogate_cache.items()):
        zoo.blocks.pop(sid, None)
        if zoo.surrogates.get(key[0]) == sid:
            del zoo.surrogates[key[0]]
    zoo._surrogate_cache.clear()
    zoo.surrogate_cache_max = 2
    a = zoo.surrogate_for(layer_ids[0], 0.25)
    assert zoo.surrogate_for(layer_ids[0], 0.25) == a  # cache hit
    b = zoo.surrogate_for(layer_ids[1], 0.25)
    c = zoo.surrogate_for(layer_ids[2], 0.25)  # evicts a (LRU)
    assert len(zoo._surrogate_cache) == 2
    assert a not in zoo.blocks  # evicted surrogates leave the zoo
    assert b in zoo.blocks and c in zoo.blocks
    # distinct ratios are distinct cache entries for the same parent
    d = zoo.surrogate_for(layer_ids[1], 0.5)
    assert d != b
    # rebuild after eviction is deterministic (same content hash -> id)
    assert zoo.surrogate_for(layer_ids[0], 0.25) == a
    assert a in zoo.blocks
    zoo.surrogate_cache_max = 32  # restore for other module-scoped tests


# ---------------------------------------------------------------------------
# stat-name alignment: engine, simulator, metrics registry
# ---------------------------------------------------------------------------


def test_spec_stat_keys_aligned(demo):
    """Both backends expose the same speculation stat names in the same
    places: ``spec_attempts``/``spec_hits`` counters (pre-registered, so
    they appear even before speculation runs) and a ``spec_accept_rate``
    gauge, plus ``spec_accept_rate`` in the simulator's report dict."""
    from repro.serving.simulator import (
        SchedulerConfig,
        Simulation,
        build_serving_config,
    )

    _, zoo = demo
    engine = _engine(zoo, speculation=True)
    sim = Simulation(build_serving_config(n_foundations=1, n_apps=2),
                     SchedulerConfig())
    for name in ("spec_attempts", "spec_hits"):
        assert name in engine.stats
        assert name in dict(sim.metrics_registry.counters_view())
    for m in (engine.metrics, sim.metrics_registry):
        assert m.gauge("spec_accept_rate").value == 0.0
    # the shared auto-CLI dataclass carries the engine-side knobs too
    for field in ("spec_lookahead", "spec_prune_ratio", "spec_min_accept"):
        assert hasattr(SchedulerConfig(), field)
