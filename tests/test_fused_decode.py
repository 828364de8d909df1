"""Fused chain-step decode: parity with the per-hop oracle, device-resident
state lifecycle, batched prefill, and executor cache bounds (DESIGN.md §2).

The fused megastep runs one jitted call per chain-signature group per
token (embedding -> every hop with paged-KV decode -> lm_head -> on-device
argmax/softmax) and keeps next-token/kv_len device-resident between steps.
The per-hop dispatch path (``EngineConfig(fused=False)``) is kept as the
parity oracle; these tests pin the two token-exact against each other.
"""
import numpy as np
import pytest

from repro.serving.api import ServeRequest


@pytest.fixture(scope="module")
def demo():
    from repro.serving.demo import build_demo_zoo

    return build_demo_zoo(seed=0)


def _requests(cfg, n, seed=0, gen_lens=(4, 5, 6), **kw):
    rng = np.random.RandomState(seed)
    apps = ["base", "vicuna", "app-lora"]
    return [ServeRequest(
        app=apps[i % 3], gen_len=gen_lens[i % len(gen_lens)],
        prompt_tokens=rng.randint(0, cfg.vocab_size,
                                  size=int(rng.randint(8, 20)))
        .astype(np.int32), **kw) for i in range(n)]


def _serve(engine, reqs):
    rids = [engine.submit(r) for r in reqs]
    out = {r.rid: r for r in engine.drain()}
    assert sorted(out) == sorted(rids)
    return [out[r] for r in rids]


def _engines(zoo, max_len=64, **kw):
    from repro.serving.engine import BlockEngine, EngineConfig

    fused = BlockEngine(zoo, max_len=max_len,
                        config=EngineConfig(fused=True, **kw))
    hop = BlockEngine(zoo, max_len=max_len,
                      config=EngineConfig(fused=False, **kw))
    return fused, hop


# ---------------------------------------------------------------------------
# parity: fused megastep == per-hop dispatch, token-exact
# ---------------------------------------------------------------------------


def test_fused_matches_per_hop_small(demo):
    """Two same-app requests with ragged prompts: one fused group, exact
    token parity with the per-hop oracle (fast smoke-tier case)."""
    cfg, zoo = demo
    fused, hop = _engines(zoo)
    reqs = _requests(cfg, n=2, seed=7, gen_lens=(3,))
    reqs[1].app = reqs[0].app  # single signature group
    got = _serve(fused, reqs)
    ref = _serve(hop, reqs)
    for g, r, req in zip(got, ref, reqs):
        np.testing.assert_array_equal(
            g.tokens, r.tokens, err_msg=f"app={req.app} fused diverged")
        np.testing.assert_allclose(g.probs_last, r.probs_last,
                                   rtol=0.05, atol=2e-3)
    assert not fused.executor.decode_states  # all groups retired at drain
    assert not fused.executor._rid_group


@pytest.mark.slow
def test_fused_matches_per_hop_mixed_apps(demo):
    """Eight mixed-app mixed-gen_len requests: several signature groups,
    membership churn as short requests finish; still token-exact."""
    cfg, zoo = demo
    fused, hop = _engines(zoo)
    reqs = _requests(cfg, n=8, seed=13)
    got = _serve(fused, reqs)
    ref = _serve(hop, reqs)
    for g, r, req in zip(got, ref, reqs):
        np.testing.assert_array_equal(
            g.tokens, r.tokens,
            err_msg=f"app={req.app} gen_len={req.gen_len} fused diverged")
    # the fused run needed far fewer device calls for the same tokens
    assert fused.stats["decode_tokens"] == hop.stats["decode_tokens"]
    assert fused.stats["group_calls"] * 4 < hop.stats["group_calls"]


@pytest.mark.slow
def test_fused_interleaved_submission(demo):
    """Requests joining mid-flight re-form fused groups (old DecodeStates
    retire, host state stays exact)."""
    cfg, zoo = demo
    fused, hop = _engines(zoo)
    reqs = _requests(cfg, n=4, seed=17, gen_lens=(6,))
    first = [fused.submit(r) for r in reqs[:2]]
    fused.step()
    fused.step()
    late = [fused.submit(r) for r in reqs[2:]]
    out = {r.rid: r for r in fused.drain()}
    assert sorted(out) == sorted(first + late)
    ref = _serve(hop, reqs)
    for rid, r in zip(first + late, ref):
        np.testing.assert_array_equal(out[rid].tokens, r.tokens)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["spill", "recalc"])
def test_fused_preemption_token_exact(demo, strategy):
    """Preempting a device-resident request mid-stream syncs its group
    before the spill/recalc touches host state; both §5.1 strategies
    resume token-exact under the fused path."""
    cfg, zoo = demo
    fused, hop = _engines(zoo)
    reqs = _requests(cfg, n=3, seed=19)
    rids = [fused.submit(r) for r in reqs]
    fused.step()
    fused.step()  # groups are device-resident with buffered tokens
    assert fused.executor.buffered(rids[0]) > 0
    assert fused.preempt(rids[0], strategy=strategy)
    out = {r.rid: r for r in fused.drain()}
    ref = _serve(hop, reqs)
    for rid, r, req in zip(rids, ref, reqs):
        np.testing.assert_array_equal(
            out[rid].tokens, r.tokens,
            err_msg=f"app={req.app} diverged after {strategy} preemption")
    assert out[rids[0]].info["preemptions"] == 1
    key = "spills" if strategy == "spill" else "recalc_readmits"
    assert fused.stats[key] == 1
    assert all(p.used_pages == 0 for p in fused.pools.values())


@pytest.mark.slow
def test_fused_interpret_attention_parity(demo):
    """The Pallas kernel in interpret mode feeds the fused megastep the
    same numbers as the reference attention: token-exact across impls."""
    cfg, zoo = demo
    fused_ref, _ = _engines(zoo)
    fused_int, _ = _engines(zoo, attn_impl="interpret")
    reqs = _requests(cfg, n=2, seed=23, gen_lens=(3,))
    got = _serve(fused_int, reqs)
    ref = _serve(fused_ref, reqs)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g.tokens, r.tokens)


@pytest.mark.parametrize("B", [1, 3])
def test_megastep_writes_kv_in_place(demo, B):
    """The compiled megastep of a LoRA chain writes each lane's new K/V
    token into the donated pool slabs in place: no copy or transpose of a
    whole slab anywhere in the optimized program, at one lane or several."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core.blocks import chain_signature
    from repro.serving.executor import BlockExecutor

    cfg, zoo = demo
    steps = [(zoo.blocks[s.block_id], tuple(zoo.blocks[a]
                                            for a in s.adapter_ids))
             for s in zoo.chains["app-lora"].steps]
    fn, _ = BlockExecutor(attn_impl="ref").fused_fn(
        steps, chain_signature(steps))
    hops = sum(b.has_kv for b, _ in steps)
    n = 4
    shape = (1 + B * n * hops, cfg.num_kv_heads, 16, cfg.resolved_head_dim)
    # float32: the CPU backend computes a bf16 update in float32, which
    # converts whole slabs on its own
    pool = jax.ShapeDtypeStruct(shape, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = fn.func.lower(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     fn.args[0]),
        i32(B), (pool,), (pool,), tuple(i32(B, n) for _ in range(hops)),
        i32(B)).compile().as_text()
    slab = re.escape("f32[" + ",".join(map(str, shape)) + "]")
    assert re.search(slab + r"\{[^}]*\} dynamic-update-slice\(", text)
    relayouts = re.findall(slab + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
    assert not relayouts, f"{len(relayouts)} whole-slab copies at B={B}"


# ---------------------------------------------------------------------------
# generate(): gen_len=0 regression
# ---------------------------------------------------------------------------


def test_generate_gen_len_zero(demo):
    """gen_len=0 returns a clean (B, 0) token array and probs_last=None
    instead of crashing on np.stack over missing distributions."""
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    rng = np.random.RandomState(29)
    prompts = rng.randint(0, cfg.vocab_size, size=(3, 12)).astype(np.int32)
    res = engine.generate(zoo.chains["base"], prompts, gen_len=0)
    assert res.tokens.shape == (3, 0)
    assert res.probs_last is None
    assert engine.step() is None  # engine quiescent afterwards


# ---------------------------------------------------------------------------
# executor cache bounds
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_table_cache_bounded_under_churn(demo):
    """The per-hop block-table cache is an LRU: with the cap forced below
    the per-step working set (4 attention hops per chain) eviction runs
    every step, the bound holds, and tokens stay exact."""
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64, config=EngineConfig(fused=False))
    engine.executor.table_cache_max = 2
    reqs = _requests(cfg, n=6, seed=31)  # mixed gen_lens: membership churn
    rids = [engine.submit(r) for r in reqs]
    done = []
    cap_seen = 0
    while True:
        res = engine.step()
        cap_seen = max(cap_seen, len(engine.executor._table_cache))
        if res is None:
            break
        done.extend(res)
    assert cap_seen <= 2
    assert sorted(r.rid for r in done) == sorted(rids)  # all completed


def test_fused_fn_rejects_sliding_window(demo):
    """Chains the megastep cannot compile raise NotImplementedError, which
    the engine catches to route the group to the per-hop path."""
    import dataclasses

    from repro.core.blocks import chain_signature
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    steps = engine._steps(zoo.chains["base"], None)[0]
    swapped = []
    for block, adapters in steps:
        if block.has_kv:
            block = dataclasses.replace(
                block, cfg=dataclasses.replace(block.cfg, sliding_window=4))
        swapped.append((block, adapters))
    with pytest.raises(NotImplementedError):
        engine.executor.fused_fn(swapped, chain_signature(swapped) + ("sw",))
