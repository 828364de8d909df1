"""The serving program's own measurement points (DESIGN.md §8): every
jitted program carries a name of its own into the device trace, and the
engine writes its layer spans on the profiler's clock."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.api import ServeRequest

PROGRAMS = ("chain_decode", "chain_decode_spec", "chain_prefill",
            "block_decode", "block_apply", "block_prefill",
            "kv_write_prefill")
SPANS = ("engine.step", "engine.finish", "sched.admit", "sched.form_groups",
         "kv.alloc", "kv.write_prefill", "exec.prefill", "exec.dispatch",
         "exec.sync", "exec.make_state")


@pytest.fixture(scope="module")
def demo():
    from repro.serving.demo import build_demo_zoo

    return build_demo_zoo(seed=0)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _lower(name, zoo):
    """The module text of program ``name`` as the engine builds it for
    the LoRA app of the demo zoo, lowered at small shapes."""
    from repro.core.blocks import chain_signature
    from repro.models.layers import COMPUTE_DTYPE
    from repro.serving.engine import BlockEngine
    from repro.serving.kv_pool import kv_write_prefill

    engine = BlockEngine(zoo, max_len=64)
    ex, kv = engine.executor, engine.kv
    steps, _ = engine._steps(zoo.chains["app-lora"], None)
    sig = chain_signature(steps)
    attn = [(b, a) for b, a in steps if b.has_kv]
    B, n = 2, 4
    tables = tuple(_i32(B, n) for _ in attn)

    def x(block, S=1, rows=B):
        return jax.ShapeDtypeStruct((rows, S, block.d_in), COMPUTE_DTYPE)

    def lowered(fn, *args):  # fn: partial(jitted program, weights...)
        return fn.func.lower(*fn.args, *args).as_text()

    def slabs(keys):
        pools = [kv.pools[k] for k in keys]
        return (tuple(p.k_pages for p in pools),
                tuple(p.v_pages for p in pools))

    block, ads = attn[0]
    _, pool = kv.pool_for(block)
    if name == "chain_decode":
        fn, keys = ex.fused_fn(steps, sig)
        return lowered(fn, _i32(B), *slabs(keys), tables, _i32(B))
    if name == "chain_decode_spec":
        sur = engine._spec_state(sig, steps).sur_steps
        fn, keys = ex.spec_fn(steps, sur, sig, 4)
        return lowered(fn, _i32(B), *slabs(keys), tables, _i32(B), _i32(B))
    if name == "chain_prefill":
        return lowered(ex.chain_prefill_fn(steps, sig), _i32(B, 16), _i32(B))
    if name == "block_decode":
        return lowered(ex.block_fn(block, ads), x(block), pool.k_pages,
                       pool.v_pages, _i32(B, n), _i32(B))
    if name == "block_apply":
        head, head_ads = steps[-1]
        assert not head.has_kv
        return lowered(ex.block_fn(head, head_ads), x(head))
    if name == "block_prefill":
        return lowered(ex.prefill_fn(block, ads), x(block, S=16, rows=1))
    assert name == "kv_write_prefill"
    new = jax.ShapeDtypeStruct((1, 20, pool.kv_heads, pool.head_dim),
                               pool.k_pages.dtype)
    return kv_write_prefill.lower(pool.k_pages, new, _i32(2)).as_text()


@pytest.mark.parametrize("name", PROGRAMS)
def test_every_served_program_lowers_under_its_own_name(demo, name):
    _, zoo = demo
    text = _lower(name, zoo)
    assert re.search(rf"module @jit_{name}\b", text), text[:200]
    assert "jit_fn" not in text


def _requests(cfg, n=4, seed=0):
    rng = np.random.RandomState(seed)
    apps = ["base", "vicuna", "app-lora"]
    return [ServeRequest(app=apps[i % 3], gen_len=3 + i % 2,
                         prompt_tokens=rng.randint(
                             0, cfg.vocab_size, size=8 + 3 * i)
                         .astype(np.int32)) for i in range(n)]


def test_engine_writes_its_spans_on_the_profiler_clock(demo, tmp_path):
    from jax.profiler import ProfileData

    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    with jax.profiler.trace(str(tmp_path)):
        for r in _requests(cfg):
            engine.submit(r)
        engine.drain()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    host = [e for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if not e.name.startswith("$")]
    names = {e.name for e in host}
    assert set(SPANS) <= names
    assert not any(n.startswith("bench.") for n in names)
    steps = [(e.start_ns, e.start_ns + e.duration_ns) for e in host
             if e.name == "engine.step"]
    dispatch = [(e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for e in host if e.name == "exec.dispatch"]
    assert dispatch
    for a, b, st in dispatch:
        assert any(s <= a and b <= e for s, e in steps)
        assert st["B"] >= 1 and st["app"] in zoo.chains
    # the engine's own step track (Chrome export) is still written
    assert {s[0] for s in engine.tracer.global_spans} == {"engine_step"}


def test_no_per_step_event_and_no_config_gauge(demo):
    """A request's trace holds its lifecycle boundaries, not one event per
    decode step, and the registry holds no copy of the batch cap."""
    from repro.serving.engine import BlockEngine

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64)
    for r in _requests(cfg, n=2, seed=1):
        engine.submit(r)
    out = engine.drain()
    for res in out:
        names = [e["name"] for e in res.info["trace"]["events"]]
        assert names == ["submit", "admit", "prefill", "finish"]
    assert "max_block_batch" not in engine.metrics.snapshot()["gauges"]


def test_kv_page_counters_count_reserved_and_live_pages(demo):
    """Each step adds every pool's pages in use and each resident
    request's cached tokens, in whole pages, on each attention hop."""
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = demo
    engine = BlockEngine(zoo, max_len=64, config=EngineConfig(page_size=8))
    for r in _requests(cfg, n=3, seed=2):
        engine.submit(r)
    reserved = live = 0
    while engine.active or engine.scheduler.waiting:
        reserved += sum(p.used_pages for p in engine.kv.pools.values())
        live += sum(
            sum(b.has_kv for b, _ in s.steps)
            * -(-(s.kv_len + engine.executor.buffered(s.rid)) // 8)
            for s in engine.active)
        engine.step()
    assert engine.stats["kv_page_steps_reserved"] == reserved > 0
    assert engine.stats["kv_page_steps_live"] == live > 0
    assert reserved >= live
