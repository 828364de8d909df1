"""DeepSeek-V2 (multi-head latent attention + DeepSeekMoE) on the serving
path, on the CPU at a tiny size, against the benchmark's own float32
reference (``benchmarks/chip/archs/deepseek_v2.py``) and against the
published equations worked by hand."""
from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import model  # noqa: E402
from benchmarks.chip.archs import deepseek_v2 as arch  # noqa: E402
from repro.core.blocks import is_mla, is_moe  # noqa: E402
from repro.models import deepseek_v2 as DS  # noqa: E402
from repro.serving.api import ServeRequest  # noqa: E402
from repro.serving.engine import BlockEngine, EngineConfig  # noqa: E402
from repro.serving.kv_pool import KVManager  # noqa: E402

SEED = 2 ** 40 + 17
# every mechanism of the published layer at test widths: 4 heads over a
# 32 + 16 latent, nope 16, v 16; layer 0 dense, then 8 experts top-2
# and one shared expert
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_rope_head_dim=16,
            qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=8,
            num_experts_per_tok=2, n_shared_experts=1,
            moe_intermediate_size=32, num_hidden_layers=3, vocab_size=256)


def tiny_cfg(**kw) -> dict:
    cfg = model.load_config("deepseek-v2-lite-l7")
    cfg.update(TINY, **kw)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    weights = arch.make_weights(cfg, SEED)
    return cfg, weights, arch.build_zoo(cfg, weights)


def _serve(zoo, impl: str, prompts):
    engine = BlockEngine(zoo, max_len=64, config=EngineConfig(
        max_active=8, max_block_batch=4, page_size=16, num_pages=64,
        attn_impl=impl))
    rids = [engine.submit(ServeRequest(app=a, gen_len=10, prompt_tokens=p))
            for a, p in prompts]
    res = {r.rid: r for r in engine.drain()}
    return engine, [res[r] for r in rids]


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_engine_matches_the_reference(tiny, impl):
    """Prefill, then fused decode through the latent pool, for the
    foundation and a LoRA tenant, two lanes of one app in one megastep.
    Tolerances: the program runs in bf16 and the reference in float32; at
    these widths bf16 moves a logit by ~1e-2.  The widest gap by which a
    served token's reference logit lies below the best is held to 0.1,
    and the last step's probabilities to 0.02 of the reference's (a
    wrong rotation, scale, absorption or expert moves either by ~1)."""
    cfg, weights, zoo = tiny
    rng = np.random.default_rng(3)
    prompts = [(a, rng.integers(0, 256, n).astype(np.int32))
               for a, n in (("base", 21), ("lora-1", 30), ("base", 17))]
    engine, results = _serve(zoo, impl, prompts)
    assert engine.kv.pools.keys() == {("latent", 48)}
    for (app, p), r in zip(prompts, results):
        toks = r.tokens
        seq = np.concatenate([p, toks[:-1]])
        ref = arch.logits(cfg, weights, app, seq,
                          np.arange(len(p) - 1, len(seq)))
        gap = (ref.max(-1) - ref[np.arange(len(toks)), toks]).max()
        assert gap <= 0.1, (app, gap)
        want = np.asarray(jax.nn.softmax(ref[-1]))
        np.testing.assert_allclose(r.probs_last, want, atol=0.02)
    # three lanes in two groups over 9 decode steps and 2 expert layers
    st = engine.stats
    assert st["moe_decode_layer_calls"] == 2 * 2 * 9
    assert st["moe_decode_pairs"] == 3 * 2 * 2 * 9
    assert 0 < st["moe_decode_experts_hit"] <= st["moe_decode_pairs"]


def _f32_layer(key):
    cfg = arch.model_config(tiny_cfg(torch_dtype="float32"))
    m = arch._dims(tiny_cfg())
    p = arch._attn_weights(m, key, jnp.float32)
    return cfg, p


def test_absorbed_decode_equals_non_absorbed_attention():
    """In float32, the absorbed decode over the latent pool (the paged
    kernel in interpret mode) gives the last position of the non-absorbed
    prefill over the same sequence, and writes the same latent row."""
    from repro.core.blocks import Block

    cfg, p = _f32_layer(jax.random.PRNGKey(5))
    S, page, n = 21, 8, 3
    x = jax.random.normal(jax.random.PRNGKey(6), (1, S, 64), jnp.float32)
    pos = jnp.arange(S)[None]
    full, lat = DS.mla_prefill(x, p, cfg, pos)
    kv = KVManager(page, 1 + n, dtype=jnp.float32)
    block = Block("a", "attention", "t", 0, 64, 64, p, cfg)
    _, pool = kv.pool_for(block)
    pool.alloc(0, 0, S)
    pool.write_prefill(0, 0, lat[:, :S - 1])
    tables = jnp.asarray(pool.block_table([(0, 0)]))
    out, pages = DS.mla_decode_paged(
        x[:, S - 1:], p, cfg, pool.k_pages, tables,
        jnp.asarray([S - 1], jnp.int32), attn_impl="interpret")
    np.testing.assert_allclose(np.asarray(out[0, 0]),
                               np.asarray(full[0, S - 1]), rtol=1e-4,
                               atol=1e-4)
    pool.k_pages = pages
    pg, col = pool.slots[(0, 0)].pages[(S - 1) // page], (S - 1) % page
    np.testing.assert_allclose(np.asarray(pages[pg, 0, :, col]),
                               np.asarray(lat[0, S - 1, 0]), rtol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_grouped_experts_equal_dense_masked_evaluation(impl):
    """The dropless grouped path equals every expert evaluated for every
    token and masked by the router's top-k weights, in float32; the router
    picks the top-k of the float32 softmax, weights not renormalized."""
    cfg = arch.model_config(tiny_cfg())
    m = arch._dims(tiny_cfg())
    p = arch._ffn_weights(m, jax.random.PRNGKey(7), jnp.float32, moe=True)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 13, 64), jnp.float32)
    stats = []
    got = DS.moe_ffn(h, p, cfg, impl=impl, stats=stats)

    x = np.asarray(h, np.float64).reshape(-1, 64)
    P = {k: np.asarray(v, np.float64) for k, v in p.items()}
    lg = x @ P["router"]
    probs = np.exp(lg - lg.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1)[:, :2]
    w, idx = DS.route(jnp.asarray(h.reshape(-1, 64)), p["router"], 2, 1.0)
    assert (np.sort(np.asarray(idx), -1) == np.sort(top, -1)).all()
    np.testing.assert_allclose(np.asarray(w).sum(-1),
                               np.take_along_axis(probs, top, -1).sum(-1),
                               rtol=1e-5)

    def swiglu(g, u, dn):
        a = x @ g
        return ((a / (1 + np.exp(-a))) * (x @ u)) @ dn

    want = swiglu(P["shared_gate"], P["shared_up"], P["shared_down"])
    for e in range(8):
        mask = (top == e).any(-1) * probs[:, e]
        want += mask[:, None] * swiglu(P["e_gate"][e], P["e_up"][e],
                                       P["e_down"][e])
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64), want,
                               rtol=2e-4, atol=2e-4)
    (pairs, hit), = stats
    assert pairs == 26 * 2 and int(hit) == len(np.unique(top))


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """DeepSeek-V2-Lite's YaRN: 64 rope dims, theta 1e4, factor 40 over
    4096 positions, beta 32 / 1.  The ramp runs from dim index
    floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4)) = floor(10.47) = 10 to
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = ceil(22.51) = 23: below it
    the plain frequency, above it the plain one / 40, linear between."""
    sc = {"factor": 40, "original_max_position_embeddings": 4096,
          "beta_fast": 32, "beta_slow": 1}
    inv = np.asarray(DS.yarn_inv_freq(64, 1e4, sc), np.float64)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    r = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], plain[16] / 40 * r
                               + plain[16] * (1 - r), rtol=1e-6)
    cfg = arch.model_config(model.load_config("deepseek-v2-lite-l7"))
    # 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2
    assert DS.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.0707 * math.log(40) + 1) ** 2)
    assert DS.softmax_scale(cfg) == pytest.approx(0.1147, abs=5e-5)
    # mscale / mscale_all_dim = 1: cos and sin are not scaled
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 64))
    r1 = DS.rope(x, jnp.arange(3)[None], cfg)
    assert np.allclose(np.linalg.norm(r1, axis=-1),
                       np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_latent_pages_hold_1152_bytes_a_token_and_no_v():
    """At published widths a latent page of 512 tokens is 512 x (512 +
    64) bf16 values, and the pool keeps no V slab."""
    from repro.core.blocks import Block

    cfg = arch.model_config(model.load_config("deepseek-v2-lite-l7"))
    block = Block("a", "attention", "t", 0, 2048, 2048,
                  {"kv_b_proj": None}, cfg)
    assert block.kv_signature == ("latent", 576)
    kv = KVManager(512, 2, dtype=jnp.bfloat16)
    _, pool = kv.pool_for(block)
    assert pool.page_bytes == 512 * 1152
    assert pool.v_pages is None and pool.k_pages.shape == (2, 1, 576, 512)


def test_latent_spill_restore_round_trips(tiny):
    """A preempted request's latent pages go to the host and come back,
    on other pages, exactly as they were."""
    _, _, zoo = tiny
    blocks = [b for b in zoo.blocks.values() if b.has_kv]
    kv = KVManager(16, 12, dtype=jnp.bfloat16)
    _, pool = kv.pool_for(blocks[0])
    pool.alloc(7, 1, 40)
    pool.alloc(8, 1, 16)
    rows = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 1, 48))
    pool.write_prefill(7, 1, rows)
    before = np.asarray(pool.k_pages[jnp.asarray(pool.slots[(7, 1)].pages)])
    snap = kv.spill(7)
    assert (7, 1) not in pool.slots and snap.kv_bytes == 3 * 16 * 48 * 2
    pool.alloc(9, 1, 16)  # the freed pages move on
    kv.restore(7, snap, 40)
    after = np.asarray(pool.k_pages[jnp.asarray(pool.slots[(7, 1)].pages)])
    np.testing.assert_array_equal(after, before)


def test_split_keeps_every_mla_and_moe_key(tiny):
    """A LoRA tenant splits each layer into attention and FFN blocks; no
    weight is left behind, and a key of neither sublayer is an error."""
    _, weights, zoo = tiny
    for i, lp in enumerate(weights["base"]["layers"]):
        lid = zoo.chains["base"].steps[1 + i].block_id
        att, ffn = (zoo.blocks[b] for b in zoo.split_layer_block(lid))
        assert set(att.params) | set(ffn.params) == set(lp)
        assert is_mla(att.params) and not is_mla(ffn.params)
        assert is_moe(ffn.params) == (i >= 1)
    blk = zoo.blocks[zoo.chains["base"].steps[2].block_id]
    blk.params["stray"], blk.meta = blk.params["ln1"], {}
    with pytest.raises(ValueError, match="stray"):
        zoo.split_layer_block(blk.id)
    del blk.params["stray"]


def test_expert_layer_flops_count_active_params(tiny):
    cfg, _, zoo = tiny
    base = [zoo.blocks[s.block_id] for s in zoo.chains["base"].steps]
    moe = base[2]
    routed = 3 * 64 * 32  # one expert's gate, up and down
    assert moe.flops_per_token() == 2.0 * (moe.n_params - 6 * routed)
    assert base[1].flops_per_token() == 2.0 * base[1].n_params


def test_per_hop_and_speculative_paths_refuse_mla_and_moe(tiny):
    from repro.serving.executor import BlockExecutor

    _, _, zoo = tiny
    steps = [(zoo.blocks[s.block_id], ()) for s in zoo.chains["base"].steps]
    ex = BlockExecutor()
    with pytest.raises(NotImplementedError, match="MLA or MoE"):
        ex.block_fn(*steps[1])
    with pytest.raises(NotImplementedError, match="MLA or MoE"):
        ex.spec_fn(steps, steps, ("sig",), 2)
