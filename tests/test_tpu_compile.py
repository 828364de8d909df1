"""Compile the served Pallas kernel and decode megastep for a described TPU
v5e chip (nothing attached): what Mosaic or XLA would refuse on the chip is
refused here.  Interpret-mode kernel tests cannot see tiling or VMEM
limits.

The topology is described inside a fixture, never at import, so that every
test worker collects the same tests and only the worker running this file
loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ops import paged_decode_step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-topology compile lands in the persistent cache but can
    # never be read back without a chip: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "blockllm-demo"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_kernel_compiles_for_v5e(one_chip, arch, dtype):
    """Head-major pages at the config's (KVH, head_dim), page 16, a batch
    of 8 sequences of 544 tokens — the serving smoke check's shapes."""
    cfg = get_config(arch)
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, page, n = 8, 16, 34
    pages = _sds((1 + B * n, KVH, page, hd), dtype, one_chip)
    compiled = jax.jit(paged_attention).lower(
        _sds((B, H, hd), dtype, one_chip), pages, pages,
        _sds((B, n), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_demo_megastep_compiles_for_v5e(one_chip):
    """The executor's fused decode program for a LoRA chain at demo width
    compiles with the Pallas kernel on every attention hop and takes the
    weights as arguments (no weight constants in the program)."""
    from repro.core import peft
    from repro.core.blocks import chain_signature
    from repro.core.zoo import BlockZoo
    from repro.models.model import build_model
    from repro.serving.executor import BlockExecutor

    cfg = get_config("blockllm-demo")
    zoo = BlockZoo()
    zoo.register_foundation("base", cfg,
                            build_model(cfg).init(jax.random.PRNGKey(0)))
    zoo.register_peft("app", cfg, "base", "lora",
                      peft.create_lora(cfg, jax.random.PRNGKey(1)))
    steps = [(zoo.blocks[s.block_id], tuple(zoo.blocks[a]
                                            for a in s.adapter_ids))
             for s in zoo.chains["app"].steps]
    fn, _ = BlockExecutor(attn_impl="pallas").fused_fn(
        steps, chain_signature(steps))
    on_chip = lambda t: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), t)
    hops = sum(b.has_kv for b, _ in steps)
    B, n = 3, 4
    pool = _sds((1 + B * n * hops, cfg.num_kv_heads, 16,
                 cfg.resolved_head_dim), jnp.bfloat16, one_chip)
    lowered = fn.func.lower(
        on_chip(fn.args[0]), _sds((B,), jnp.int32, one_chip), (pool,),
        (pool,), tuple(_sds((B, n), jnp.int32, one_chip)
                       for _ in range(hops)),
        _sds((B,), jnp.int32, one_chip))
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == hops
    # weights stayed arguments: the program is far smaller than them
    assert len(lowered.as_text()) < zoo.zoo_bytes() // 4


def test_multi_lane_kv_write_in_place_for_v5e(one_chip):
    """Two donated megastep hops at DeepSeek-LLM-7B's pool (385 pages of
    512 tokens, 32 KV heads of 128, bf16) with 4 lanes and the Pallas
    kernel: each lane's K/V token is written in place, and no op copies a
    whole slab between the write and the kernel."""
    P, KVH, page, hd, B, n = 385, 32, 512, 128, 4, 8

    def two_hops(q, k_new, v_new, k_pages, v_pages, tables, kv_len):
        for _ in range(2):
            q, k_pages, v_pages = paged_decode_step(
                q, k_new, v_new, k_pages, v_pages, tables, kv_len,
                impl="pallas")
            q, k_pages, v_pages = jax.lax.optimization_barrier(
                (q, k_pages, v_pages))
        return q, k_pages, v_pages

    tok = _sds((B, KVH, hd), jnp.bfloat16, one_chip)
    pages = _sds((P, KVH, page, hd), jnp.bfloat16, one_chip)
    text = jax.jit(two_hops, donate_argnums=(3, 4)).lower(
        tok, tok, tok, pages, pages, _sds((B, n), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    slab = re.escape(f"bf16[{P},{KVH},{page},{hd}]")
    copies = re.findall(slab + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
    assert not copies, f"{len(copies)} whole-slab copies"


def test_llama_megastep_outputs_are_unchanged_for_v5e(one_chip):
    """A chain without expert layers compiles the megastep it had before
    the MoE counters: tokens, probs, one K and one V slab, kv_len."""
    from repro.configs.base import ModelConfig
    from repro.core.blocks import Block, chain_signature
    from repro.serving.executor import BlockExecutor

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=256,
                      num_heads=2, num_kv_heads=2, d_ff=512, vocab_size=512,
                      head_dim=128)
    d, f, H, hd = 256, 512, 2, 128
    sds = lambda *s: _sds(s, jnp.bfloat16, one_chip)  # noqa: E731
    layer = {"ln1": sds(d), "ln2": sds(d), "wq": sds(d, H, hd),
             "wk": sds(d, H, hd), "wv": sds(d, H, hd), "wo": sds(H, hd, d),
             "w_gate": sds(d, f), "w_up": sds(d, f), "w_down": sds(f, d)}
    steps = [(Block("e", "embed", "t", None, 1, d, {"embed": sds(512, d)},
                    cfg), ()),
             (Block("l", "layer", "t", 0, d, d, layer, cfg), ()),
             (Block("h", "lm_head", "t", None, d, 512,
                    {"final_ln": sds(d), "lm_head": sds(d, 512)}, cfg), ())]
    fn, keys = BlockExecutor(attn_impl="pallas").fused_fn(
        steps, chain_signature(steps))
    assert keys == ((H, hd),)
    B, n = 2, 2
    pool = _sds((1 + B * n, H, 16, hd), jnp.bfloat16, one_chip)
    lowered = fn.func.lower(
        fn.args[0], _sds((B,), jnp.int32, one_chip), (pool,), (pool,),
        (_sds((B, n), jnp.int32, one_chip),), _sds((B,), jnp.int32, one_chip))
    assert len(jax.tree.leaves(lowered.out_info)) == 5
    text = lowered.compile().as_text()
    assert "%gmm" not in text and "mla_paged_attention" not in text


def _deepseek_v2_steps(sharding, lora: bool):
    """A DeepSeek-V2-Lite chain at published widths (layer 0 dense, then
    one MoE layer), as shapes on ``sharding``."""
    from repro.configs.base import ModelConfig
    from repro.core.blocks import Block

    d, H, R, rope, nope, vd, E, fe, V = (2048, 16, 512, 64, 128, 128, 64,
                                         1408, 102400)
    cfg = ModelConfig(
        name="dsv2", family="moe", num_layers=2, d_model=d, num_heads=H,
        num_kv_heads=H, d_ff=10944, vocab_size=V, num_experts=E,
        num_experts_per_tok=6, kv_lora_rank=R, qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=vd, rope_scaling=(
            ("beta_fast", 32.0), ("beta_slow", 1.0), ("factor", 40.0),
            ("mscale", 0.707), ("mscale_all_dim", 0.707),
            ("original_max_position_embeddings", 4096.0)))
    sds = lambda *s: _sds(s, jnp.bfloat16, sharding)  # noqa: E731
    attn = {"ln1": sds(d), "q_proj": sds(d, H, nope + rope),
            "kv_a_proj_with_mqa": sds(d, R + rope), "kv_a_layernorm": sds(R),
            "kv_b_proj": sds(R, H, nope + vd), "o_proj": sds(H, vd, d)}
    ffns = [{"ln2": sds(d), "w_gate": sds(d, 10944), "w_up": sds(d, 10944),
             "w_down": sds(10944, d)},
            {"ln2": sds(d), "router": sds(d, E), "e_gate": sds(E, d, fe),
             "e_up": sds(E, d, fe), "e_down": sds(E, fe, d),
             "shared_gate": sds(d, 2 * fe), "shared_up": sds(d, 2 * fe),
             "shared_down": sds(2 * fe, d)}]
    ad = {"a_q": sds(d, 8), "b_q": sds(8, H * (nope + rope)),
          "a_kv": sds(d, 8), "b_kv": sds(8, R + rope),
          "scaling": _sds((), jnp.float32, sharding)}
    steps = [(Block("e", "embed", "m", None, 1, d, {"embed": sds(V, d)},
                    cfg), ())]
    for i, ffn in enumerate(ffns):
        if lora:
            steps.append((Block(f"a{i}", "attention", "m", i, d, d, attn, cfg),
                          (Block(f"l{i}", "lora", "m", i, d, d, ad, cfg),)))
            steps.append((Block(f"f{i}", "ffn", "m", i, d, d, ffn, cfg), ()))
        else:
            steps.append((Block(f"y{i}", "layer", "m", i, d, d,
                                {**attn, **ffn}, cfg), ()))
    steps.append((Block("h", "lm_head", "m", None, d, V,
                        {"final_ln": sds(d), "lm_head": sds(d, V)}, cfg), ()))
    return steps


@pytest.mark.parametrize("lora", [False, True])
def test_deepseek_v2_megastep_compiles_for_v5e(one_chip, lora):
    """The fused decode megastep of a DeepSeek-V2-Lite chain at published
    widths, 8 lanes, over the latent pool (1121 pages of 512 tokens, one
    576-wide row a token): one MLA kernel per attention hop, three grouped
    matmuls per expert layer, each lane's latent row written in place (no
    whole-slab copy), and the routing counts as one more output."""
    from repro.core.blocks import chain_signature
    from repro.serving.executor import BlockExecutor

    steps = _deepseek_v2_steps(one_chip, lora)
    fn, keys = BlockExecutor(attn_impl="pallas").fused_fn(
        steps, chain_signature(steps))
    assert keys == (("latent", 576),)
    B, n, P = 8, 5, 1121
    pool = _sds((P, 1, 576, 512), jnp.bfloat16, one_chip)
    lowered = fn.func.lower(
        fn.args[0], _sds((B,), jnp.int32, one_chip), (pool,), (None,),
        tuple(_sds((B, n), jnp.int32, one_chip) for _ in range(2)),
        _sds((B,), jnp.int32, one_chip), _sds((3,), jnp.int32, one_chip))
    assert len(jax.tree.leaves(lowered.out_info)) == 5
    text = lowered.compile().as_text()
    calls = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(", text)
    assert sorted(set(calls)) == ["gmm", "mla_paged_attention"]
    assert calls.count("mla_paged_attention") == 2 and calls.count("gmm") == 3
    slab = re.escape(f"bf16[{P},1,576,512]")
    copies = re.findall(slab + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
    assert not copies, f"{len(copies)} whole-slab copies"


@pytest.mark.parametrize("m", [48, 98304])
def test_grouped_matmul_compiles_for_v5e(one_chip, m):
    """The routed experts' three grouped matmuls at DeepSeek-V2-Lite's
    widths (64 experts, 2048 -> 1408 -> 2048), for a decode call of 8
    lanes x top-6 and for a prefill of 8 x 2048 tokens x top-6."""
    from repro.kernels.grouped_matmul import grouped_matmul

    def experts(x, wg, wu, wd, sizes):
        g = grouped_matmul(x, wg, sizes, impl="pallas")
        u = grouped_matmul(x, wu, sizes, impl="pallas")
        return grouped_matmul(jax.nn.silu(g) * u, wd, sizes, impl="pallas")

    bf = jnp.bfloat16
    text = jax.jit(experts).lower(
        _sds((m, 2048), bf, one_chip), _sds((64, 2048, 1408), bf, one_chip),
        _sds((64, 2048, 1408), bf, one_chip),
        _sds((64, 1408, 2048), bf, one_chip),
        _sds((64,), jnp.int32, one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
