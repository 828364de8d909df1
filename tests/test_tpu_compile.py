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
