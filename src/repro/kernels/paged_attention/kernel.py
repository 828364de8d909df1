"""Paged decode attention (TPU Pallas) — BlockLLM's KV-cache layer.

PagedAttention (vLLM) adapted to TPU (DESIGN.md §2): KV lives in HBM page
pools laid out head-major, ``(num_pages, KVH, page_size, hd)``; each
sequence owns a row of the ``block_tables``.  The page table is a
**scalar-prefetch** operand (PrefetchScalarGridSpec) so the BlockSpec
index_map can chase page pointers at DMA-issue time.  Head-major pages make
each grid step's K/V block the slab's whole trailing ``(page_size, hd)``
tile: Mosaic requires a block's last two dims to be (8, 128)-divisible or
equal to the array's, which a ``(page_size, 1, hd)`` slice of a
page-major slab is not.

Grid: (B, KVH, pages_per_seq); the page dim is innermost/"arbitrary" so the
online-softmax scratch persists across a sequence's pages.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(block_tables, seq_lens,  # scalar-prefetch
                  q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref,
                  *, page_size: int, pages_per_seq: int, sm_scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens[b]
    page_start = pi * page_size

    @pl.when(page_start < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (page_size, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale  # (G, page_size)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(pi == pages_per_seq - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    *, sm_scale: float | None = None,
                    interpret: bool = False):
    """q: (B, Hq, hd); k_pages/v_pages: (num_pages, KVH, page_size, hd);
    block_tables: (B, pages_per_seq) int32; seq_lens: (B,) int32.

    Returns (B, Hq, hd).
    """
    B, Hq, hd = q.shape
    num_pages, KVH, page_size, _ = k_pages.shape
    assert Hq % KVH == 0
    G = Hq // KVH
    pages_per_seq = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, KVH, G, hd)

    grid = (B, KVH, pages_per_seq)
    kernel = functools.partial(
        _paged_kernel, page_size=page_size, pages_per_seq=pages_per_seq,
        sm_scale=sm_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, i, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, i, bt, sl: (bt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, i, bt, sl: (bt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, i, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, seq_lens, qg, k_pages, v_pages)
    return out.reshape(B, Hq, hd)
