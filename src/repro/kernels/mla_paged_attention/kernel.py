"""Paged decode attention over a multi-head latent cache (TPU Pallas).

Multi-head latent attention (DeepSeek-V2) caches one row a token a layer,
``[c_kv; k_pe]`` (kv_lora_rank + rope dims), shared by every head.  In
the absorbed decode the query of head h is ``[q_nope_h W_UK_h; q_pe_h]``,
as wide as the row, so every head scores against the same row, and the
value is the row's first ``value_dim`` columns (c_kv); the caller maps
the (H, value_dim) result through W_UV.

Pages are token-minor, ``(num_pages, 1, L, page_size)``: a page's tokens
lie along the lanes.  (A row-major page of L = 576 does not fill whole
128-lane tiles, so the TPU compiler lays such a slab out transposed and
copies it whole for the kernel at every call.)  So the scores are
``q (H, L) @ page (L, page_size)`` and the output ``p @ page[:value_dim]^T``.
Grid: (B, pages_per_seq), the page dim innermost /
"arbitrary" so the online-softmax scratch persists across a sequence's
pages.  One (L, page_size) page is read once per (lane, page) step and
all heads are computed against it.  Steps past ``seq_len`` compute
nothing, and their index map repeats the last live page, so the pipeline
does not fetch the padded tail of the table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_kernel(block_tables, seq_lens,  # scalar-prefetch
                q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref,
                *, page_size: int, pages_per_seq: int, sm_scale: float,
                value_dim: int):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens[b]
    page_start = pi * page_size

    @pl.when(page_start < seq_len)
    def _compute():
        q = q_ref[0]         # (H, L)
        kv = kv_ref[0, 0]    # (L, page_size)
        s = jax.lax.dot_general(q, kv, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale     # (H, page_size)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:value_dim], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(pi == pages_per_seq - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def mla_paged_attention(q, pages, block_tables, seq_lens, *, value_dim: int,
                        sm_scale: float, interpret: bool = False):
    """q: (B, H, L); pages: (num_pages, 1, L, page_size); block_tables:
    (B, pages_per_seq) int32; seq_lens: (B,) int32, each at least 1.

    Returns (B, H, value_dim): softmax(q pages^T * sm_scale) over each
    sequence's first ``seq_len`` rows, times their first ``value_dim``
    columns."""
    B, H, L = q.shape
    page_size = pages.shape[3]
    pages_per_seq = block_tables.shape[1]
    kernel = functools.partial(
        _mla_kernel, page_size=page_size, pages_per_seq=pages_per_seq,
        sm_scale=sm_scale, value_dim=value_dim)

    def page_of(b, i, bt, sl):
        return (bt[b, jnp.minimum(i, (sl[b] - 1) // page_size)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, H, L), lambda b, i, bt, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, L, page_size), page_of),
        ],
        out_specs=pl.BlockSpec((1, H, value_dim),
                               lambda b, i, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, value_dim), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_paged_attention",
    )(block_tables, seq_lens, q, pages)
