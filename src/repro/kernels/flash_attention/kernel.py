"""Flash attention forward (TPU Pallas, causal, GQA-aware).

Grid: (B, Hq, S_q/bq, S_k/bk); the kv dimension is innermost ("arbitrary"
semantics) so VMEM scratch accumulators persist across kv steps — the
canonical Mosaic online-softmax pattern.  Blocks are MXU-aligned
(head_dim on the lane dim; bq/bk multiples of 128 by default).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, bq: int, bk: int, sm_scale: float, causal: bool,
                  kv_steps: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale  # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked kv blocks (all keys strictly after the last query)
        pl.when(k_start <= q_start + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)  # (bq, 1)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, bq: int = 128, bk: int = 128,
                        causal: bool = True, sm_scale: float | None = None,
                        interpret: bool = False):
    """q: (B, Hq, S, hd); k, v: (B, KVH, S, hd).  Returns (B, Hq, S, hd)."""
    B, Hq, S, hd = q.shape
    KVH = k.shape[1]
    assert Hq % KVH == 0
    G = Hq // KVH
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    kv_steps = S // bk
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)

    grid = (B, Hq, S // bq, kv_steps)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, sm_scale=sm_scale, causal=causal,
        kv_steps=kv_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
