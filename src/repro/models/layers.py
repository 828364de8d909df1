"""Shared neural-net layers (pure JAX, bf16 compute / fp32 params).

The attention here is the *reference* path used for smoke tests and the
dry-run lowering: query-chunked causal attention (flash-style memory
behaviour, plain-jnp numerics).  The Pallas kernels in ``repro.kernels``
implement the TPU-optimized equivalents and are validated against
``repro.kernels.*.ref`` oracles.
"""
from __future__ import annotations

import math
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# bf16 compute by default; tests can set REPRO_COMPUTE_DTYPE=float32 for
# tight numerical comparisons (prefill/decode consistency, kernel oracles).
COMPUTE_DTYPE = jnp.dtype(os.environ.get("REPRO_COMPUTE_DTYPE", "bfloat16"))

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, in_axis_size=None):
    """Truncated-normal fan-in init, fp32 params."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return std * jax.random.truncated_normal(rng, -2.0, 2.0, shape, jnp.float32)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim // 2, dtype=jnp.float32) * 2.0 / head_dim))


def apply_rope(x, positions, theta: float, mrope_sections: Tuple[int, ...] = (),
               inv_freq=None):
    """x: (B, S, H, hd).  positions: (B, S) int32 or (B, S, 3) for M-RoPE.

    M-RoPE (qwen2-vl): the head_dim/2 frequency slots are split into
    sections; each section takes its angle from a different position stream
    (temporal / height / width).  ``inv_freq`` (hd/2,) replaces the plain
    ``theta`` frequencies (YaRN).
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta) if inv_freq is None else inv_freq  # (hd/2,)
    if mrope_sections:
        assert positions.ndim == 3 and positions.shape[-1] == len(mrope_sections)
        # section id per frequency slot
        sec = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.array(mrope_sections),
            total_repeat_length=hd // 2,
        )  # (hd/2,)
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(sec[None, None, :], positions.shape[:2] + (hd // 2,)),
            axis=-1,
        )  # (B, S, hd/2)
        ang = pos * inv[None, None, :]
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv[None, None, :]  # (B,S,hd/2)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA sharding helper: KV-head replication (DESIGN.md §5)
# ---------------------------------------------------------------------------


def kv_replication_factor(num_heads: int, num_kv_heads: int, axis_size: int) -> int:
    """Pick r | (H/KVH) maximizing TP utilization of KVH*r heads on axis_size
    shards; ties -> smaller r (less KV memory)."""
    group = num_heads // num_kv_heads
    best_r, best_util = 1, -1.0
    for r in range(1, group + 1):
        if group % r:
            continue
        kvh = num_kv_heads * r
        util = kvh / (math.ceil(kvh / axis_size) * axis_size)
        if util > best_util + 1e-9:
            best_r, best_util = r, util
        if util >= 1.0:
            break  # smallest perfectly-divisible r
    return best_r


# ---------------------------------------------------------------------------
# attention (reference, query-chunked)
# ---------------------------------------------------------------------------


def _causal_chunk_attn(q_chunk, k, v, q_start, kv_len, window: int, shd=None,
                      scale=None):
    """q_chunk: (B, C, H, G, hd) grouped query; k/v: (B, S, H, hd) (v may
    be narrower than q and k).

    Masked softmax over keys [0, S) with causal (+ optional sliding window)
    mask relative to absolute query positions q_start..q_start+C; scores
    are scaled by ``scale`` (default hd^-0.5).
    """
    B, C, H, G, hd = q_chunk.shape
    S = k.shape[1]
    if shd is not None:
        q_chunk = shd.q_rep(q_chunk) if hasattr(shd, "q_rep") else q_chunk
    # bf16 operands with fp32 accumulate (native MXU path; avoids
    # materializing an fp32 copy of K)
    scores = jnp.einsum(
        "bchgd,bshd->bhgcs", q_chunk, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    qpos = q_start + jnp.arange(C)[:, None]  # (C, 1)
    kpos = jnp.arange(S)[None, :]  # (1, S)
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    if shd is not None:
        scores = shd.scores(scores)
    probs = jax.nn.softmax(scores, axis=-1)
    if shd is not None:
        probs = shd.scores(probs)
    out = jnp.einsum("bhgcs,bshd->bchgd", probs.astype(v.dtype), v)
    return out


def causal_attention(q, k, v, *, chunk: int, window: int = 0, shd=None,
                     scale=None):
    """Reference causal attention with GQA, scanned over query chunks.

    q: (B, S, Hq, hd); k, v: (B, S, KVH, hd) (v's last dim may differ).
    Returns (B, S, Hq, v's last dim); ``scale`` as in ``_causal_chunk_attn``.
    Non-divisible S is zero-padded on the query side (outputs sliced off).

    When KVH does not divide the model axis, K/V are expanded to MHA so the
    score tensors shard cleanly on the head dim (otherwise GSPMD falls back
    to replicating multi-GB prob tensors in the backward pass).
    """
    B, S, Hq, hd = q.shape
    KVH = k.shape[2]
    if shd is not None:
        from repro.models.sharding import MODEL_AXIS

        msize = shd.mesh.shape[MODEL_AXIS]
        if getattr(shd, "seq_shard", False):
            k = shd.kv_seq(k)
            v = shd.kv_seq(v)
        elif KVH % msize != 0 and Hq % msize == 0 and Hq != KVH:
            rep = Hq // KVH
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            KVH = Hq
    G = Hq // KVH
    chunk = min(chunk, S)
    Sp = ((S + chunk - 1) // chunk) * chunk
    qg = q.reshape(B, S, KVH, G, hd)
    if Sp != S:
        qg = jnp.pad(qg, ((0, 0), (0, Sp - S), (0, 0), (0, 0), (0, 0)))
    n = Sp // chunk

    def body(_, qc_i):
        qc, i = qc_i
        out = _causal_chunk_attn(qc, k, v, i * chunk, None, window, shd=shd,
                                 scale=scale)
        return (), out

    qs = qg.reshape(B, n, chunk, KVH, G, hd).transpose(1, 0, 2, 3, 4, 5)
    _, outs = jax.lax.scan(body, (), (qs, jnp.arange(n)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, Hq, v.shape[-1])
    return out[:, :S]


def decode_attention(q, k_cache, v_cache, kv_len, *, window: int = 0,
                     kv_chunk: int = 0):
    """One-token attention over a (possibly quantized) KV cache.

    q: (B, 1, Hq, hd); caches: (B, S, KVH, hd); kv_len: (B,) valid lengths.
    ``kv_chunk`` > 0 scans KV blocks with an online softmax (flash-style):
    score tensors never materialize beyond one block (§Perf iteration).
    """
    B, _, Hq, hd = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = Hq // KVH
    qg = q.reshape(B, KVH, G, hd)
    if kv_chunk and S > kv_chunk and S % kv_chunk == 0:
        n = S // kv_chunk

        def body(carry, xs):
            m, l, acc = carry
            kb, vb, i = xs  # (n stacked) blocks: (B, C, KVH, hd)
            s = jnp.einsum("bhgd,bshd->bhgs", qg, kb,
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            kpos = i * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = kpos < kv_len[:, None]
            if window:
                mask &= kpos >= jnp.maximum(kv_len[:, None] - window, 0)
            s = jnp.where(mask[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgs,bshd->bhgd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l, acc), ()

        kb = k_cache.reshape(B, n, kv_chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
        vb = v_cache.reshape(B, n, kv_chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
        init = (jnp.full((B, KVH, G), -1e30, jnp.float32),
                jnp.zeros((B, KVH, G), jnp.float32),
                jnp.zeros((B, KVH, G, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, jnp.arange(n)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, 1, Hq, hd).astype(q.dtype)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    kpos = jnp.arange(S)[None, :]  # (1, S)
    mask = kpos < kv_len[:, None]
    if window:
        mask &= kpos >= jnp.maximum(kv_len[:, None] - window, 0)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, Hq, hd)


# ---------------------------------------------------------------------------
# KV cache (dense ring buffer; int8 quantization option)
# ---------------------------------------------------------------------------


def quantize_kv(x):
    """Per-(token, head) symmetric int8.  x: (..., hd)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale


def init_kv_cache(cfg: ModelConfig, num_layers: int, batch: int, max_len: int, kv_heads: int):
    hd = cfg.resolved_head_dim
    if cfg.sliding_window:
        max_len = min(max_len, cfg.sliding_window)
    shape = (num_layers, batch, max_len, kv_heads, hd)
    if cfg.kv_cache_dtype == "int8":
        z = jnp.zeros(shape, jnp.int8)
        s = jnp.zeros(shape[:-1] + (1,), jnp.float32)
        return {"k": z, "v": z, "k_scale": s, "v_scale": s}
    z = jnp.zeros(shape, COMPUTE_DTYPE)
    return {"k": z, "v": z}


def cache_insert(cache_layer: dict, k_new, v_new, positions, cfg: ModelConfig):
    """Insert new K/V at per-sequence positions (ring-buffer for SWA).

    cache_layer entries: (B, S, KVH, hd) [+ scales]; k_new: (B, T, KVH, hd);
    positions: (B,) absolute write position of the first new token.
    """
    S = cache_layer["k"].shape[1]
    B, T = k_new.shape[:2]
    if cfg.sliding_window:
        slots = (positions[:, None] + jnp.arange(T)[None]) % S  # ring buffer
    else:
        slots = positions[:, None] + jnp.arange(T)[None]

    def upd(buf, val):
        def one(b, v, s):
            return b.at[s].set(v)

        return jax.vmap(one)(buf, val.astype(buf.dtype), slots)

    out = dict(cache_layer)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        out["k"] = upd(cache_layer["k"], kq)
        out["v"] = upd(cache_layer["v"], vq)
        out["k_scale"] = upd(cache_layer["k_scale"], ks)
        out["v_scale"] = upd(cache_layer["v_scale"], vs)
    else:
        out["k"] = upd(cache_layer["k"], k_new)
        out["v"] = upd(cache_layer["v"], v_new)
    return out


def finalize_prefill_cache(k, v, cfg: ModelConfig, max_len=None, seq_axis: int = 1):
    """Turn full-sequence prefill K/V into a decode cache.

    - sliding window: keep the last W tokens at ring slots pos % cache_len;
    - otherwise pad the seq axis up to ``max_len`` (decode growth budget).
    Returns a cache dict (quantized if configured).
    """
    import numpy as np

    S = k.shape[seq_axis]
    cache_len = max_len or S
    if cfg.sliding_window:
        cache_len = min(cache_len, max(cfg.sliding_window, 1))
        cache_len = max(cache_len, min(S, cfg.sliding_window))
    if cfg.sliding_window and S > cache_len:
        # last cache_len tokens land at slots pos % cache_len (static perm)
        slots = np.arange(S - cache_len, S) % cache_len
        inv = np.argsort(slots)
        idx = (slice(None),) * seq_axis
        k = k[idx + (slice(S - cache_len, S),)][idx + (inv,)]
        v = v[idx + (slice(S - cache_len, S),)][idx + (inv,)]
    elif cache_len > S:
        pad = [(0, 0)] * k.ndim
        pad[seq_axis] = (0, cache_len - S)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": k.astype(COMPUTE_DTYPE), "v": v.astype(COMPUTE_DTYPE)}


def cache_kv_arrays(cache_layer: dict, cfg: ModelConfig):
    if cfg.kv_cache_dtype == "int8":
        k = dequantize_kv(cache_layer["k"], cache_layer["k_scale"]).astype(COMPUTE_DTYPE)
        v = dequantize_kv(cache_layer["v"], cache_layer["v_scale"]).astype(COMPUTE_DTYPE)
        return k, v
    return cache_layer["k"], cache_layer["v"]


# --- in-place decode-cache access (cache carried through the layer scan;
# writes are one-token scatters, never whole-layer rewrites) ---


def cache_insert_layer(cache: dict, layer_idx, k_new, v_new, positions,
                       cfg: ModelConfig):
    """Scatter one new token into stacked cache at (layer_idx, b, slot).

    cache entries: (L, B, S, KVH, hd) [+ scales]; k_new/v_new: (B, 1, KVH, hd);
    positions: (B,) absolute position of the new token.
    """
    S = cache["k"].shape[2]
    B = k_new.shape[0]
    slots = positions % S if cfg.sliding_window else positions
    bidx = jnp.arange(B)

    def upd(buf, val):  # val (B, 1, ...)
        return buf.at[layer_idx, bidx, slots].set(val[:, 0].astype(buf.dtype))

    out = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ks)
        out["v_scale"] = upd(cache["v_scale"], vs)
    else:
        out["k"] = upd(cache["k"], k_new)
        out["v"] = upd(cache["v"], v_new)
    return out


def cache_layer_arrays(cache: dict, layer_idx, cfg: ModelConfig):
    """Read layer ``layer_idx``'s K/V (dequantized view) from stacked cache."""
    k = jax.lax.dynamic_index_in_dim(cache["k"], layer_idx, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache["v"], layer_idx, 0, keepdims=False)
    if cfg.kv_cache_dtype == "int8":
        ks = jax.lax.dynamic_index_in_dim(cache["k_scale"], layer_idx, 0, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(cache["v_scale"], layer_idx, 0, keepdims=False)
        return (dequantize_kv(k, ks).astype(COMPUTE_DTYPE),
                dequantize_kv(v, vs).astype(COMPUTE_DTYPE))
    return k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w_gate.astype(x.dtype)))
    h = h * jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, w_down.astype(x.dtype))


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, w_in.astype(x.dtype)) + b_in.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, w_out.astype(x.dtype)) + b_out.astype(x.dtype)
