"""The two layer halves of DeepSeek-V2 (arXiv:2405.04434) as the serving
path runs them: multi-head latent attention (MLA) and the DeepSeekMoE
feed-forward.  Names and equations follow the public
``modeling_deepseek.py``.

MLA: ``q = h q_proj`` (no q LoRA); ``[c; k_pe] = h kv_a_proj_with_mqa``;
the cache holds ``[rmsnorm(c); rope(k_pe)]`` (kv_lora_rank + rope dims a
token), shared by every head.  Prefill decompresses ``[k_nope; v] = c
kv_b_proj`` per head and runs causal attention over ``[q_nope; q_pe]``
and ``[k_nope; k_pe]``.  Decode absorbs ``kv_b_proj`` into the query and
the output: ``q_lat = q_nope W_UK``, scores ``q_lat c + q_pe k_pe``,
``out = (p c) W_UV``, so only the latent is read.  RoPE takes the
published layout: the rope dims are de-interleaved, then rotated by
halves, at YaRN frequencies; the softmax scale carries YaRN's mscale.

DeepSeekMoE: a float32 softmax router, greedy top-k, weights not
renormalized, times ``routed_scaling_factor``; the routed experts run
dropless as a grouped matmul over the (token, expert) pairs sorted by
expert; the shared experts are one SwiGLU."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models import layers as L


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> jnp.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s frequencies for ``dim`` rope
    dims: the interpolated ones (divided by ``factor``) below the ramp
    from ``beta_fast`` to ``beta_slow`` rotations over the original
    context, the plain ones above it, blended linearly on it."""
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]
    base = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def rotations(n):
        return (dim * math.log(orig / (n * 2 * math.pi))) / (
            2 * math.log(theta))

    low = max(math.floor(rotations(scaling["beta_fast"])), 0)
    high = min(math.ceil(rotations(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (1.0 / (factor * base)) * (1 - keep) + (1.0 / base) * keep


def softmax_scale(cfg: ModelConfig) -> float:
    """q_head_dim^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = dict(cfg.rope_scaling)
    if sc.get("mscale_all_dim"):
        s *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return s


def rope(x, positions, cfg: ModelConfig):
    """x: (B, S, H, rope dims); the published layout: de-interleave the
    pairs, then rotate by halves at YaRN frequencies, scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    sc = dict(cfg.rope_scaling)
    out = L.apply_rope(x, positions, cfg.rope_theta,
                       inv_freq=yarn_inv_freq(d, cfg.rope_theta, sc))
    m = (yarn_mscale(sc["factor"], sc.get("mscale", 1.0))
         / yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0.0)))
    return (out * m).astype(out.dtype)


# ---------------------------------------------------------------------------
# multi-head latent attention
# ---------------------------------------------------------------------------


def _project(x, p, cfg: ModelConfig, positions, adapters):
    """(q_nope (B,S,H,nope), roped q_pe (B,S,H,rope), latent (B,S,R+rope)).
    LoRA adds to q and to the latent projection's output."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["q_proj"].astype(h.dtype))
    kv = jnp.einsum("bsd,dk->bsk", h, p["kv_a_proj_with_mqa"].astype(h.dtype))
    for a in adapters:
        if a.kind != "lora":
            raise NotImplementedError(
                f"{a.kind} adapters on multi-head latent attention")
        ap = a.params
        s = ap["scaling"].astype(h.dtype)
        dq = jnp.einsum("bsd,dr,re->bse", h, ap["a_q"].astype(h.dtype),
                        ap["b_q"].astype(h.dtype)) * s
        q = q + dq.reshape(q.shape).astype(h.dtype)
        kv = kv + (jnp.einsum("bsd,dr,re->bse", h, ap["a_kv"].astype(h.dtype),
                              ap["b_kv"].astype(h.dtype)) * s).astype(h.dtype)
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    c = L.rms_norm(kv[..., :R], p["kv_a_layernorm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, R:], positions, cfg)[..., 0, :]
    q_pe = rope(q[..., nope:], positions, cfg)
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def mla_prefill(x, p, cfg: ModelConfig, positions, adapters=()):
    """Non-absorbed attention over a whole prompt.  x: (B, S, D).
    Returns (x + attention, latent (B, S, 1, R + rope)) — the latent as
    one head of the pool's slab."""
    q_nope, q_pe, lat = _project(x, p, cfg, positions, adapters)
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("bsr,rhe->bshe", lat[..., :R],
                    p["kv_b_proj"].astype(lat.dtype))
    k_pe = jnp.broadcast_to(lat[:, :, None, R:],
                            kv.shape[:3] + (cfg.qk_rope_head_dim,))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    o = L.causal_attention(q, k, kv[..., nope:], chunk=cfg.attn_chunk,
                           scale=softmax_scale(cfg))
    o = jnp.einsum("bshv,hvd->bsd", o, p["o_proj"].astype(o.dtype))
    return x + o, lat[:, :, None]


def mla_decode_paged(x, p, cfg: ModelConfig, pages, block_tables, kv_len, *,
                     adapters=(), attn_impl: str = "auto"):
    """One token a lane, absorbed, over the latent pool.  x: (B, 1, D);
    pages: (P, 1, R + rope, page_size); kv_len: (B,) tokens cached.
    Writes the token's latent row, attends, and returns (out, pages)."""
    from repro.kernels.mla_paged_attention.ops import mla_paged_decode_step

    q_nope, q_pe, lat = _project(x, p, cfg, kv_len[:, None], adapters)
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w = p["kv_b_proj"].astype(x.dtype)  # (R, H, nope + v)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w[..., :nope])
    q = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)
    o, pages = mla_paged_decode_step(q, lat[:, 0], pages, block_tables,
                                     kv_len,
                                     value_dim=R,
                                     sm_scale=softmax_scale(cfg),
                                     impl=attn_impl)
    o = jnp.einsum("bhr,rhv->bhv", o.astype(x.dtype), w[..., nope:])
    o = jnp.einsum("bhv,hvd->bd", o, p["o_proj"].astype(x.dtype))
    return x + o[:, None], pages


# ---------------------------------------------------------------------------
# DeepSeekMoE
# ---------------------------------------------------------------------------


def route(x, router, k: int, scaling: float):
    """Float32 softmax over the experts, greedy top-k, not renormalized.
    x: (T, D).  Returns (weights (T, k) float32, experts (T, k))."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return w * scaling, idx


def moe_ffn(h, p, cfg: ModelConfig, *, impl: str = "auto", stats=None):
    """Routed plus shared experts of normalized hidden states h (B, S, D).
    With ``stats`` (a list) appends (token-expert pairs, experts given at
    least one token) of this call."""
    B, S, D = h.shape
    k = cfg.num_experts_per_tok
    x = h.reshape(B * S, D)
    E = p["router"].shape[1]
    w, idx = route(x, p["router"], k, cfg.routed_scaling_factor)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat)
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    xs = x[order // k]

    def gmm(a, name):
        return grouped_matmul(a, p[name].astype(a.dtype), sizes, impl=impl)

    y = gmm(jax.nn.silu(gmm(xs, "e_gate")) * gmm(xs, "e_up"), "e_down")
    y = y[jnp.argsort(order)].reshape(B * S, k, D)
    routed = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)
    shared = L.swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    if stats is not None:
        stats.append((flat.shape[0], jnp.sum(sizes > 0)))
    return routed.reshape(B, S, D).astype(h.dtype) + shared
