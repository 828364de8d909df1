"""Block abstraction (paper §4.2).

A Block is the unit of provisioning: a named pytree of params plus a pure
apply function determined by ``kind``.  Partitioning respects architectural
boundaries — the finest-grained components are {embedding, attention, ffn,
lm_head}; the default (avoid over-partitioning) is one Block per transformer
layer, split into attention/ffn only when an adapter forces it (Fig. 11).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field, replace
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import deepseek_v2 as DS
from repro.models import layers as L
from repro.models.transformer import _mlp_layer


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_hash(tree) -> str:
    h = hashlib.sha1()
    for path, leaf in sorted(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()[:16]


ATTENTION_KINDS = ("layer", "attention")  # block kinds that own KV state


def is_mla(params) -> bool:
    """Multi-head latent attention (DeepSeek-V2) params."""
    return "kv_b_proj" in params


def is_moe(params) -> bool:
    """DeepSeekMoE feed-forward params (router, routed and shared experts)."""
    return "router" in params


@dataclass
class Block:
    id: str
    kind: str  # embed | layer | attention | ffn | lm_head | lora | adapter | bitfit | stitch
    model: str  # model that first contributed it
    layer_idx: Optional[int]
    d_in: int
    d_out: int
    params: dict
    cfg: Optional[ModelConfig] = None
    meta: dict = field(default_factory=dict)

    @property
    def n_params(self) -> int:
        return sum(x.size for x in jax.tree.leaves(self.params))

    @property
    def has_kv(self) -> bool:
        """True for blocks that carry attention KV state when serving."""
        return self.kind in ATTENTION_KINDS

    @property
    def kv_signature(self) -> Tuple:
        """(kv_heads, head_dim) — the KV-pool signature this block's slots
        live under (one shared pool per signature, DESIGN.md §2); an MLA
        block's is ("latent", row width): one row a token, no V."""
        cfg = self.cfg
        if is_mla(self.params):
            return ("latent", cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        return (cfg.num_kv_heads or cfg.num_heads, cfg.resolved_head_dim)

    @property
    def bytes(self) -> int:
        return tree_bytes(self.params)

    def flops_per_token(self) -> float:
        """2 * the params a token passes through: all of them, but of an
        expert layer's routed experts only the ``num_experts_per_tok`` it
        is sent to."""
        n = self.n_params
        if is_moe(self.params):
            routed = sum(self.params[w].size
                         for w in ("e_gate", "e_up", "e_down"))
            E = self.params["e_gate"].shape[0]
            n -= routed * (E - self.cfg.num_experts_per_tok) // E
        return 2.0 * n


# ---------------------------------------------------------------------------
# apply fns (full-sequence; serving engine drives these per block instance)
# ---------------------------------------------------------------------------


def _attn_sublayer(x, p, cfg, positions, adapters=()):
    if is_mla(p):
        return DS.mla_prefill(x, p, cfg, positions, adapters)[0]
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    wq, wk, wv = p["wq"], p["wk"], p["wv"]
    q = jnp.einsum("bsd,dhk->bshk", h, wq.astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, wk.astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, wv.astype(h.dtype))
    for a in adapters:
        if a.kind == "lora":
            ap = a.params
            s = ap["scaling"].astype(h.dtype)
            dq = jnp.einsum("bsd,dr,re->bse", h, ap["a_q"].astype(h.dtype),
                            ap["b_q"].astype(h.dtype)) * s
            dv = jnp.einsum("bsd,dr,re->bse", h, ap["a_v"].astype(h.dtype),
                            ap["b_v"].astype(h.dtype)) * s
            q = q + dq.reshape(q.shape).astype(h.dtype)
            v = v + dv.reshape(v.shape).astype(h.dtype)
        elif a.kind == "bitfit":
            q = q + a.params["bq"].astype(h.dtype)
            k = k + a.params["bk"].astype(h.dtype)
            v = v + a.params["bv"].astype(h.dtype)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    o = L.causal_attention(q, k, v, chunk=cfg.attn_chunk,
                           window=cfg.sliding_window)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(h.dtype))
    return x + o


def _ffn_sublayer(x, p, cfg, adapters=(), impl="auto", stats=None):
    if is_moe(p):
        out = x + DS.moe_ffn(L.rms_norm(x, p["ln2"], cfg.norm_eps), p, cfg,
                             impl=impl, stats=stats)
    else:
        out = _mlp_layer(x, p, cfg, None)
    for a in adapters:
        if a.kind == "adapter":
            ap = a.params
            h = jax.nn.gelu(jnp.einsum("bsd,de->bse", out,
                                       ap["down"].astype(out.dtype)))
            out = out + jnp.einsum("bse,ed->bsd", h, ap["up"].astype(out.dtype))
    return out


def apply_block(block: Block, x, *, positions=None, adapters=(),
                impl: str = "auto", stats=None):
    """x: hidden states (B, S, D) — or token ids for embed blocks.
    ``impl`` picks the expert FFN's grouped matmul (as ``attn_impl`` picks
    the paged kernel); ``stats`` collects its routing counts."""
    cfg = block.cfg
    p = block.params
    if block.kind == "embed":
        return jnp.take(p["embed"], x, axis=0).astype(L.COMPUTE_DTYPE)
    if block.kind == "lm_head":
        h = L.rms_norm(x, p["final_ln"], cfg.norm_eps)
        return jnp.einsum("bsd,dv->bsv", h, p["lm_head"].astype(h.dtype))
    if block.kind == "layer":
        B, S = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x0 = x
        x = _attn_sublayer(x, p, cfg, positions, adapters)
        out = _ffn_sublayer(x, p, cfg, adapters, impl, stats)
        if "recover_a" in p:  # surrogate LoRA recovery (paper §5.2)
            out = out + jnp.einsum(
                "bsd,dr,re->bse", x0, p["recover_a"].astype(x0.dtype),
                p["recover_b"].astype(x0.dtype))
        return out
    if block.kind == "attention":
        B, S = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return _attn_sublayer(x, p, cfg, positions, adapters)
    if block.kind == "ffn":
        return _ffn_sublayer(x, p, cfg, adapters, impl, stats)
    if block.kind == "stitch":
        B, S, D = x.shape
        posval = jnp.full((B, S, 1), float(block.meta["position_value"]),
                          x.dtype)
        xin = jnp.concatenate([x, posval], axis=-1)
        return jnp.einsum("bse,ed->bsd", xin, p["w"].astype(x.dtype))
    raise ValueError(f"apply_block: {block.kind}")


# ---------------------------------------------------------------------------
# stateful block execution (real serving engine: per-block KV caches)
# ---------------------------------------------------------------------------


def block_prefill_raw(block: Block, x, *, positions=None, adapters=(),
                      impl: str = "auto"):
    """Prefill one block, returning the raw rotated K and V alongside the
    output (``(out, k_r, v)``; ``k_r``/``v`` are ``None`` for blocks without
    attention state; an MLA block returns its latent rows as ``k_r`` and no
    ``v``).  The paged serving engine scatters the raw K/V into its
    shared page pool; ``block_prefill`` wraps this with the dense ring-buffer
    cache layout instead."""
    cfg = block.cfg
    p = block.params
    if block.kind not in ("layer", "attention"):
        out = apply_block(block, x, positions=positions, adapters=adapters,
                          impl=impl)
        return out, None, None
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if is_mla(p):
        out, lat = DS.mla_prefill(x, p, cfg, positions, adapters)
        if block.kind == "layer":
            out = _ffn_sublayer(out, p, cfg, adapters, impl)
        return out, lat, None
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
    q, k, v = _peft_qkv(h, q, k, v, adapters)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k_r = L.apply_rope(k, positions, cfg.rope_theta)
    o = L.causal_attention(q, k_r, v, chunk=cfg.attn_chunk,
                           window=cfg.sliding_window)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(h.dtype))
    out = x + o
    if block.kind == "layer":
        out = _ffn_sublayer(out, p, cfg, adapters, impl)
    return out, k_r, v


def block_prefill(block: Block, x, *, positions=None, adapters=(),
                  max_len=None):
    """Like apply_block, but attention-bearing blocks also return their KV
    cache (dict) for subsequent block_decode calls."""
    out, k_r, v = block_prefill_raw(block, x, positions=positions,
                                    adapters=adapters)
    if k_r is None:
        return out, None
    return out, L.finalize_prefill_cache(k_r, v, block.cfg, max_len)


def block_decode(block: Block, x, cache, kv_len, *, adapters=()):
    """One-token step.  x: (B, 1, D); cache from block_prefill; kv_len (B,).

    Returns (out, new_cache)."""
    cfg = block.cfg
    p = block.params
    if block.kind not in ("layer", "attention"):
        return apply_block(block, x, adapters=adapters), cache
    B = x.shape[0]
    positions = kv_len[:, None]
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
    q, k, v = _peft_qkv(h, q, k, v, adapters)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    cache = L.cache_insert(cache, k, v, kv_len, cfg)
    kc, vc = L.cache_kv_arrays(cache, cfg)
    S = kc.shape[1]
    valid = jnp.minimum(kv_len + 1, S)
    o = L.decode_attention(q, kc, vc, valid, window=0)
    o = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"].astype(x.dtype))
    out = x + o
    if block.kind == "layer":
        out = _ffn_sublayer(out, p, cfg, adapters)
    return out, cache


def block_decode_paged(block: Block, x, k_pages, v_pages, block_tables,
                       kv_len, *, adapters=(), attn_impl: str = "auto",
                       stats=None):
    """One-token step over a shared paged KV pool (DESIGN.md §2).

    x: (B, 1, D) hidden states (or token ids for embed blocks);
    k_pages/v_pages: (P, KVH, page_size, hd) pool slabs (an MLA block's
    latent slab and ``None``); block_tables: (B, n) page ids per sequence;
    kv_len: (B,) tokens already cached.

    Writes the new token's K/V into the pool and attends over the pages via
    the paged-attention kernel (Pallas on TPU, jnp oracle elsewhere).
    Returns (out, k_pages, v_pages).
    """
    cfg = block.cfg
    p = block.params
    if block.kind not in ("layer", "attention"):
        return (apply_block(block, x, adapters=adapters, impl=attn_impl,
                            stats=stats), k_pages, v_pages)
    if is_mla(p):
        out, k_pages = DS.mla_decode_paged(x, p, cfg, k_pages, block_tables,
                                           kv_len, adapters=adapters,
                                           attn_impl=attn_impl)
        if block.kind == "layer":
            out = _ffn_sublayer(out, p, cfg, adapters, attn_impl, stats)
        return out, k_pages, v_pages
    from repro.kernels.paged_attention.ops import paged_decode_step

    positions = kv_len[:, None]
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
    q, k, v = _peft_qkv(h, q, k, v, adapters)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    o, k_pages, v_pages = paged_decode_step(
        q[:, 0], k[:, 0], v[:, 0], k_pages, v_pages, block_tables, kv_len,
        impl=attn_impl)
    o = jnp.einsum("bhk,hkd->bd", o.astype(x.dtype),
                   p["wo"].astype(x.dtype))[:, None]
    out = x + o
    if block.kind == "layer":
        out = _ffn_sublayer(out, p, cfg, adapters, attn_impl, stats)
    return out, k_pages, v_pages


def _peft_qkv(h, q, k, v, adapters):
    for a in adapters:
        if a.kind == "lora":
            ap = a.params
            s = ap["scaling"].astype(h.dtype)
            dq = jnp.einsum("bsd,dr,re->bse", h, ap["a_q"].astype(h.dtype),
                            ap["b_q"].astype(h.dtype)) * s
            dv = jnp.einsum("bsd,dr,re->bse", h, ap["a_v"].astype(h.dtype),
                            ap["b_v"].astype(h.dtype)) * s
            q = q + dq.reshape(q.shape).astype(h.dtype)
            v = v + dv.reshape(v.shape).astype(h.dtype)
        elif a.kind == "bitfit":
            q = q + a.params["bq"].astype(h.dtype)
            k = k + a.params["bk"].astype(h.dtype)
            v = v + a.params["bv"].astype(h.dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# chain-level fused execution (one computation for all hops of a chain)
# ---------------------------------------------------------------------------


def chain_signature(steps) -> Tuple:
    """Fusion key for a resolved chain: the ordered tuple of
    (block id, adapter ids) hops.  Requests with identical signatures run
    the same computation and can share one fused megastep."""
    return tuple((block.id, tuple(a.id for a in adapters))
                 for block, adapters in steps)


def chain_params(steps) -> Tuple:
    """The weights of a resolved chain as one pytree, in hop order.

    Jitted chain functions take these as an argument: weights a jitted
    function closes over are baked into its program as constants, which at
    published widths means gigabytes of literals, a device copy of every
    weight per executable, and a compile-cache key that hashes weights."""
    return tuple((block.params, tuple(a.params for a in adapters))
                 for block, adapters in steps)


def bind_params(steps, params):
    """``steps`` with every block's params replaced by the matching entry
    of ``params`` (laid out as ``chain_params`` builds it) — inside a jitted
    chain function, its traced weight arguments."""
    return [(replace(block, params=p),
             tuple(replace(a, params=q) for a, q in zip(adapters, qs)))
            for (block, adapters), (p, qs) in zip(steps, params)]


def _chain_step_fused(steps, pool_index, tokens, pools_k, pools_v, tables,
                      kv_len, attn_impl: str, stats=None):
    """One single-token walk of a whole chain over the paged pools — the
    shared body of ``chain_decode_fused`` and of every draft/verify
    sub-step inside ``chain_decode_spec_fused``.  The speculative verify
    pass reuses THIS exact computation (same ops, same barriers) so its
    token stream is bitwise identical to the plain fused path.

    pools_k/pools_v are lists and are threaded through; ``stats`` (a
    list) collects each expert layer's routing counts; returns
    (next_tokens, probs, pools_k, pools_v)."""
    x = tokens[:, None]  # (B, 1) ids; the embed hop maps them to hidden
    hop = 0
    for block, adapters in steps:
        if block.has_kv:
            pi = pool_index[hop]
            x, pools_k[pi], pools_v[pi] = block_decode_paged(
                block, x, pools_k[pi], pools_v[pi], tables[hop], kv_len,
                adapters=adapters, attn_impl=attn_impl, stats=stats)
            hop += 1
        else:
            x = apply_block(block, x, adapters=adapters, impl=attn_impl,
                            stats=stats)
        # pin hop boundaries — the hidden state AND the updated slabs:
        # without this XLA fuses across blocks (including a hop's K/V
        # write into the next hop's reads) and the low-precision rounding
        # diverges from the per-hop oracle, flipping near-tie argmaxes;
        # dispatch stays a single device call either way
        x, pools_k, pools_v = jax.lax.optimization_barrier(
            (x, pools_k, pools_v))
    logits = x[:, 0]  # (B, V)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return next_tokens, probs, pools_k, pools_v


def chain_decode_fused(steps, pool_index, tokens, pools_k, pools_v, tables,
                       kv_len, moe_acc=None, *, attn_impl: str = "auto"):
    """One full-chain decode megastep for a batch of sequences, designed to
    be jitted once per chain signature (DESIGN.md §2).

    Runs embedding -> every attention/MLP/adapter hop (paged-KV decode with
    in-computation single-token K/V write) -> lm_head -> greedy argmax +
    softmax, with no Python dispatch between hops.

    tokens: (B,) pending token ids; pools_k/pools_v: tuples of page slabs,
    one per KV-pool signature the chain touches; pool_index[i]: which slab
    the i-th attention hop uses; tables: tuple of (B, n) page tables, one
    per attention hop; kv_len: (B,) tokens already cached.

    Returns (next_tokens, probs, pools_k, pools_v, kv_len + 1), and for a
    chain with expert layers, given the (3,) int32 ``moe_acc``, that plus
    this step's (token-expert pairs, experts hit, expert-layer calls).
    """
    pools_k, pools_v = list(pools_k), list(pools_v)
    stats = None if moe_acc is None else []
    next_tokens, probs, pools_k, pools_v = _chain_step_fused(
        steps, pool_index, tokens, pools_k, pools_v, tables, kv_len,
        attn_impl, stats)
    out = (next_tokens, probs, tuple(pools_k), tuple(pools_v), kv_len + 1)
    if moe_acc is None:
        return out
    step = jnp.stack([jnp.asarray(sum(n for n, _ in stats), jnp.int32),
                      sum(h for _, h in stats).astype(jnp.int32),
                      jnp.asarray(len(stats), jnp.int32)])
    return out + (moe_acc + step,)


def chain_decode_spec_fused(steps, sur_steps, pool_index, tokens, pools_k,
                            pools_v, tables, kv_len, budget, *,
                            lookahead: int, attn_impl: str = "auto"):
    """Draft-verify speculative decode megastep (paper §5.2 ported to the
    real engine, DESIGN.md §2): one jitted call that commits up to
    ``lookahead`` tokens per sequence while staying bitwise identical to
    ``lookahead`` plain ``chain_decode_fused`` calls.

    Phase 1 (draft): the surrogate chain ``sur_steps`` — the same chain
    with its expensive FFN hops structurally pruned
    (``core.surrogates.build_surrogate(prune_kv=False)``, so every
    attention hop keeps the full chain's KV signature and page tables) —
    runs ``lookahead - 1`` sequential single-token steps, drafting tokens
    d_1..d_{k-1} after the pending token p.  Its K/V writes land in the
    shared pools at positions kv_len..kv_len+k-2 as scratch.

    Phase 2 (verify): the full chain replays [p, d_1, .., d_{k-1}] through
    the exact ``_chain_step_fused`` computation, overwriting the draft
    scratch with true K/V and producing the true next token n_j at every
    position.  The accept rule is verify-exact: d_j is accepted iff it
    equals n_{j-1}, so the committed stream is the full model's greedy
    stream, bit for bit.

    Rollback is positional: ``kv_len`` only advances past accepted
    positions, so K/V written beyond the accepted prefix is dead — later
    steps overwrite those slots and attention masks them out meanwhile.
    Callers must size KV slots with ``lookahead`` tokens of headroom
    because both phases write up to ``kv_len + lookahead - 1``.

    budget: (B,) max tokens each lane may commit this call (the engine
    passes remaining gen budget minus one, keeping the pending-token
    finish protocol intact); accepted drafts are clamped to ``budget - 1``.

    Returns (commit_tok (B, k) committed-token candidates [p, d_1, ..],
    commit_cnt (B,) how many of them committed (>= 1), accepted (B,)
    drafts accepted, attempts (B,) drafts that could have committed,
    next_tokens (B,) new pending token, probs (B, V) its distribution,
    pools_k, pools_v, kv_len + commit_cnt).
    """
    k = lookahead
    if k < 2:
        raise ValueError("speculative decode needs lookahead >= 2")
    B = tokens.shape[0]
    pools_k, pools_v = list(pools_k), list(pools_v)
    # phase 1: sequential surrogate drafts (cheap pruned-FFN chain steps)
    cur = tokens
    drafts = []
    for j in range(k - 1):
        cur, _, pools_k, pools_v = _chain_step_fused(
            sur_steps, pool_index, cur, pools_k, pools_v, tables,
            kv_len + j, attn_impl)
        drafts.append(cur)
    # pin the phase boundary: draft numerics must not fuse into the verify
    # pass (verify must stay bitwise identical to the plain fused path)
    pools_k, pools_v, drafts = jax.lax.optimization_barrier(
        (pools_k, pools_v, drafts))
    # phase 2: exact sequential verify of [p, d_1, .., d_{k-1}]
    inputs = [tokens] + drafts
    outs, probs_steps = [], []
    for j in range(k):
        nxt, probs, pools_k, pools_v = _chain_step_fused(
            steps, pool_index, inputs[j], pools_k, pools_v, tables,
            kv_len + j, attn_impl)
        outs.append(nxt)
        probs_steps.append(probs)
    commit_tok = jnp.stack(inputs, axis=1)    # (B, k)
    outs_m = jnp.stack(outs, axis=1)          # (B, k): n_0..n_{k-1}
    probs_m = jnp.stack(probs_steps, axis=1)  # (B, k, V)
    # accept: longest drafted prefix matching the true argmaxes, clamped so
    # a lane never commits past its remaining generation budget
    match = (commit_tok[:, 1:] == outs_m[:, :-1]).astype(jnp.int32)
    accepted = jnp.cumprod(match, axis=1).sum(axis=1)          # (B,)
    attempts = jnp.minimum(k - 1, jnp.maximum(budget - 1, 0))  # (B,)
    accepted = jnp.minimum(accepted, attempts)
    commit_cnt = accepted + 1
    lane = jnp.arange(B)
    next_tokens = outs_m[lane, accepted]
    probs_out = probs_m[lane, accepted]
    return (commit_tok, commit_cnt, accepted, attempts, next_tokens,
            probs_out, tuple(pools_k), tuple(pools_v), kv_len + commit_cnt)


def chain_prefill_fused(steps, tokens, lens, impl: str = "auto"):
    """Batched multi-request prefill through a whole chain (one jitted call
    per (chain signature, length bucket) instead of one per request).

    tokens: (B, S) ids right-padded to the bucket length; lens: (B,) true
    prompt lengths.  Causality makes the padded tail inert for every valid
    position, so per-row results match the unpadded single-request path.

    Returns (next_tokens, probs, kvs) where kvs[i] = (k_r, v) raw rotated
    K/V (B, S, KVH, hd) for the i-th attention hop, or (latent,) for an
    MLA hop.
    """
    x = tokens
    kvs = []
    for block, adapters in steps:
        x, k_r, v = block_prefill_raw(block, x, adapters=adapters, impl=impl)
        if k_r is not None:
            kvs.append(tuple(t for t in (k_r, v) if t is not None))
        # pin hop boundaries, exactly as in chain_decode_fused: the KV this
        # writes seeds every later decode step, and a 1-ulp rounding
        # difference from cross-block fusion flips near-tie argmaxes
        # downstream
        x, kvs = jax.lax.optimization_barrier((x, kvs))
    B = x.shape[0]
    logits = x[jnp.arange(B), lens - 1]  # last valid position per row
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return next_tokens, probs, kvs


@dataclass
class ChainStep:
    block_id: str
    adapter_ids: Tuple[str, ...] = ()


@dataclass
class BlockChain:
    model: str
    steps: List[ChainStep]

    def block_ids(self):
        return [s.block_id for s in self.steps]


def run_chain(zoo, chain: BlockChain, tokens, *, block_override=None):
    """Execute a chain end-to-end (offline/eval path; the online engine in
    repro.serving drives blocks individually with KV state)."""
    x = tokens
    for step in chain.steps:
        bid = (block_override or {}).get(step.block_id, step.block_id)
        block = zoo.blocks[bid]
        adapters = tuple(zoo.blocks[a] for a in step.adapter_ids)
        x = apply_block(block, x, adapters=adapters)
    return x
