"""The DeepSeek-V2 layer (arXiv:2405.04434; the public
``modeling_deepseek.py``): RMSNorm pre-norm, multi-head latent attention
(MLA) with YaRN RoPE, and DeepSeekMoE (a softmax router over the routed
experts, greedy top-k, weights not renormalized, plus shared experts)
after ``first_k_dense_replace`` dense SwiGLU layers; untied head.  Its
tenants are LoRA apps on ``q_proj`` and ``kv_a_proj_with_mqa``.

Weights: all tenants' weights come from one jitted call on the device,
from the seed, in the dtype they are served in, under the public
checkpoint's names for attention (``q_proj``, ``kv_a_proj_with_mqa``,
``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``); the experts are
``router`` (d, E), ``e_gate``/``e_up`` (E, d, f), ``e_down`` (E, f, d)
and ``shared_gate``/``shared_up``/``shared_down`` (width n_shared x f).

Reference: the plain float32 chain written from the published equations,
non-absorbed: K and V are decompressed from the latent for every head;
every routed expert is evaluated for every token and masked by the float32
router's top-k weights; attention runs in query chunks and the experts one
at a time.  Its RoPE rotates interleaved pairs (as the original RoPE
does), where the program de-interleaves and rotates by halves (the
published layout): the two give the same dot products of q and k.  It
imports nothing of the program and reads only the weights the benchmark
made.

Work: the operation and byte counts of the served model, on the
parameters a token passes through (of the routed experts, its top-k)."""
from __future__ import annotations

import functools
import importlib.util
import json
import math
from typing import Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import arith
from benchmarks.chip.model import _normal, seed_key
from benchmarks.chip.reference import (CHUNK, HIGHEST, _mm, _norm, _q8,
                                       tenant_of)

# a program without this layer cannot serve the configuration: say so at
# load, before any weights are made
if importlib.util.find_spec("repro.models.deepseek_v2") is None:
    raise ImportError("the program has no DeepSeek-V2 layer "
                      "(repro.models.deepseek_v2)")

# what the program and this reference implement of the published config
SUPPORTED = {"q_lora_rank": None, "norm_topk_prob": False,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False}


def _check(cfg: dict) -> None:
    for k, v in SUPPORTED.items():
        if cfg[k] != v:
            raise ValueError(f"{cfg['name']}: {k}={cfg[k]!r}, only {v!r} "
                             "is implemented")


def _dims(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    R, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    fe = cfg["moe_intermediate_size"]
    return dict(d=cfg["hidden_size"], H=H, R=R, rope=rope, nope=nope, vd=vd,
                f=cfg["intermediate_size"], E=cfg["n_routed_experts"],
                k=cfg["num_experts_per_tok"], fe=fe,
                fs=cfg["n_shared_experts"] * fe, L=cfg["num_hidden_layers"],
                dense=cfg["first_k_dense_replace"], V=cfg["vocab_size"])


# -- weights ------------------------------------------------------------------


def _norm_w(key, n):
    return 1.0 + _normal(key, (n,), 0.1, jnp.float32)


def _attn_weights(m: dict, key, dtype) -> dict:
    d, H, R, rope = m["d"], m["H"], m["R"], m["rope"]
    k = jax.random.split(key, 6)
    return {
        "ln1": _norm_w(k[0], d).astype(dtype),
        "q_proj": _normal(k[1], (d, H, m["nope"] + rope), d ** -0.5, dtype),
        "kv_a_proj_with_mqa": _normal(k[2], (d, R + rope), d ** -0.5, dtype),
        "kv_a_layernorm": _norm_w(k[3], R).astype(dtype),
        "kv_b_proj": _normal(k[4], (R, H, m["nope"] + m["vd"]), R ** -0.5,
                             dtype),
        "o_proj": _normal(k[5], (H, m["vd"], d), (H * m["vd"]) ** -0.5, dtype),
    }


def _ffn_weights(m: dict, key, dtype, moe: bool) -> dict:
    d, f, E, fe, fs = m["d"], m["f"], m["E"], m["fe"], m["fs"]
    k = jax.random.split(key, 8)
    out = {"ln2": _norm_w(k[0], d).astype(dtype)}
    if not moe:
        out.update(w_gate=_normal(k[1], (d, f), d ** -0.5, dtype),
                   w_up=_normal(k[2], (d, f), d ** -0.5, dtype),
                   w_down=_normal(k[3], (f, d), f ** -0.5, dtype))
        return out
    out.update(router=_normal(k[1], (d, E), d ** -0.5, dtype),
               e_gate=_normal(k[2], (E, d, fe), d ** -0.5, dtype),
               e_up=_normal(k[3], (E, d, fe), d ** -0.5, dtype),
               e_down=_normal(k[4], (E, fe, d), fe ** -0.5, dtype),
               shared_gate=_normal(k[5], (d, fs), d ** -0.5, dtype),
               shared_up=_normal(k[6], (d, fs), d ** -0.5, dtype),
               shared_down=_normal(k[7], (fs, d), fs ** -0.5, dtype))
    return out


def _lora(m: dict, key, dtype, spec: dict) -> dict:
    r, d = spec["rank"], m["d"]
    k = jax.random.split(key, 4)
    b = spec["b_rms"] / math.sqrt(r)
    return {"a_q": _normal(k[0], (d, r), d ** -0.5, dtype),
            "b_q": _normal(k[1], (r, m["H"] * (m["nope"] + m["rope"])), b,
                           dtype),
            "a_kv": _normal(k[2], (d, r), d ** -0.5, dtype),
            "b_kv": _normal(k[3], (r, m["R"] + m["rope"]), b, dtype),
            "scaling": jnp.asarray(spec["scaling"], jnp.float32)}


def make_weights(cfg: dict, seed: int) -> Dict:
    """Every tenant's weights, in one jitted call on the default device:
    ``{"base": foundation tree (layers a list), "<lora app>": [per-layer
    adapter trees]}``."""
    _check(cfg)
    return _init_fn(json.dumps(cfg, sort_keys=True))(seed_key(seed))


@functools.lru_cache(maxsize=4)
def _init_fn(cfg_json: str):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(cfg["torch_dtype"])
    m = _dims(cfg)
    peft = cfg["assumed"]["peft"]

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 4 + len(cfg["tenants"]))
        layers = []
        for i in range(m["L"]):  # one layer at a time bounds the temporaries
            ka, kf = jax.random.split(jax.random.fold_in(ks[0], i))
            layers.append({**_attn_weights(m, ka, dtype),
                           **_ffn_weights(m, kf, dtype, i >= m["dense"])})
        out = {"base": {
            "embed": _normal(ks[1], (m["V"], m["d"]), m["d"] ** -0.5, dtype),
            "layers": layers,
            "final_ln": _norm_w(ks[2], m["d"]).astype(dtype),
            "lm_head": _normal(ks[3], (m["d"], m["V"]), m["d"] ** -0.5,
                               dtype)}}
        for k, t in zip(ks[4:], cfg["tenants"]):
            if t["kind"] == "lora":
                out[t["name"]] = [
                    _lora(m, jax.random.fold_in(k, i), dtype, peft["lora"])
                    for i in range(m["L"])]
            elif t["kind"] != "foundation":
                raise ValueError(f"{t['name']}: tenant kind {t['kind']!r} "
                                 "is not implemented for this layer")
        return out

    return init


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a benchmark configuration."""
    from repro.configs.base import ModelConfig

    _check(cfg)
    scaling = tuple(sorted((k, float(v)) for k, v in
                           cfg["rope_scaling"].items() if k != "type"))
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        num_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_scaling=scaling,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), source=cfg["source"])


def build_zoo(cfg: dict, weights: Dict, log=None):
    """Register every tenant with the program's ``BlockZoo``."""
    import time

    from repro.core.zoo import BlockZoo

    mc = model_config(cfg)
    zoo = BlockZoo()
    for t in cfg["tenants"]:
        t0 = time.perf_counter()
        if t["kind"] == "foundation":
            zoo.register_foundation(t["name"], mc, weights["base"])
        else:
            zoo.register_peft(t["name"], mc, "base", t["kind"],
                              weights[t["name"]])
        if log is not None:
            log(f"registered {t['name']} ({t['kind']}) in "
                f"{time.perf_counter() - t0:.1f} s")
    return zoo


# -- reference ----------------------------------------------------------------


def _yarn(cfg: dict):
    """(inverse frequencies of the rope dims, cos/sin factor, softmax
    scale), from the published YaRN equations."""
    sc, theta, dim = cfg["rope_scaling"], cfg["rope_theta"], \
        cfg["qk_rope_head_dim"]
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(corr_dim(sc["beta_fast"])), 0)
    hi = min(math.ceil(corr_dim(sc["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    extrap = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    interp = extrap / factor
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    inv = interp * ramp + extrap * (1 - ramp)
    cs = mscale(sc.get("mscale", 1.0)) / mscale(sc.get("mscale_all_dim", 0))
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 * mscale(
        sc.get("mscale_all_dim", 0)) ** 2
    return jnp.asarray(inv, jnp.float32), cs, scale


def _rope(t, inv, cs):
    """Rotate the interleaved pairs (t[2i], t[2i+1]) of t (S, n, d) by
    position x inv[i]."""
    S, n, d = t.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x = t.reshape(S, n, d // 2, 2)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(t.shape)


def _swiglu(h, g, u, dn, low):
    return _mm(jax.nn.silu(_mm(h, g, low)) * _mm(h, u, low), dn, low)


@functools.partial(jax.jit, static_argnames=(
    "lora", "low", "eps", "k", "scaling", "cs", "scale", "nope"))
def _layer(x, p, ap, inv, *, lora: bool, low: bool, eps: float, k: int,
           scaling: float, cs: float, scale: float, nope: int):
    """One DeepSeek-V2 layer over a whole sequence (S, d), float32."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    p = jax.tree.map(f32, p)
    ap = jax.tree.map(f32, ap)
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    q = _mm(h, p["q_proj"], low)                      # (S, H, nope + rope)
    kv = _mm(h, p["kv_a_proj_with_mqa"], low)         # (S, R + rope)
    if lora:
        q = q + (_mm(_mm(h, ap["a_q"], low), ap["b_q"], low)
                 * ap["scaling"]).reshape(q.shape)
        kv = kv + _mm(_mm(h, ap["a_kv"], low), ap["b_kv"], low) * ap["scaling"]
    R = p["kv_b_proj"].shape[0]
    c = _norm(kv[:, :R], p["kv_a_layernorm"], eps)
    k_pe = _rope(kv[:, None, R:], inv, cs)
    kvb = _mm(c, p["kv_b_proj"], low)                 # (S, H, nope + v)
    H = kvb.shape[1]
    qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cs)], -1)
    kf = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (S, H, k_pe.shape[-1]))], -1)
    v = kvb[..., nope:]
    if low:
        qf, kf, v = _q8(qf, -1), _q8(kf, -1), _q8(v, 0)
    pos = jnp.arange(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qf, i * CHUNK, CHUNK)
        s = jnp.einsum("qhd,khd->hqk", qi, kf, precision=HIGHEST) * scale
        mask = (i * CHUNK + jnp.arange(CHUNK))[:, None] >= pos[None, :]
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if low:
            a = _q8(a, -1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)

    vd = v.shape[-1]
    o = jax.lax.map(block, jnp.arange(S // CHUNK)).reshape(S, H * vd)
    x = x + _mm(o, p["o_proj"].reshape(H * vd, -1), low)
    h = _norm(x, p["ln2"], eps)
    if "router" not in p:
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], low)
    probs = jax.nn.softmax(_mm(h, p["router"], low), axis=-1)    # (S, E)
    w, top = jax.lax.top_k(probs, k)
    E = probs.shape[1]
    comb = jnp.sum(jax.nn.one_hot(top, E) * (w * scaling)[..., None], 1)

    def expert(acc, e):
        g, u, dn, we = e
        return acc + we[:, None] * _swiglu(h, g, u, dn, low), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        p["e_gate"], p["e_up"], p["e_down"], comb.T))
    shared = _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                     low)
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=("low", "eps"))
def _head(x, ln, w, *, low: bool, eps: float):
    return _mm(_norm(x, ln.astype(jnp.float32), eps), w.astype(jnp.float32),
               low)


def logits(cfg: dict, weights: Dict, app: str, seq: np.ndarray,
           positions: np.ndarray, low: bool = False) -> np.ndarray:
    """Logits (len(positions), V) of ``app``'s chain over the token
    sequence ``seq``, teacher-forced, at ``positions``."""
    _check(cfg)
    t = tenant_of(cfg, app)
    base = weights["base"]
    S = len(seq)
    Sp = -(-S // CHUNK) * CHUNK  # causal: the padded tail is inert
    tok = np.zeros(Sp, np.int32)
    tok[:S] = seq
    x = jnp.take(base["embed"], jnp.asarray(tok), axis=0).astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    inv, cs, scale = _yarn(cfg)
    for i in range(cfg["num_hidden_layers"]):
        lora = t["kind"] == "lora"
        x = _layer(x, base["layers"][i], weights[app][i] if lora else {}, inv,
                   lora=lora, low=low, eps=eps,
                   k=cfg["num_experts_per_tok"],
                   scaling=float(cfg["routed_scaling_factor"]), cs=cs,
                   scale=scale, nope=cfg["qk_nope_head_dim"])
    h = x[jnp.asarray(positions)]
    return np.asarray(_head(h, base["final_ln"], base["lm_head"], low=low,
                            eps=eps))


# -- work ---------------------------------------------------------------------


def attn_params(cfg: dict) -> int:
    """Matmul parameters of one MLA block (q_proj, kv_a_proj_with_mqa,
    kv_b_proj, o_proj).  The absorbed decode applies kv_b_proj to q and
    to the output instead of to the latent: the same count."""
    m = _dims(cfg)
    d, H, R, rope = m["d"], m["H"], m["R"], m["rope"]
    return (d * H * (m["nope"] + rope) + d * (R + rope)
            + R * H * (m["nope"] + m["vd"]) + H * m["vd"] * d)


def expert_params(cfg: dict) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_active_params(cfg: dict, layer: int) -> int:
    """Matmul parameters a token passes through in layer ``layer``'s FFN:
    the dense SwiGLU, or the router, the shared experts and its top-k
    routed experts."""
    m = _dims(cfg)
    if layer < m["dense"]:
        return 3 * m["d"] * m["f"]
    return (m["d"] * m["E"] + 3 * m["d"] * m["fs"]
            + m["k"] * expert_params(cfg))


def chain_matmul_params(cfg: dict, tenant: dict) -> int:
    """Matmul parameters one token of ``tenant`` passes through: every
    layer's attention and active FFN, its LoRA and the head (the
    embedding is a lookup)."""
    m = _dims(cfg)
    lora = 0
    if tenant["kind"] == "lora":
        r = cfg["assumed"]["peft"]["lora"]["rank"]
        lora = r * (2 * m["d"] + m["H"] * (m["nope"] + m["rope"])
                    + m["R"] + m["rope"])
    return (sum(attn_params(cfg) + lora + ffn_active_params(cfg, i)
                for i in range(m["L"])) + m["d"] * m["V"])


def decode_attn_work(cfg: dict, context: int) -> tuple:
    """(FLOPs, bytes) of one query token's absorbed attention in one layer
    over ``context`` cached tokens: scores over the latent row (R + rope
    wide) and the value product over its first R columns, for every
    head; the bytes are the latent rows read, plus q read and out
    written, in bf16."""
    m = _dims(cfg)
    row = m["R"] + m["rope"]
    flops = 2.0 * m["H"] * (row + m["R"]) * context
    nbytes = (row * context + m["H"] * (row + m["R"])) * 2
    return flops, float(nbytes)


def decode_token_flops(cfg: dict, app: str, contexts: Iterable[int]) -> float:
    return arith.decode_token_flops(
        chain_matmul_params(cfg, tenant_of(cfg, app)),
        (decode_attn_work(cfg, c)[0] for c in contexts))


def prefill_flops(cfg: dict, app: str, prompt_len: int) -> float:
    """The prompt as the prefill computes it: non-absorbed attention,
    2 H (nope + rope) + 2 H v FLOPs a query and key pair."""
    m = _dims(cfg)
    pair = 2.0 * m["H"] * (m["nope"] + m["rope"] + m["vd"])
    return arith.prefill_flops(
        chain_matmul_params(cfg, tenant_of(cfg, app)), m["d"] * m["V"],
        prompt_len, m["L"], pair)
