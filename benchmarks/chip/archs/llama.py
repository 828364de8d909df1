"""The llama layer: RMSNorm pre-norm, rotate-half RoPE, GQA/MHA causal
attention, SwiGLU, untied head; and its tenants' adapters.

Weights: all weights of all tenants come from one jitted call on the
device, from the seed, in the dtype they are served in.  The same trees
feed the program's ``BlockZoo`` and the reference.

Reference: the plain float32 chain, written from the model's definition
and from the adapters' definitions: LoRA adds ``h A B * scaling`` to q
and v; BitFit adds biases to q, k and v; an adapter maps the layer's
output ``y`` to ``y + gelu(y down) up`` (tanh gelu).  It imports nothing
of the program and reads only the weights the benchmark made.

Work: the operation and byte counts of the served model."""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import arith
from benchmarks.chip.model import _diverge, _normal, _per_layer, seed_key
from benchmarks.chip.reference import (CHUNK, HIGHEST, _mm, _norm, _q8,
                                       tenant_of)

# -- weights ------------------------------------------------------------------


def _layer_weights(cfg: dict, key, dtype) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    k = jax.random.split(key, 9)
    return {
        "ln1": (1.0 + _normal(k[0], (d,), 0.1, jnp.float32)).astype(dtype),
        "ln2": (1.0 + _normal(k[1], (d,), 0.1, jnp.float32)).astype(dtype),
        "wq": _normal(k[2], (d, h, hd), d ** -0.5, dtype),
        "wk": _normal(k[3], (d, kvh, hd), d ** -0.5, dtype),
        "wv": _normal(k[4], (d, kvh, hd), d ** -0.5, dtype),
        "wo": _normal(k[5], (h, hd, d), (h * hd) ** -0.5, dtype),
        "w_gate": _normal(k[6], (d, f), d ** -0.5, dtype),
        "w_up": _normal(k[7], (d, f), d ** -0.5, dtype),
        "w_down": _normal(k[8], (f, d), f ** -0.5, dtype),
    }


def _peft(cfg: dict, kind: str, key, dtype, spec: dict) -> list:
    d = cfg["hidden_size"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def one(k):
        k = jax.random.split(k, 4)
        if kind == "lora":
            r = spec["rank"]
            return {
                "a_q": _normal(k[0], (d, r), d ** -0.5, dtype),
                "b_q": _normal(k[1], (r, h * hd), spec["b_rms"] / math.sqrt(r),
                               dtype),
                "a_v": _normal(k[2], (d, r), d ** -0.5, dtype),
                "b_v": _normal(k[3], (r, kvh * hd),
                               spec["b_rms"] / math.sqrt(r), dtype),
                "scaling": jnp.asarray(spec["scaling"], jnp.float32)}
        if kind == "adapter":
            b = spec["bottleneck"]
            return {
                "down": _normal(k[0], (d, b), d ** -0.5, dtype),
                "up": _normal(k[1], (b, d), spec["up_rms"] / math.sqrt(b),
                              dtype)}
        if kind == "bitfit":
            return {"bq": _normal(k[0], (h, hd), spec["rms"], dtype),
                    "bk": _normal(k[1], (kvh, hd), spec["rms"], dtype),
                    "bv": _normal(k[2], (kvh, hd), spec["rms"], dtype)}
        raise ValueError(f"unknown adapter kind {kind!r}")

    return _per_layer(one, key, cfg["num_hidden_layers"])


def make_weights(cfg: dict, seed: int) -> Dict:
    """Every tenant's weights, in one jitted call on the default device:
    ``{"base": foundation tree, "<fpft app>": {layer index: layer tree},
    "<peft app>": [per-layer adapter trees]}``."""
    return _init_fn(json.dumps(cfg, sort_keys=True))(seed_key(seed))


@functools.lru_cache(maxsize=4)
def _init_fn(cfg_json: str):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    peft = cfg["assumed"]["peft"]

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 4 + len(cfg["tenants"]))
        layers = _per_layer(lambda k: _layer_weights(cfg, k, dtype), ks[0], L)
        out = {"base": {
            "embed": _normal(ks[1], (V, d), d ** -0.5, dtype),
            "layers": layers,
            "final_ln": (1.0 + _normal(ks[2], (d,), 0.1, jnp.float32)
                         ).astype(dtype),
            "lm_head": _normal(ks[3], (d, V), d ** -0.5, dtype)}}
        for k, t in zip(ks[4:], cfg["tenants"]):
            if t["kind"] == "fpft":
                out[t["name"]] = {
                    str(i): _diverge(layers[i], jax.random.fold_in(k, i),
                                     peft["fpft"]["noise"])
                    for i in t["layers"]}
            elif t["kind"] != "foundation":
                out[t["name"]] = _peft(cfg, t["kind"], k, dtype, peft[t["kind"]])
        return out

    return init


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a benchmark configuration."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        source=cfg["source"])


def build_zoo(cfg: dict, weights: Dict, log=None):
    """Register every tenant with the program's ``BlockZoo``."""
    import time

    from repro.core.zoo import BlockZoo

    mc = model_config(cfg)
    zoo = BlockZoo()
    base = weights["base"]
    for t in cfg["tenants"]:
        name, kind = t["name"], t["kind"]
        t0 = time.perf_counter()
        if kind == "foundation":
            zoo.register_foundation(name, mc, base)
        elif kind == "fpft":
            layers = list(base["layers"])
            for i, lp in weights[name].items():
                layers[int(i)] = lp
            zoo.register_fpft(name, mc, {**base, "layers": layers}, "base")
        else:
            zoo.register_peft(name, mc, "base", kind, weights[name])
        if log is not None:
            log(f"registered {name} ({kind}) in "
                f"{time.perf_counter() - t0:.1f} s")
    return zoo


# -- reference ----------------------------------------------------------------


def _rope(t, theta):
    S, _, hd = t.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    t1, t2 = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("kind", "low", "eps", "theta"))
def _layer(x, p, ap, *, kind: str, low: bool, eps: float, theta: float):
    """One llama layer over a whole sequence (S, d), float32."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    p = jax.tree.map(f32, p)
    ap = jax.tree.map(f32, ap)
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    q, k, v = (_mm(h, p[w], low) for w in ("wq", "wk", "wv"))
    if kind == "lora":
        q = q + (_mm(_mm(h, ap["a_q"], low), ap["b_q"], low)
                 * ap["scaling"]).reshape(q.shape)
        v = v + (_mm(_mm(h, ap["a_v"], low), ap["b_v"], low)
                 * ap["scaling"]).reshape(v.shape)
    if kind == "bitfit":
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    H, KVH, hd = q.shape[1], k.shape[1], q.shape[2]
    k, v = (jnp.repeat(t, H // KVH, axis=1) for t in (k, v))
    if low:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    pos = jnp.arange(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * CHUNK, CHUNK)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) / hd ** 0.5
        mask = (i * CHUNK + jnp.arange(CHUNK))[:, None] >= pos[None, :]
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if low:
            a = _q8(a, -1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(S // CHUNK)).reshape(S, H, hd)
    x = x + _mm(o.reshape(S, H * hd), p["wo"].reshape(H * hd, -1), low)
    h = _norm(x, p["ln2"], eps)
    y = x + _mm(jax.nn.silu(_mm(h, p["w_gate"], low)) * _mm(h, p["w_up"], low),
                p["w_down"], low)
    if kind == "adapter":
        y = y + _mm(jax.nn.gelu(_mm(y, ap["down"], low), approximate=True),
                    ap["up"], low)
    return y


@functools.partial(jax.jit, static_argnames=("low", "eps"))
def _head(x, ln, w, *, low: bool, eps: float):
    return _mm(_norm(x, ln.astype(jnp.float32), eps), w.astype(jnp.float32),
               low)


def logits(cfg: dict, weights: Dict, app: str, seq: np.ndarray,
           positions: np.ndarray, low: bool = False) -> np.ndarray:
    """Logits (len(positions), V) of ``app``'s chain over the token
    sequence ``seq``, teacher-forced, at ``positions``."""
    t = tenant_of(cfg, app)
    base = weights["base"]
    S = len(seq)
    Sp = -(-S // CHUNK) * CHUNK  # causal: the padded tail is inert
    tok = np.zeros(Sp, np.int32)
    tok[:S] = seq
    x = jnp.take(base["embed"], jnp.asarray(tok), axis=0).astype(jnp.float32)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    for i in range(cfg["num_hidden_layers"]):
        p = base["layers"][i]
        kind, ap = "none", {}
        if t["kind"] == "fpft":
            p = weights[app].get(str(i), p)
        elif t["kind"] != "foundation":
            kind, ap = t["kind"], weights[app][i]
        x = _layer(x, p, ap, kind=kind, low=low, eps=eps, theta=theta)
    h = x[jnp.asarray(positions)]
    return np.asarray(_head(h, base["final_ln"], base["lm_head"], low=low,
                            eps=eps))


# -- work ---------------------------------------------------------------------


def layer_params(cfg: dict) -> int:
    """Parameters of one llama layer (RMSNorm, GQA attention, SwiGLU)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return 2 * d + d * hd * (2 * h + 2 * kvh) + 3 * d * f


def chain_matmul_params(cfg: dict, tenant: dict) -> int:
    """Matmul parameters one token of ``tenant`` passes through: every
    layer, its adapters and the head (the embedding is a lookup)."""
    L = cfg["num_hidden_layers"]
    d, h, kvh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    per_layer = layer_params(cfg) - 2 * d
    extra = 0
    spec = cfg["assumed"]["peft"].get(tenant["kind"], {})
    if tenant["kind"] == "lora":
        extra = spec["rank"] * (2 * d + h * hd + kvh * hd)
    elif tenant["kind"] == "adapter":
        extra = 2 * d * spec["bottleneck"]
    return L * (per_layer + extra) + d * cfg["vocab_size"]


def attn_flops(cfg: dict, context: int) -> float:
    """Score and value products of one query token over ``context``
    cached tokens, in one attention layer."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context


def decode_attn_bytes(cfg: dict, context: int, dtype_bytes: int = 2) -> float:
    """Bytes one query token's paged attention has to move in one layer:
    the live K and V of ``context`` tokens, plus q read and out written."""
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * context
    qo = 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return float((kv + qo) * dtype_bytes)


def decode_attn_work(cfg: dict, context: int) -> tuple:
    return attn_flops(cfg, context), decode_attn_bytes(cfg, context)


def decode_token_flops(cfg: dict, app: str, contexts: Iterable[int]) -> float:
    return arith.decode_token_flops(
        chain_matmul_params(cfg, tenant_of(cfg, app)),
        (attn_flops(cfg, c) for c in contexts))


def prefill_flops(cfg: dict, app: str, prompt_len: int) -> float:
    return arith.prefill_flops(
        chain_matmul_params(cfg, tenant_of(cfg, app)),
        cfg["hidden_size"] * cfg["vocab_size"], prompt_len,
        cfg["num_hidden_layers"], attn_flops(cfg, 1))
