"""Weights and tenants of a configuration, made by the benchmark.

All weights of all tenants come from one jitted call on the device, from
the seed, in the dtype they are served in.  The same trees feed the
program's ``BlockZoo`` and the benchmark's own reference."""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_config(name: str, config_dir=CONFIG_DIR) -> dict:
    cfg = json.loads((Path(config_dir) / f"{name}.json").read_text())
    cfg["name"] = name
    return cfg


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _layer(cfg: dict, key, dtype) -> dict:
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


def _diverge(layer: dict, key, scale: float) -> dict:
    """A fine-tune's layer: the foundation's plus ``scale`` x each leaf's
    std of noise."""
    keys = jax.random.split(key, len(layer))
    return {n: (x.astype(jnp.float32) + scale * jnp.std(
        x.astype(jnp.float32)) * jax.random.normal(k, x.shape)).astype(x.dtype)
        for k, (n, x) in zip(keys, sorted(layer.items()))}


def _per_layer(fn, key, n: int) -> list:
    """``n`` trees from ``fn(key)``, made by one vmapped call and unstacked
    (inside a jit the slices are the outputs themselves, not copies)."""
    stacked = jax.vmap(fn)(jax.random.split(key, n))
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


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
        layers = _per_layer(lambda k: _layer(cfg, k, dtype), ks[0], L)
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


def chain_matmul_params(cfg: dict, tenant: dict) -> int:
    """Matmul parameters one token of ``tenant`` passes through: every
    layer, its adapters and the head (the embedding is a lookup)."""
    from benchmarks.chip.arith import layer_params

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
