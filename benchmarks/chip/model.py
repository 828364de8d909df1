"""Configuration files, seeds and the weight-making helpers every
architecture module (``archs/<model_type>.py``) shares."""
from __future__ import annotations

import json
from pathlib import Path

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
