"""Segment-aligned batched LoRA (TPU Pallas) — multi-tenant adapter serving.

BlockLLM's block zoo shares one foundation block across tenants whose PEFT
deltas differ (paper Table 1); at serving time a batch mixes requests from
many adapters.  This kernel computes

    y[t] = x[t] @ W + s * (x[t] @ A[g(t)]) @ B[g(t)]

in one pass.  The serving batcher packs requests so each row-tile of size
``bt`` belongs to ONE adapter (segment-aligned padding — repro.serving
controls batch composition, so this is free); the per-tile adapter id is a
scalar-prefetch operand consumed by the A/B BlockSpec index_maps.

VMEM budget per grid step: x(bt,D) + W(D,bf) + A(D,r) + B(r,bf) + acc —
D up to 8k, bt=bf=256, r<=64 stays well under 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lora_kernel(tile_groups, x_ref, w_ref, a_ref, b_ref, o_ref,
                 *, scaling: float):
    x = x_ref[...].astype(jnp.float32)  # (bt, D)
    w = w_ref[...].astype(jnp.float32)  # (D, bf)
    a = a_ref[0].astype(jnp.float32)  # (D, r)
    b = b_ref[0].astype(jnp.float32)  # (r, bf)
    base = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    xa = jax.lax.dot_general(x, a, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jax.lax.dot_general(xa, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[...] = (base + scaling * delta).astype(o_ref.dtype)


def batched_lora_matmul(x, w, a, b, tile_groups, *, bt: int = 128,
                        bf: int = 256, scaling: float = 1.0,
                        interpret: bool = False):
    """x: (T, D); w: (D, F); a: (G, D, r); b: (G, r, F);
    tile_groups: (T // bt,) int32 adapter id per row tile.

    Returns (T, F).
    """
    T, D = x.shape
    F = w.shape[1]
    bt = min(bt, T)
    bf = min(bf, F)
    assert T % bt == 0 and F % bf == 0, (T, F, bt, bf)
    assert tile_groups.shape[0] == T // bt

    grid = (T // bt, F // bf)
    kernel = functools.partial(_lora_kernel, scaling=scaling)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j, tg: (i, 0)),
            pl.BlockSpec((D, bf), lambda i, j, tg: (0, j)),
            pl.BlockSpec((1, D, a.shape[-1]), lambda i, j, tg: (tg[i], 0, 0)),
            pl.BlockSpec((1, b.shape[1], bf), lambda i, j, tg: (tg[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda i, j, tg: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(tile_groups, x, w, a, b)
