"""Grouped matmul over rows sorted by group: the routed experts of a
dropless mixture-of-experts layer.

On a TPU it is megablox's Pallas ``gmm`` (shipped with JAX): the grid
walks only the (row tile, group) pairs that hold rows, so an expert that
no token chose is never read.  Elsewhere the oracle is
``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import jax

_WHOLE = 1536   # a k or n up to this is one tile; wider ones tile by 512


def _tile(dim: int) -> int:
    return dim if dim <= _WHOLE else 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str = "auto"):
    """lhs: (m, k) rows sorted by group; rhs: (G, k, n); group_sizes: (G,)
    int32, summing to at most m (rows past the sum are not computed and
    come back undefined).  Returns (m, n) in lhs's dtype, accumulated in
    float32."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    # one row tile while the rows fit in 512: a decode call's groups then
    # each take one grid step per (k, n) tile
    tm = min(512, _round_up(m, 16))
    mp = _round_up(m, tm)
    if mp != m:
        lhs = jax.numpy.pad(lhs, ((0, mp - m), (0, 0)))
    out = gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
              tiling=(tm, _tile(k), _tile(rhs.shape[2])),
              interpret=(impl == "interpret"))
    return out[:m]
