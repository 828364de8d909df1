"""Jitted wrapper and the one-token decode step over a latent pool."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.mla_paged_attention.kernel import (
    mla_paged_attention as _kernel,
)
from repro.kernels.mla_paged_attention.ref import mla_paged_attention_ref


@functools.partial(jax.jit,
                   static_argnames=("value_dim", "sm_scale", "impl"))
def mla_paged_attention(q, pages, block_tables, seq_lens, *, value_dim: int,
                        sm_scale: float, impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return mla_paged_attention_ref(q, pages, block_tables, seq_lens,
                                       value_dim=value_dim, sm_scale=sm_scale)
    return _kernel(q, pages, block_tables, seq_lens, value_dim=value_dim,
                   sm_scale=sm_scale, interpret=(impl == "interpret"))


def write_latent_token(pages, block_tables, positions, new):
    """Each lane's latent row new (B, L) into column ``positions % page``
    of its page of the token-minor slab (P, 1, L, page_size): one
    ``dynamic_update_slice`` per lane, in place on a donated slab (as
    ``paged_attention.ops.write_token_to_pages`` writes K/V)."""
    page_size = pages.shape[3]
    page_idx = block_tables[jnp.arange(block_tables.shape[0]),
                            positions // page_size]
    slot = positions % page_size
    new = new.astype(pages.dtype)[:, None, :, None]  # (B, 1, L, 1)
    for b in range(new.shape[0]):
        pages = jax.lax.dynamic_update_slice(pages, new[b:b + 1],
                                             (page_idx[b], 0, 0, slot[b]))
    return pages


def mla_paged_decode_step(q, latent_new, pages, block_tables, kv_len, *,
                          value_dim: int, sm_scale: float,
                          impl: str = "auto"):
    """Write each lane's new latent row into its page in place, then
    attend over the pages.  q: (B, H, L); latent_new: (B, L); pages:
    (P, 1, L, page_size); kv_len: (B,) tokens already cached.  Returns
    (o (B, H, value_dim), pages)."""
    pages = write_latent_token(pages, block_tables, kv_len, latent_new)
    o = mla_paged_attention(q.astype(pages.dtype), pages, block_tables,
                            kv_len + 1, value_dim=value_dim,
                            sm_scale=sm_scale, impl=impl)
    return o, pages
