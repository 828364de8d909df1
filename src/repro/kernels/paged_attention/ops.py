"""Jitted wrapper + page-pool utilities used by the serving engine."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention as _kernel
from repro.kernels.paged_attention.ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens)
    return _kernel(q, k_pages, v_pages, block_tables, seq_lens,
                   interpret=(impl == "interpret"))


def write_token_to_pages(k_pages, v_pages, block_tables, positions, k_new, v_new):
    """Write one token per sequence into its page pool, in place.

    k_pages/v_pages: (P, KVH, page_size, hd); k_new/v_new: (B, KVH, hd);
    positions: (B,) absolute token index.

    Each lane's (KVH, hd) token is one ``dynamic_update_slice`` at
    (page, 0, slot, 0), which XLA performs in place on a donated slab.  A
    single scatter indexed on dims 0 and 2 (``.at[page, :, slot].set``)
    holds the same values, but for B > 1 the TPU compiler gives the
    scatter a slab layout of its own and copies the whole slab back for
    the attention kernel, for K and for V, at every hop.  Lanes are
    written in order; only padded lanes share a (page, slot), on the trash
    page, which is never read unmasked.
    """
    page_size = k_pages.shape[2]
    page_idx = block_tables[jnp.arange(block_tables.shape[0]),
                            positions // page_size]
    slot = positions % page_size
    k_new = k_new.astype(k_pages.dtype)[:, :, None]  # (B, KVH, 1, hd)
    v_new = v_new.astype(v_pages.dtype)[:, :, None]
    for b in range(k_new.shape[0]):
        at = (page_idx[b], 0, slot[b], 0)
        k_pages = jax.lax.dynamic_update_slice(k_pages, k_new[b:b + 1], at)
        v_pages = jax.lax.dynamic_update_slice(v_pages, v_new[b:b + 1], at)
    return k_pages, v_pages


def paged_decode_step(q, k_new, v_new, k_pages, v_pages, block_tables,
                      kv_len, *, impl: str = "auto"):
    """One fused single-token decode step: write the new token's K/V into
    the pages, then attend over them (the write and the attention lower
    into one computation when called under an enclosing jit).

    q/k_new/v_new: (B, H, hd) / (B, KVH, hd); kv_len: (B,) tokens already
    cached.  Returns (o, k_pages, v_pages) with o: (B, H, hd).
    """
    k_pages, v_pages = write_token_to_pages(
        k_pages, v_pages, block_tables, kv_len, k_new, v_new)
    o = paged_attention(q, k_pages, v_pages, block_tables, kv_len + 1,
                        impl=impl)
    return o, k_pages, v_pages
