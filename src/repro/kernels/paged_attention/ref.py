"""Pure-jnp oracle for paged decode attention."""
import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens):
    """q: (B, Hq, hd); pages: (P, KVH, page, hd); block_tables: (B, n)."""
    B, Hq, hd = q.shape
    _, KVH, page, _ = k_pages.shape
    n = block_tables.shape[1]
    G = Hq // KVH

    def dense(pages):  # gather each sequence's pages -> (B, n*page, KVH, hd)
        return pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
            B, n * page, KVH, hd)

    k, v = dense(k_pages), dense(v_pages)
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    pos = jnp.arange(n * page)[None, None, None]
    s = jnp.where(pos < seq_lens[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, hd).astype(q.dtype)
