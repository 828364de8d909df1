"""Pure-jnp oracle for paged decode attention over a latent cache."""
import jax
import jax.numpy as jnp


def mla_paged_attention_ref(q, pages, block_tables, seq_lens, *,
                            value_dim: int, sm_scale: float):
    """q: (B, H, L); pages: (P, 1, L, page) token-minor; block_tables:
    (B, n)."""
    B, H, L = q.shape
    page = pages.shape[3]
    n = block_tables.shape[1]
    kv = pages[block_tables][:, :, 0].transpose(0, 1, 3, 2).reshape(
        B, n * page, L)
    kv = kv.astype(jnp.float32)
    s = jnp.einsum("bhl,bsl->bhs", q.astype(jnp.float32), kv) * sm_scale
    pos = jnp.arange(n * page)[None, None]
    s = jnp.where(pos < seq_lens[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", p, kv[..., :value_dim])
    return o.astype(q.dtype)
