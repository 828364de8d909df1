"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.batched_lora.kernel import batched_lora_matmul
from repro.kernels.batched_lora.ref import batched_lora_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Hq,KVH,S,hd", [
    (1, 4, 4, 256, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA
    (1, 4, 1, 512, 128),    # MQA, bigger block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, Hq, KVH, S, hd, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KVH, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KVH, S, hd), dtype)
    out = flash_attention_fwd(q, k, v, bq=128, bk=128, causal=causal,
                              interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,Hq,KVH,hd,page,npages_per_seq", [
    (2, 8, 2, 64, 128, 4),
    (3, 4, 4, 128, 128, 2),
    (1, 8, 1, 64, 256, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(B, Hq, KVH, hd, page, npages_per_seq, dtype):
    rng = np.random.RandomState(0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    total_pages = B * npages_per_seq + 2
    q = jax.random.normal(ks[0], (B, Hq, hd), dtype)
    k_pages = jax.random.normal(ks[1], (total_pages, KVH, page, hd), dtype)
    v_pages = jax.random.normal(ks[2], (total_pages, KVH, page, hd), dtype)
    # each sequence owns a disjoint, shuffled set of pages
    perm = rng.permutation(B * npages_per_seq) + 2
    block_tables = jnp.asarray(perm.reshape(B, npages_per_seq), jnp.int32)
    seq_lens = jnp.asarray(
        rng.randint(1, page * npages_per_seq + 1, size=(B,)), jnp.int32)
    out = paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                          interpret=True)
    ref = paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("T,D,F,G,r,bt,bf", [
    (256, 128, 256, 4, 16, 128, 128),
    (512, 256, 512, 2, 8, 128, 256),
    (128, 64, 128, 1, 4, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_lora(T, D, F, G, r, bt, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (T, D), dtype)
    w = jax.random.normal(ks[1], (D, F), dtype) / np.sqrt(D)
    a = jax.random.normal(ks[2], (G, D, r), dtype) / np.sqrt(D)
    b = jax.random.normal(ks[3], (G, r, F), dtype) / np.sqrt(r)
    tile_groups = jnp.asarray(
        np.random.RandomState(3).randint(0, G, size=(T // bt,)), jnp.int32)
    out = batched_lora_matmul(x, w, a, b, tile_groups, bt=bt, bf=bf,
                              scaling=0.5, interpret=True)
    ref = batched_lora_ref(x, w, a, b, tile_groups, bt=bt, scaling=0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_page_pool_roundtrip():
    """write_token_to_pages + paged_attention_ref == dense decode_attention."""
    from repro.kernels.paged_attention.ops import write_token_to_pages

    B, KVH, hd, page, nps = 2, 2, 64, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    k_pages = jnp.zeros((B * nps + 1, KVH, page, hd), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    block_tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    # fill 130 tokens of each sequence token-by-token, then attend
    ktoks = jax.random.normal(ks[0], (130, B, KVH, hd))
    vtoks = jax.random.normal(ks[1], (130, B, KVH, hd))
    for t in range(130):
        k_pages, v_pages = write_token_to_pages(
            k_pages, v_pages, block_tables,
            jnp.full((B,), t, jnp.int32), ktoks[t], vtoks[t])
    q = jax.random.normal(ks[2], (B, 4, hd))
    seq_lens = jnp.full((B,), 130, jnp.int32)
    out = paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens)

    from repro.models.layers import decode_attention

    kd = jnp.stack([ktoks[:, b] for b in range(B)])  # (B, 130, KVH, hd)
    vd = jnp.stack([vtoks[:, b] for b in range(B)])
    ref = decode_attention(q[:, None][:, 0:1].reshape(B, 1, 4, hd), kd, vd,
                           seq_lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_write_token_lanes_match_scatter(dtype):
    """Per-lane in-place writes give the slabs the one 2-index scatter
    gives, away from the trash page: four lanes, two of them on one page at
    different slots, one padded onto the trash page (page 0)."""
    from repro.kernels.paged_attention.ops import write_token_to_pages

    KVH, hd, page = 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    k_pages = jax.random.normal(ks[0], (6, KVH, page, hd), dtype)
    v_pages = jax.random.normal(ks[1], (6, KVH, page, hd), dtype)
    block_tables = jnp.asarray([[1, 2], [1, 3], [4, 5], [0, 0]], jnp.int32)
    positions = jnp.asarray([3, 5, 9, 0], jnp.int32)  # lane 2: page 5
    k_new = jax.random.normal(ks[2], (4, KVH, hd), jnp.float32)
    v_new = jax.random.normal(ks[3], (4, KVH, hd), jnp.float32)

    page_idx = block_tables[jnp.arange(4), positions // page]
    slot = positions % page
    want_k = k_pages.at[page_idx, :, slot].set(k_new.astype(dtype))
    want_v = v_pages.at[page_idx, :, slot].set(v_new.astype(dtype))
    got_k, got_v = jax.jit(write_token_to_pages)(
        k_pages, v_pages, block_tables, positions, k_new, v_new)
    np.testing.assert_array_equal(np.asarray(got_k[1:], np.float32),
                                  np.asarray(want_k[1:], np.float32))
    np.testing.assert_array_equal(np.asarray(got_v[1:], np.float32),
                                  np.asarray(want_v[1:], np.float32))
