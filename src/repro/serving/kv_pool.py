"""KV manager: slot-based paged KV pools shared across chains, with
preemption (DESIGN.md §2).

One ``KVPool`` per (kv_heads, head_dim, dtype) signature holds two
head-major page slabs ``(num_pages, KVH, page_size, hd)`` for K and V; a
multi-head latent attention pool (signature ``("latent", width)``) holds
one token-minor slab ``(num_pages, 1, width, page_size)`` of latent rows
(``kernels/mla_paged_attention`` says why) and no V.  Every
attention-bearing chain step of every in-flight request owns a run of
page ids (a *slot*) carved out of the same slab, so requests from
different apps — and the shared foundation blocks they batch on — draw
from one memory budget, the way vLLM-style paged attention manages a
single device cache.

``KVManager`` coordinates the pools as one memory plane: admission
planning across signatures, slot **preemption** (spill the pages to host
memory, or drop them for recompute-on-readmit — the paper's §5.1
transfer-vs-recalc decision applied to a single host), and restore.

Page 0 is reserved as a scratch ("trash") page: group batching pads ragged
block tables with it, and masked lanes of padded rows read/write there
harmlessly.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

TRASH_PAGE = 0  # reserved scratch page for padded table entries


@functools.partial(jax.jit, static_argnames=("token_minor",))
def kv_write_prefill(pages, new, idx, token_minor: bool = False):
    """Scatter a prefill's raw K or V ``(1, S, KVH, hd)`` into the pages
    ``idx`` of a head-major slab ``(num_pages, KVH, page_size, hd)`` (with
    ``token_minor``, of a ``(num_pages, KVH, hd, page_size)`` slab),
    padding S up to ``len(idx)`` whole pages.  The slab is not donated, so
    XLA copies it whole around the scatter."""
    npages, page = idx.shape[0], pages.shape[3 if token_minor else 2]
    new = jnp.pad(new[0], ((0, npages * page - new.shape[1]), (0, 0), (0, 0)))
    new = new.reshape(npages, page, *new.shape[1:]).transpose(
        (0, 2, 3, 1) if token_minor else (0, 2, 1, 3))
    return pages.at[idx].set(new.astype(pages.dtype))


@dataclass
class KVSlot:
    """A sequence's page run inside one pool for one attention block."""
    pages: List[int]
    max_len: int  # capacity in tokens = len(pages) * page_size


class KVPool:
    """Paged K/V slab with a free list and per-slot bookkeeping."""

    def __init__(self, num_pages: int, page_size: int, kv_heads: int,
                 head_dim: int, dtype=jnp.bfloat16, metrics=None,
                 name: str = "", values: bool = True):
        assert num_pages >= 2, "pool needs at least the trash page + one slot"
        self.page_size = page_size
        self.num_pages = num_pages
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        # a latent pool keeps one token-minor slab: its V is part of the
        # K rows
        shape = ((num_pages, kv_heads, page_size, head_dim) if values
                 else (num_pages, kv_heads, head_dim, page_size))
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype) if values else None
        # page 0 reserved (TRASH_PAGE); never handed out
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self.slots: Dict[Tuple[int, int], KVSlot] = {}  # (rid, step) -> slot
        self.alloc_count = 0
        self.free_count = 0
        # observability (DESIGN.md §8): used/free pages as gauges, tagged
        # by pool signature so per-signature pressure is visible
        self.metrics = metrics
        self.name = name or f"{kv_heads}x{head_dim}"
        self._update_gauges()

    def _update_gauges(self):
        if self.metrics is not None:
            self.metrics.set_gauge(f"kv_used_pages[{self.name}]",
                                   self.used_pages)
            self.metrics.set_gauge(f"kv_free_pages[{self.name}]",
                                   len(self._free))

    # -- accounting ---------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def page_bytes(self) -> int:
        """K+V bytes held by one page."""
        slabs = 1 if self.v_pages is None else 2
        return slabs * (self.page_size * self.kv_heads * self.head_dim
                        * jnp.dtype(self.k_pages.dtype).itemsize)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def pages_needed(self, tokens: int) -> int:
        return max(1, -(-tokens // self.page_size))

    def can_fit(self, tokens: int, n_slots: int) -> bool:
        return self.pages_needed(tokens) * n_slots <= len(self._free)

    # -- slot lifecycle -----------------------------------------------------

    def alloc(self, rid: int, step: int, tokens: int) -> KVSlot:
        """Reserve enough pages for ``tokens`` total tokens (prompt + full
        generation budget — allocation happens once, at admission)."""
        n = self.pages_needed(tokens)
        if n > len(self._free):
            raise MemoryError(
                f"KV pool exhausted: need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        slot = KVSlot(pages=pages, max_len=n * self.page_size)
        self.slots[(rid, step)] = slot
        self.alloc_count += n
        self._update_gauges()
        return slot

    def free(self, rid: int, step: int):
        slot = self.slots.pop((rid, step))
        self._free.extend(slot.pages)
        self.free_count += len(slot.pages)
        self._update_gauges()

    def free_request(self, rid: int):
        for key in [k for k in self.slots if k[0] == rid]:
            self.free(*key)

    # -- batched table construction ----------------------------------------

    def block_table(self, keys: List[Tuple[int, int]]) -> np.ndarray:
        """Stack the slots' page runs into a (B, n) int32 table, padding
        ragged rows with the trash page (reads beyond kv_len are masked)."""
        rows = [self.slots[k].pages for k in keys]
        width = max(len(r) for r in rows)
        table = np.full((len(rows), width), TRASH_PAGE, np.int32)
        for i, r in enumerate(rows):
            table[i, :len(r)] = r
        return table

    # -- prefill scatter ----------------------------------------------------

    def write_prefill(self, rid: int, step: int, k_r, v=None):
        """Scatter a prefill's raw K/V (1, S, KVH, hd) into the slot's pages
        (a latent pool's rows come as ``k_r`` alone)."""
        pages = self.slots[(rid, step)].pages
        idx = jnp.asarray(pages[:self.pages_needed(k_r.shape[1])], jnp.int32)
        # each call makes a whole new slab (not donated): waiting for this
        # slab's previous write keeps one queued copy per slab, where
        # back-to-back calls queue copies until HBM is full; the other
        # slab's write keeps the device busy meanwhile
        self.k_pages.block_until_ready()
        self.k_pages = kv_write_prefill(self.k_pages, k_r, idx,
                                        token_minor=self.v_pages is None)
        if self.v_pages is not None:
            self.v_pages.block_until_ready()
            self.v_pages = kv_write_prefill(self.v_pages, v, idx)


# ---------------------------------------------------------------------------
# manager: the pools as one coordinated memory plane
# ---------------------------------------------------------------------------


@dataclass
class KVSnapshot:
    """Host-side copy of a preempted request's pages (spill strategy).

    Keyed by (pool signature, chain step); each value is the (K, V) page
    stack exactly as it sat in the device slabs (V None for a latent
    pool)."""
    pages: Dict[Tuple[Tuple, int], Tuple[np.ndarray, Optional[np.ndarray]]
                ] = field(default_factory=dict)
    kv_bytes: int = 0


class KVManager:
    """Coordinates one ``KVPool`` per KV signature under a shared budget.

    The serving engine's memory layer: admission planning (can a request's
    whole slot footprint fit *now*), allocation bookkeeping, and slot
    preemption/restore so long requests can be paused under memory
    pressure instead of blocking the queue (lifting the
    "all slots allocated at admission forever" restriction)."""

    def __init__(self, page_size: int, num_pages: int, dtype=jnp.bfloat16,
                 metrics=None, tracer=None):
        self.page_size = page_size
        self.num_pages = num_pages
        self.dtype = dtype
        self.metrics = metrics  # shared registry: per-pool page gauges
        self.tracer = tracer    # spill/restore lifecycle events (§8)
        self.pools: Dict[Tuple, KVPool] = {}

    def pool_for(self, block) -> Tuple[Tuple, KVPool]:
        """The (signature key, pool) a block's KV slots live in; pools are
        created lazily on first use of a signature."""
        key = block.kv_signature
        pool = self.pools.get(key)
        if pool is None:
            latent = key[0] == "latent"
            heads, width = (1, key[1]) if latent else key
            pool = self.pools[key] = KVPool(
                self.num_pages, self.page_size, heads, width,
                dtype=self.dtype, metrics=self.metrics,
                name=f"latent{width}" if latent else "", values=not latent)
        return key, pool

    # -- admission planning --------------------------------------------------

    def plan(self, steps) -> Dict[Tuple, int]:
        """Slots needed per pool signature for one request's resolved chain
        steps (``[(block, adapters), ...]``)."""
        need: Dict[Tuple[int, int], int] = {}
        for block, _ in steps:
            if block.has_kv:
                key, _ = self.pool_for(block)
                need[key] = need.get(key, 0) + 1
        return need

    def can_admit(self, steps, tokens: int) -> bool:
        """Whole-lifetime footprint check: every slot the request will ever
        need (``tokens`` = prompt + full generation budget) fits now."""
        return all(self.pools[k].can_fit(tokens, n)
                   for k, n in self.plan(steps).items())

    # -- request lifecycle ---------------------------------------------------

    def free_request(self, rid: int) -> None:
        for pool in self.pools.values():
            pool.free_request(rid)

    def kv_bytes(self, rid: int) -> int:
        """Device bytes currently pinned by a request across all pools."""
        total = 0
        for pool in self.pools.values():
            for (r, _), slot in pool.slots.items():
                if r == rid:
                    total += len(slot.pages) * pool.page_bytes
        return total

    # -- preemption ----------------------------------------------------------

    def spill(self, rid: int) -> KVSnapshot:
        """Copy the request's pages to host memory and free its slots."""
        snap = KVSnapshot()
        for key, pool in self.pools.items():
            for r, step in [k for k in pool.slots if k[0] == rid]:
                slot = pool.slots[(r, step)]
                idx = jnp.asarray(slot.pages, jnp.int32)
                snap.pages[(key, step)] = (
                    np.asarray(pool.k_pages[idx]),
                    None if pool.v_pages is None
                    else np.asarray(pool.v_pages[idx]))
                snap.kv_bytes += len(slot.pages) * pool.page_bytes
                pool.free(r, step)
        if self.tracer is not None:
            self.tracer.event(rid, "spill", kv_bytes=snap.kv_bytes,
                              slots=len(snap.pages))
        return snap

    def restore(self, rid: int, snap: KVSnapshot, tokens: int) -> None:
        """Re-allocate slots (possibly on different pages) and write the
        spilled page contents back into the device slabs."""
        for (key, step), (k_np, v_np) in snap.pages.items():
            pool = self.pools[key]
            slot = pool.alloc(rid, step, tokens)
            assert len(slot.pages) == k_np.shape[0], \
                "restore allocated a different page count than was spilled"
            idx = jnp.asarray(slot.pages, jnp.int32)
            pool.k_pages = pool.k_pages.at[idx].set(
                jnp.asarray(k_np, pool.k_pages.dtype))
            if v_np is not None:
                pool.v_pages = pool.v_pages.at[idx].set(
                    jnp.asarray(v_np, pool.v_pages.dtype))
        if self.tracer is not None:
            self.tracer.event(rid, "restore", kv_bytes=snap.kv_bytes,
                              slots=len(snap.pages))
