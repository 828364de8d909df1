"""Numerics layer of the serving stack (DESIGN.md §2).

``BlockExecutor`` owns everything that touches device compute for the
real-execution plane: the fused per-chain-signature megastep (one jitted
call per group per token: embedding -> every attention/MLP/adapter hop
with paged-KV decode and in-computation K/V write -> lm_head ->
on-device greedy argmax/softmax), device-resident ``DecodeState`` kept
across steps, batched multi-request prefill, and — as the parity oracle
and heterogeneous-tail fallback — the jitted per-(block, adapters)
function caches with per-hop group batching (cross-app batching on shared
foundation blocks, paper §5.2).  It holds no request lifecycle: the shared
``Scheduler`` decides *what* runs and the ``KVManager`` decides *where*
KV lives; the executor decides *how* it runs.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import (
    Block,
    apply_block,
    bind_params,
    block_decode_paged,
    block_prefill_raw,
    chain_decode_fused,
    chain_decode_spec_fused,
    chain_params,
    chain_prefill_fused,
    chain_signature,
    is_mla,
    is_moe,
)
from repro.observability import MetricsRegistry, Tracer
from repro.serving.kv_pool import KVManager


def _bucket(n: int, lo: int = 8) -> int:
    """Pad-to-bucket prompt length: next power of two, floor ``lo`` — bounds
    the number of prefill shapes XLA ever compiles per chain."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclass
class DecodeState:
    """Device-resident decode state for one fused group (DESIGN.md §2).

    While a group's membership is stable, its pending next-token ids,
    kv lengths and emitted-token backlog live on device; nothing syncs to
    host until a member finishes, is preempted, or the group re-forms.
    ``states`` are the engine's per-request records (duck-typed: ``rid``,
    ``tokens``, ``next_token``, ``probs_last``, ``kv_len``).

    ``emitted`` entries are ``(tokens, counts)`` draft/commit buffers: a
    device ``(B, c)`` token block plus the host ``(B,)`` per-lane count of
    how many of its columns committed.  A plain fused step appends a
    one-column block with count 1 everywhere; a speculative step appends
    its ``(B, lookahead)`` commit candidates with the per-lane accepted
    counts.  ``buffered_counts`` mirrors the running per-lane totals on
    the host so the engine's finish logic sees exact progress without
    materializing the token backlog.
    """
    rids: Tuple[int, ...]
    sig: Tuple
    states: List            # engine request states, group order
    next_token: jnp.ndarray  # (B,) pending sampled token, not yet emitted
    kv_len: jnp.ndarray      # (B,) tokens cached, tracked on device
    tables: Tuple[jnp.ndarray, ...]  # staged (B, n) page table per attn hop
    kv_len0: List[int]       # host kv_len at creation (host mirror base)
    emitted: List[Tuple[jnp.ndarray, np.ndarray]] = field(
        default_factory=list)
    buffered_counts: List[int] = field(default_factory=list)  # per lane
    probs: Optional[jnp.ndarray] = None  # (B, V) probs of latest next_token
    # (3,) int32 on device for a chain with expert layers: token-expert
    # pairs, experts hit and expert-layer calls of its megasteps so far
    moe: Optional[jnp.ndarray] = None


def _has_moe(steps) -> bool:
    return any(is_moe(b.params) for b, _ in steps)


def _no_mla_moe(steps, path: str) -> None:
    """The per-hop and speculative paths run the dense llama layer only."""
    for b, _ in steps:
        if is_mla(b.params) or is_moe(b.params):
            raise NotImplementedError(
                f"the {path} path does not run MLA or MoE blocks ({b.id})")


class BlockExecutor:
    """Fused chain execution, per-hop fallback, batching and sampling."""

    def __init__(self, attn_impl: str = "auto",
                 metrics: Optional[MetricsRegistry] = None):
        self.attn_impl = attn_impl
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # typed handles held once — the decode hot loop pays one attribute
        # add per event, not a registry lookup (DESIGN.md §8)
        self._c_prefills = self.metrics.counter("prefills")
        self._c_decode_tokens = self.metrics.counter("decode_tokens")
        self._c_group_calls = self.metrics.counter("group_calls")
        self._c_host_syncs = self.metrics.counter("host_syncs")
        self._c_spec_attempts = self.metrics.counter("spec_attempts")
        self._c_spec_hits = self.metrics.counter("spec_hits")
        # expert routing of the fused megasteps, counted on the device and
        # added here when a group syncs
        self._c_moe = [self.metrics.counter(n) for n in (
            "moe_decode_pairs", "moe_decode_experts_hit",
            "moe_decode_layer_calls")]
        # per-block batch occupancy: every batched device call observes its
        # batch width (compare p50/mean against EngineConfig.max_block_batch)
        self._h_group_batch = self.metrics.histogram("group_batch")
        self._block_fns: Dict[Tuple, object] = {}
        self._prefill_fns: Dict[Tuple, object] = {}
        # fused megastep + batched prefill, one jitted callable per chain
        # signature (prefill retraces per (B, bucket) shape)
        self._fused_fns: Dict[Tuple, Tuple[object, Tuple]] = {}
        # speculative megastep per (chain sig, surrogate sig, lookahead)
        self._spec_fns: Dict[Tuple, Tuple[object, Tuple]] = {}
        self._chain_prefill_fns: Dict[Tuple, object] = {}
        # device-resident decode state per fused group, keyed by rid tuple
        self.decode_states: Dict[Tuple[int, ...], DecodeState] = {}
        self._rid_group: Dict[int, Tuple[int, ...]] = {}
        # per-hop path: slots are fixed while a request stays resident, so a
        # group's block table is constant between membership changes: LRU
        # cache per (rids, hop); the engine invalidates on
        # finish/preempt/restore and the cap bounds membership churn
        self.table_cache_max = 128
        self._table_cache: OrderedDict[Tuple, jnp.ndarray] = OrderedDict()

    def invalidate_tables(self) -> None:
        self._table_cache.clear()

    # -- jitted per-block executors (per-hop fallback / parity oracle) -------

    def block_fn(self, block: Block, adapters: Tuple[Block, ...]):
        key = (block.id, tuple(a.id for a in adapters))
        fn = self._block_fns.get(key)
        if fn is not None:
            return fn
        impl = self.attn_impl
        hop = [(block, adapters)]
        _no_mla_moe(hop, "per-hop decode")
        if block.has_kv:
            if block.cfg.sliding_window:
                raise NotImplementedError(
                    "paged decode does not support sliding-window blocks")

            # donate the pool slabs: each lane's one-token write then
            # updates them in place instead of copying the whole pool
            @functools.partial(jax.jit, donate_argnums=(2, 3))
            def block_decode(params, x, k_pages, v_pages, tables, kv_len):
                (b, ads), = bind_params(hop, params)
                return block_decode_paged(b, x, k_pages, v_pages, tables,
                                          kv_len, adapters=ads,
                                          attn_impl=impl)
            fn = block_decode
        else:

            @jax.jit
            def block_apply(params, x):
                (b, ads), = bind_params(hop, params)
                return apply_block(b, x, adapters=ads)
            fn = block_apply

        fn = functools.partial(fn, chain_params(hop))
        self._block_fns[key] = fn
        return fn

    def prefill_fn(self, block: Block, adapters: Tuple[Block, ...]):
        """Jitted prefill per (block, adapters) — without this every prefill
        re-lowers the attention scan from scratch (dominates admission)."""
        key = (block.id, tuple(a.id for a in adapters))
        fn = self._prefill_fns.get(key)
        if fn is None:
            hop = [(block, adapters)]
            impl = self.attn_impl

            @jax.jit
            def block_prefill(params, x):
                (b, ads), = bind_params(hop, params)
                return block_prefill_raw(b, x, adapters=ads, impl=impl)

            fn = functools.partial(block_prefill, chain_params(hop))
            self._prefill_fns[key] = fn
        return fn

    # -- prefill -------------------------------------------------------------

    def prefill(self, state, tokens: np.ndarray, kv: KVManager, *,
                sample: bool = True) -> None:
        """Run ``tokens`` through the chain, allocating whole-lifetime slots
        and scattering raw K/V into the pools.  With ``sample=False`` the
        lm_head output is discarded — the recompute-on-readmit path rebuilds
        KV for an already-sampled prefix and must keep the pending token."""
        x = jnp.asarray(tokens, jnp.int32)[None]  # (1, S)
        for i, (block, adapters) in enumerate(state.steps):
            x, k_r, v = self.prefill_fn(block, adapters)(x)
            if k_r is not None:
                _, pool = kv.pool_for(block)
                if (state.rid, i) not in pool.slots:
                    slot = (getattr(state, "slot_tokens", 0)
                            or state.prompt_len + state.gen_len)
                    pool.alloc(state.rid, i, slot)
                pool.write_prefill(state.rid, i, k_r, v)
        state.kv_len = len(tokens)
        if sample:
            logits = x[0, -1]
            state.next_token = int(jnp.argmax(logits))
            state.probs_last = np.asarray(
                jax.nn.softmax(logits.astype(jnp.float32)))
            self._c_host_syncs.inc()
        self._c_prefills.inc()

    def prefill_batched(self, states: List, kv: KVManager) -> None:
        """Batched multi-request prefill: pad each request's prompt to a
        power-of-two bucket and run one jitted chain call per
        (chain signature, bucket) instead of one per-block call per request.
        KV slots must already be allocated (admission does that so the
        scheduler's ``fits`` sees true occupancy)."""
        groups: Dict[Tuple, List] = {}
        for s in states:
            key = (chain_signature(s.steps), _bucket(s.prompt_len))
            groups.setdefault(key, []).append(s)
        for (sig, bucket), members in groups.items():
            with Tracer.span("exec.prefill", B=len(members), bucket=bucket):
                self._prefill_group(sig, bucket, members, kv)

    def chain_prefill_fn(self, steps, sig):
        fn = self._chain_prefill_fns.get(sig)
        if fn is None:
            impl = self.attn_impl

            @jax.jit
            def chain_prefill(params, tok, lens):
                return chain_prefill_fused(bind_params(steps, params), tok,
                                           lens, impl)

            fn = functools.partial(chain_prefill, chain_params(steps))
            self._chain_prefill_fns[sig] = fn
        return fn

    def _prefill_group(self, sig, bucket: int, states: List,
                       kv: KVManager) -> None:
        B = len(states)
        tok = np.zeros((B, bucket), np.int32)
        for i, s in enumerate(states):
            tok[i, :s.prompt_len] = s.prompt_tokens
        lens = jnp.asarray([s.prompt_len for s in states], jnp.int32)
        fn = self.chain_prefill_fn(states[0].steps, sig)
        nxt, probs, kvs = fn(jnp.asarray(tok), lens)
        with Tracer.span("kv.write_prefill"):
            hop = 0
            for i, (block, _) in enumerate(states[0].steps):
                if not block.has_kv:
                    continue
                _, pool = kv.pool_for(block)
                for bi, s in enumerate(states):
                    pool.write_prefill(s.rid, i, *(
                        t[bi:bi + 1, :s.prompt_len] for t in kvs[hop]))
                hop += 1
        with Tracer.span("exec.sync"):
            nxt_h, probs_h = jax.device_get((nxt, probs))
        self._c_host_syncs.inc()
        for i, s in enumerate(states):
            s.kv_len = s.prompt_len
            s.next_token = int(nxt_h[i])
            s.probs_last = np.asarray(probs_h[i])
            self._c_prefills.inc()

    # -- fused chain-step decode (device-resident megastep) ------------------

    @staticmethod
    def _pool_layout(steps) -> Tuple[List[Tuple], List[int]]:
        """KV-pool layout of a chain: the ordered list of distinct pool
        signatures it touches and, per attention hop, the index into it."""
        pool_keys: List[Tuple] = []
        pool_index: List[int] = []
        for block, _ in steps:
            if block.has_kv:
                if block.cfg.sliding_window:
                    raise NotImplementedError(
                        "paged decode does not support sliding-window blocks")
                key = block.kv_signature
                if key not in pool_keys:
                    pool_keys.append(key)
                pool_index.append(pool_keys.index(key))
        return pool_keys, pool_index

    def fused_fn(self, steps, sig):
        """One jitted megastep per chain signature; returns (fn, pool_keys)
        where ``pool_keys`` orders the KV-pool signatures the chain needs.
        A chain with expert layers takes and returns one more argument,
        its ``DecodeState.moe`` counts."""
        cached = self._fused_fns.get(sig)
        if cached is not None:
            return cached
        impl = self.attn_impl
        pool_keys, pool_index = self._pool_layout(steps)

        @functools.partial(jax.jit, donate_argnums=(2, 3))
        def chain_decode(params, tok, pools_k, pools_v, tables, kv_len,
                         *moe):
            return chain_decode_fused(bind_params(steps, params), pool_index,
                                      tok, pools_k, pools_v, tables, kv_len,
                                      *moe, attn_impl=impl)

        out = (functools.partial(chain_decode, chain_params(steps)),
               tuple(pool_keys))
        self._fused_fns[sig] = out
        return out

    def spec_fn(self, steps, sur_steps, sig, lookahead: int):
        """Jitted draft-verify megastep (paper §5.2) per (chain signature,
        surrogate signature, lookahead).  The surrogate chain must share the
        full chain's KV-pool layout (FFN-only surrogates guarantee this);
        verification reuses the exact fused-step graph, so committed tokens
        are bit-identical to the plain fused path."""
        key = (sig, chain_signature(sur_steps), lookahead)
        cached = self._spec_fns.get(key)
        if cached is not None:
            return cached
        _no_mla_moe(steps, "speculative")
        impl = self.attn_impl
        pool_keys, pool_index = self._pool_layout(steps)
        sur_keys, _ = self._pool_layout(sur_steps)
        if tuple(sur_keys) != tuple(pool_keys):
            raise ValueError(
                "surrogate chain must share the full chain's KV-pool layout")

        @functools.partial(jax.jit, donate_argnums=(3, 4))
        def chain_decode_spec(params, sur_params, tok, pools_k, pools_v,
                              tables, kv_len, budget):
            return chain_decode_spec_fused(
                bind_params(steps, params), bind_params(sur_steps, sur_params),
                pool_index, tok, pools_k, pools_v, tables, kv_len, budget,
                lookahead=lookahead, attn_impl=impl)

        out = (functools.partial(chain_decode_spec, chain_params(steps),
                                 chain_params(sur_steps)), tuple(pool_keys))
        self._spec_fns[key] = out
        return out

    def buffered(self, rid: int) -> int:
        """Tokens a request has committed since its host state was last
        synced (0 when it is not device-resident)."""
        key = self._rid_group.get(rid)
        if key is None:
            return 0
        ds = self.decode_states[key]
        if not ds.buffered_counts:
            return 0
        return ds.buffered_counts[ds.rids.index(rid)]

    def retire_states(self, keep: frozenset = frozenset()) -> None:
        """Sync-and-drop every DecodeState whose rid tuple is not in
        ``keep`` — called when group membership changes (finish, preempt,
        admission) so host state is fresh before the engine touches it."""
        for rids in [k for k in self.decode_states if k not in keep]:
            self._sync_state(self.decode_states.pop(rids))
            for r in rids:
                self._rid_group.pop(r, None)

    def sync_rid(self, rid: int) -> None:
        """Materialize the group containing ``rid`` (no-op when absent)."""
        key = self._rid_group.get(rid)
        if key is not None:
            self.retire_states(keep=frozenset(
                k for k in self.decode_states if k != key))

    def _sync_state(self, ds: DecodeState) -> None:
        if not ds.emitted:
            return  # never stepped: host state is still authoritative
        with Tracer.span("exec.sync"):
            blocks, nxt, probs, moe = jax.device_get(
                (tuple(t for t, _ in ds.emitted), ds.next_token, ds.probs,
                 ds.moe))
            self._c_host_syncs.inc()
            if moe is not None:
                for c, v in zip(self._c_moe, moe):
                    c.inc(int(v))
            for i, s in enumerate(ds.states):
                for t, cnt in zip(blocks, (c for _, c in ds.emitted)):
                    s.tokens.extend(int(tok) for tok in t[i, :cnt[i]])
                s.next_token = int(nxt[i])
                s.probs_last = probs[i]
                s.kv_len = ds.kv_len0[i] + ds.buffered_counts[i]

    def _make_state(self, states: List, kv: KVManager) -> DecodeState:
        steps = states[0].steps
        sig = chain_signature(steps)
        rids = tuple(s.rid for s in states)
        tables = []
        for i, (block, _) in enumerate(steps):
            if block.has_kv:
                _, pool = kv.pool_for(block)
                tables.append(jnp.asarray(
                    pool.block_table([(s.rid, i) for s in states])))
        ds = DecodeState(
            rids=rids, sig=sig, states=list(states),
            next_token=jnp.asarray([s.next_token for s in states], jnp.int32),
            kv_len=jnp.asarray([s.kv_len for s in states], jnp.int32),
            tables=tuple(tables),
            kv_len0=[s.kv_len for s in states],
            buffered_counts=[0] * len(states),
            moe=jnp.zeros(3, jnp.int32) if _has_moe(steps) else None)
        self.decode_states[rids] = ds
        for r in rids:
            self._rid_group[r] = rids
        return ds

    def fused_step(self, states: List, kv: KVManager) -> None:
        """One token for one fused group: a single jitted call covering the
        whole chain, with sampling on device.  The pending token and kv
        lengths stay device-resident between calls."""
        rids = tuple(s.rid for s in states)
        ds = self.decode_states.get(rids)
        if ds is None:
            with Tracer.span("exec.make_state"):
                ds = self._make_state(states, kv)
        fn, pool_keys = self.fused_fn(states[0].steps, ds.sig)
        pools = [kv.pools[k] for k in pool_keys]
        pk = tuple(p.k_pages for p in pools)
        pv = tuple(p.v_pages for p in pools)
        self._c_group_calls.inc()
        self._h_group_batch.observe(len(states))
        moe = () if ds.moe is None else (ds.moe,)
        nxt, probs, pk, pv, kv_len, *moe = fn(ds.next_token, pk, pv,
                                              ds.tables, ds.kv_len, *moe)
        if moe:
            ds.moe, = moe
        for p, k_new, v_new in zip(pools, pk, pv):
            p.k_pages, p.v_pages = k_new, v_new
        B = len(states)
        ds.emitted.append((ds.next_token[:, None], np.ones(B, np.int64)))
        for i in range(B):
            ds.buffered_counts[i] += 1
        ds.next_token = nxt
        ds.probs = probs
        ds.kv_len = kv_len
        self._c_decode_tokens.inc(len(states))

    def spec_step(self, states: List, kv: KVManager, sur_steps,
                  lookahead: int, budgets: List[int]
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One draft-verify megastep for one fused group (paper §5.2): the
        surrogate chain drafts ``lookahead - 1`` tokens, the full chain
        verifies all positions inside the same jitted call, and per-lane
        accept/rollback happens on device.  Commits 1..lookahead tokens per
        lane; returns host ``(attempts, hits, committed)`` arrays (one small
        count sync per call — the engine needs exact per-lane progress for
        finish decisions).  ``budgets[i]`` is how many tokens lane i may
        still commit (rem); the device clamps drafts so the pending-token
        protocol never overshoots it."""
        rids = tuple(s.rid for s in states)
        ds = self.decode_states.get(rids)
        if ds is None:
            with Tracer.span("exec.make_state"):
                ds = self._make_state(states, kv)
        fn, pool_keys = self.spec_fn(states[0].steps, sur_steps, ds.sig,
                                     lookahead)
        pools = [kv.pools[k] for k in pool_keys]
        pk = tuple(p.k_pages for p in pools)
        pv = tuple(p.v_pages for p in pools)
        self._c_group_calls.inc()
        self._h_group_batch.observe(len(states))
        budget = jnp.asarray(budgets, jnp.int32)
        (commit_tok, commit_cnt, accepted, attempts, nxt, probs,
         pk, pv, kv_len) = fn(ds.next_token, pk, pv, ds.tables,
                              ds.kv_len, budget)
        for p, k_new, v_new in zip(pools, pk, pv):
            p.k_pages, p.v_pages = k_new, v_new
        with Tracer.span("exec.sync"):
            cnt_h, acc_h, att_h = (np.asarray(a, np.int64) for a in
                                   jax.device_get((commit_cnt, accepted,
                                                   attempts)))
        self._c_host_syncs.inc()
        ds.emitted.append((commit_tok, cnt_h))
        for i in range(len(states)):
            ds.buffered_counts[i] += int(cnt_h[i])
        ds.next_token = nxt
        ds.probs = probs
        ds.kv_len = kv_len
        self._c_decode_tokens.inc(int(cnt_h.sum()))
        self._c_spec_attempts.inc(int(att_h.sum()))
        self._c_spec_hits.inc(int(acc_h.sum()))
        return att_h, acc_h, cnt_h

    # -- decode: per-hop batched group execution (fallback path) -------------

    def seed_tokens(self, states) -> Dict[int, jnp.ndarray]:
        """Per-request (1, 1) input carrying the pending sampled token."""
        return {s.rid: jnp.asarray([[s.next_token]], jnp.int32)
                for s in states}

    def _tables_for(self, rids: List[int], cursor: int, pool,
                    cursors) -> jnp.ndarray:
        key = (tuple(rids), cursor)
        tables = self._table_cache.get(key)
        if tables is not None:
            self._table_cache.move_to_end(key)
            return tables
        tables = jnp.asarray(pool.block_table(
            [(r, cursors[r]) for r in rids]))
        self._table_cache[key] = tables
        while len(self._table_cache) > self.table_cache_max:
            self._table_cache.popitem(last=False)
        return tables

    def run_group(self, rids: List[int], by_rid, cursors, xs,
                  kv: KVManager) -> None:
        """Batched execution of one (block, adapters) group at one hop."""
        s0 = by_rid[rids[0]]
        cursor = cursors[s0.rid]
        block, adapters = s0.steps[cursor]
        fn = self.block_fn(block, adapters)
        x = jnp.concatenate([xs[r] for r in rids], axis=0)
        self._c_group_calls.inc()
        self._h_group_batch.observe(len(rids))
        if block.has_kv:
            _, pool = kv.pool_for(block)
            tables = self._tables_for(rids, cursor, pool, cursors)
            kv_len = jnp.asarray([by_rid[r].kv_len for r in rids], jnp.int32)
            out, pool.k_pages, pool.v_pages = fn(
                x, pool.k_pages, pool.v_pages, tables, kv_len)
        else:
            out = fn(x)
        for i, r in enumerate(rids):
            xs[r] = out[i:i + 1]

    # -- sampling (fallback path; the fused megastep samples on device) ------

    def sample_step(self, states, xs) -> None:
        """Greedy next-token selection over the lm_head outputs — one
        batched argmax/softmax per step keeps host round-trips off the hot
        path.  Final-step probabilities are kept for requests emitting
        their last token next step (adaptive-serving quality, Fig. 20)."""
        by_vocab: Dict[int, list] = {}
        for s in states:
            by_vocab.setdefault(xs[s.rid].shape[-1], []).append(s)
        for group in by_vocab.values():
            logits = jnp.concatenate([xs[s.rid] for s in group], axis=0)[:, 0]
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            self._c_host_syncs.inc()
            last = [i for i, s in enumerate(group)
                    if len(s.tokens) + 1 >= s.gen_len]
            if last:
                probs = np.asarray(jax.nn.softmax(
                    logits[jnp.asarray(last)].astype(jnp.float32), axis=-1))
                self._c_host_syncs.inc()
                for j, i in enumerate(last):
                    group[i].probs_last = probs[j]
            for i, s in enumerate(group):
                s.next_token = int(nxt[i])
                s.kv_len += 1
                self._c_decode_tokens.inc()
