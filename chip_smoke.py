#!/usr/bin/env python3
"""Chip smoke check: serve TinyLlama-1.1B at full width through BlockEngine
on one TPU chip, and check what comes out.

    python3 chip_smoke.py [--seed N]

Random weights from the seed, nothing downloaded.  The demo zoo is built at
``tinyllama-1.1b`` width (22 layers, d_model 2048, 32 heads / 4 KV heads,
d_ff 5632, vocab 32000): foundation ``base``, FPFT variant ``vicuna`` whose
one divergent layer is its only new block, and LoRA app ``app-lora``.
Phases, each of which fails the run:

1. kernel     the Pallas paged-attention kernel matches
              ``paged_attention_ref`` on the device at TinyLlama shapes;
2. megastep   every app's compiled fused decode program holds one
              ``tpu_custom_call`` per attention hop (``attn_impl="pallas"``,
              explicit);
3. serve      8 requests round robin over the three apps, prompt lengths
              128-512 from the seed, gen_len 32, max_active 8, through
              ``BlockEngine.submit``/``drain`` — once to compile, once more
              timed, with identical tokens; the per-hop fallback
              (``engine._run_hops``) is never entered;
4. reference  each request's ``probs_last`` matches a float32 forward of
              its chain, teacher-forced on prompt + emitted tokens.

Earlier lines print widths, set-up and compile seconds, requests and tokens,
TTFT and step-wall p50 and peak device memory: printed, never claimed.  The
last line of stdout is ``{"ok": true, "device": {...}}``, printed only when
JAX's first device is a TPU and every phase passed; otherwise the script
exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "tinyllama-1.1b"
# probs_last vs the float32 reference, as total-variation distance.  The
# engine runs bf16 activations over bf16-cast weights (unit roundoff 2^-8)
# through every hop; the reference is float32 at "highest" matmul
# precision.  Random-init logits are ~N(0, 1) across the vocab, so an
# unrelated distribution sits near TV 0.5, while bf16 rounding gave
# 4.3e-3 / 4.9e-3 at 2 / 6 layers of this width on the CPU backend and
# grows slowly with depth: 0.05 leaves ~10x room at 22 layers.
TV_TOL = 0.05
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)  # bf16 kernel vs oracle (as tests)


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_kernel(cfg, *, attn_impl: str, batch: int, max_len: int,
                 page_size: int, seed: int, log=_log) -> float:
    """The paged-attention kernel against its jnp oracle at the served
    shapes and dtype; returns the max abs difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.models.layers import COMPUTE_DTYPE

    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    n = -(-max_len // page_size)
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pages = 1 + batch * n
    q = jax.random.normal(ks[0], (batch, H, hd), COMPUTE_DTYPE)
    k = jax.random.normal(ks[1], (pages, KVH, page_size, hd), COMPUTE_DTYPE)
    v = jax.random.normal(ks[2], (pages, KVH, page_size, hd), COMPUTE_DTYPE)
    tables = jnp.asarray((rng.permutation(batch * n) + 1).reshape(batch, n),
                         jnp.int32)
    lens = jnp.asarray(rng.randint(1, max_len + 1, size=batch), jnp.int32)
    out = paged_attention(q, k, v, tables, lens, impl=attn_impl)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q, k, v, tables, lens)
    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    np.testing.assert_allclose(out, ref, **KERNEL_TOL)
    err = float(np.abs(out - ref).max())
    log(f"kernel: {attn_impl} paged attention == ref at B={batch} H={H} "
        f"KVH={KVH} hd={hd} page={page_size} pages/seq={n} "
        f"{jnp.dtype(COMPUTE_DTYPE).name}; max abs diff {err:.3e}")
    return err


def megastep_kernel_calls(engine, app: str):
    """(Pallas kernel calls, attention hops) of the fused decode program the
    engine runs for ``app``'s chain, compiled for one lane from the
    executor's own cached function."""
    import jax
    import jax.numpy as jnp

    from repro.core.blocks import chain_signature

    steps, _ = engine._steps(engine.zoo.chains[app], None)
    fn, pool_keys = engine.executor.fused_fn(steps, chain_signature(steps))
    for block, _ in steps:
        if block.has_kv:
            engine.kv.pool_for(block)
    pools = [engine.kv.pools[k] for k in pool_keys]
    n = -(-engine.max_len // engine.config.page_size)
    sds = jax.ShapeDtypeStruct
    args = (sds((1,), jnp.int32),
            tuple(sds(p.k_pages.shape, p.k_pages.dtype) for p in pools),
            tuple(sds(p.v_pages.shape, p.v_pages.dtype) for p in pools),
            tuple(sds((1, n), jnp.int32) for b, _ in steps if b.has_kv),
            sds((1,), jnp.int32))
    text = fn.func.lower(*fn.args, *args).compile().as_text()
    return (text.count('custom_call_target="tpu_custom_call"'),
            sum(b.has_kv for b, _ in steps))


def make_requests(cfg, apps, *, n: int, prompt_lens, gen_len: int,
                  seed: int):
    import numpy as np

    from repro.serving.api import ServeRequest

    rng = np.random.RandomState(seed)
    lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, size=n)
    return [ServeRequest(app=apps[i % len(apps)], gen_len=gen_len,
                         prompt_tokens=rng.randint(0, cfg.vocab_size,
                                                   size=int(s)).astype(
                                                       np.int32))
            for i, s in enumerate(lens)]


def _ref_forward(kinds, cfg, params, tokens, last):
    """Plain float32 forward of a resolved chain over one sequence; returns
    the logits at position ``last``.  Written from the model's definition,
    independent of the serving code: embedding, per layer RMSNorm ->
    GQA attention with RoPE (+ LoRA on q/v) -> SwiGLU, final norm, lm_head."""
    import jax
    import jax.numpy as jnp

    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    S = tokens.shape[0]
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                   / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(t):  # (S, heads, hd), rotate-half
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    def norm(t, w):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                 + cfg.norm_eps) * w

    causal = jnp.tril(jnp.ones((S, S), bool))
    x = None
    for (kind, adapter_kinds), (p, aps) in zip(kinds, params):
        if kind == "embed":
            x = p["embed"][tokens]
        if kind in ("layer", "attention"):
            h = norm(x, p["ln1"])
            q = jnp.einsum("sd,dhk->shk", h, p["wq"])
            k = jnp.einsum("sd,dhk->shk", h, p["wk"])
            v = jnp.einsum("sd,dhk->shk", h, p["wv"])
            for ak, ap in zip(adapter_kinds, aps):
                if ak != "lora":
                    raise NotImplementedError(f"reference adapter {ak}")
                q = q + (h @ ap["a_q"] @ ap["b_q"]
                         * ap["scaling"]).reshape(q.shape)
                v = v + (h @ ap["a_v"] @ ap["b_v"]
                         * ap["scaling"]).reshape(v.shape)
            q, k = rope(q), rope(k)
            k, v = (jnp.repeat(t, H // KVH, axis=1) for t in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("hqk,khd->qhd", a, v)
            x = x + jnp.einsum("qhd,hdm->qm", o, p["wo"])
        if kind in ("layer", "ffn"):
            h = norm(x, p["ln2"])
            x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
                     ) @ p["w_down"]
        if kind == "lm_head":
            x = norm(x, p["final_ln"]) @ p["lm_head"]
    return x[last]


def check_reference(zoo, cfg, reqs, results, *, max_len: int,
                    log=_log) -> float:
    """Every request's probs_last against the float32 reference of its
    chain, teacher-forced on prompt + emitted tokens (the last emitted
    token was drawn from probs_last, so it is not fed).  Returns the worst
    total-variation distance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref_fn = jax.jit(_ref_forward, static_argnums=(0, 1))
    t0 = time.perf_counter()
    worst = 0.0
    for req, res in zip(reqs, results):
        chain = zoo.chains[req.app]
        hops = [(zoo.blocks[s.block_id],
                 [zoo.blocks[a] for a in s.adapter_ids])
                for s in chain.steps]
        kinds = tuple((b.kind, tuple(a.kind for a in ads)) for b, ads in hops)
        params = tuple((b.params, tuple(a.params for a in ads))
                       for b, ads in hops)
        seq = np.concatenate([req.prompt_tokens, res.tokens[:-1]])
        padded = np.zeros(max_len, np.int32)  # causal: the tail is inert
        padded[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            logits = ref_fn(kinds, cfg, params, jnp.asarray(padded),
                            len(seq) - 1)
        ref = np.asarray(jax.nn.softmax(logits), np.float64)
        got = np.asarray(res.probs_last, np.float64)
        tv = 0.5 * float(np.abs(got - ref).sum())
        worst = max(worst, tv)
        log(f"reference: rid {res.rid} app {req.app} len {len(seq)}: "
            f"TV {tv:.3e}, engine token {int(res.tokens[-1])} "
            f"ref argmax {int(ref.argmax())}")
        if not tv <= TV_TOL:
            raise AssertionError(
                f"rid {res.rid}: probs_last is TV {tv:.3e} from the float32 "
                f"reference (tolerance {TV_TOL})")
    log(f"reference: {len(reqs)} requests within TV {TV_TOL} "
        f"(worst {worst:.3e}) in {time.perf_counter() - t0:.1f} s")
    return worst


def run(*, arch: str = ARCH, attn_impl: str = "pallas", seed: int = 0,
        n_requests: int = 8, prompt_lens=(128, 512), gen_len: int = 32,
        max_active: int = 8, log=_log) -> dict:
    """All phases at ``arch``'s width; raises on the first failure."""
    import jax
    import numpy as np

    from repro.serving.demo import build_demo_zoo
    from repro.serving.engine import BlockEngine, EngineConfig

    t0 = time.perf_counter()
    cfg, zoo = build_demo_zoo(seed=seed, arch=arch)
    t_setup = time.perf_counter() - t0
    log(f"arch {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} kv, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    log(f"zoo: apps {list(zoo.chains)}, {len(zoo.blocks)} blocks, "
        f"{zoo.zoo_bytes() / 1e9:.3f} GB of weights; set-up {t_setup:.1f} s")

    max_len = prompt_lens[1] + gen_len
    config = EngineConfig(max_active=max_active, attn_impl=attn_impl)
    check_kernel(cfg, attn_impl=attn_impl, batch=max_active, max_len=max_len,
                 page_size=config.page_size, seed=seed, log=log)

    engine = BlockEngine(zoo, max_len=max_len, config=config)
    hop_calls = []

    def _no_hops(states):
        hop_calls.append(len(states))
        raise AssertionError("a group took the per-hop fallback")

    engine._run_hops = _no_hops
    if attn_impl == "pallas":
        t0 = time.perf_counter()
        for app in zoo.chains:
            n_calls, n_hops = megastep_kernel_calls(engine, app)
            log(f"megastep: {app} decode program holds {n_calls} "
                f"tpu_custom_call for {n_hops} attention hops")
            if n_calls != n_hops:
                raise AssertionError(
                    f"{app}: {n_calls} Pallas kernel calls in the megastep, "
                    f"expected one per attention hop ({n_hops})")
        log(f"megastep: compiled in {time.perf_counter() - t0:.1f} s")

    apps = list(zoo.chains)
    passes = []
    for label in ("compile", "timed"):
        reqs = make_requests(cfg, apps, n=n_requests, prompt_lens=prompt_lens,
                             gen_len=gen_len, seed=seed)
        t0 = time.perf_counter()
        for r in reqs:
            engine.submit(r)
        results = sorted(engine.drain(), key=lambda r: r.rid)
        wall = time.perf_counter() - t0
        passes.append((reqs, results, wall))
        log(f"serve ({label} pass): {len(results)}/{len(reqs)} requests, "
            f"{sum(len(r.tokens) for r in results)} tokens, {wall:.2f} s")
        if len(results) != len(reqs) or any(
                len(r.tokens) != gen_len for r in results):
            raise AssertionError("not every request completed in full")
    (_, first, wall_c), (reqs, results, wall_t) = passes
    if any(not np.array_equal(a.tokens, b.tokens)
           for a, b in zip(first, results)):
        raise AssertionError("the timed pass emitted different tokens")
    log(f"per-hop fallback entered {len(hop_calls)} times; "
        f"group calls {engine.stats['group_calls']}, "
        f"host syncs {engine.stats['host_syncs']}")
    ttft = [r.info["ttft_s"] for r in results]
    steps = engine.metrics.histogram("step_wall_s")
    report = {
        "setup_s": t_setup,
        "compile_s": wall_c - wall_t,
        "timed_pass_s": wall_t,
        "requests": len(results),
        "tokens": sum(len(r.tokens) for r in results),
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "step_wall_p50_s": steps.percentile(50),
    }
    log(f"TTFT p50 {report['ttft_p50_s']:.4f} s (timed pass), step wall "
        f"p50 {report['step_wall_p50_s']:.4f} s (both passes), host clock; "
        f"compile ~{report['compile_s']:.1f} s (first pass minus timed pass)")

    report["worst_tv"] = check_reference(zoo, cfg, reqs, results,
                                         max_len=max_len, log=log)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        report["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
        log(f"peak_bytes_in_use {stats['peak_bytes_in_use']}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"device {dev.device_kind} x{len(devices)}; compile cache "
         f"{enable_compile_cache()}")
    run(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
