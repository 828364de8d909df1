"""The plain float32 reference of a served llama chain, and the check
that decides ``correct``.

Written from the model's definition (RMSNorm pre-norm, rotate-half RoPE,
GQA/MHA causal attention, SwiGLU, untied head) and from the adapters'
definitions: LoRA adds ``h A B * scaling`` to q and v; BitFit adds biases
to q, k and v; an adapter maps the layer's output ``y`` to
``y + gelu(y down) up`` (tanh gelu).  It imports nothing of the program
and reads only the weights the benchmark made.

``widest gap``: for every served token, the reference's best logit at
that position minus the reference's logit of the served token (greedy
decoding serves the argmax, so a sound program reads a rounding-sized
gap).  The control is this same reference computed in float8 (e4m3,
per-channel scales) for every matmul operand: it reports the gap of the
token that float8 puts first."""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 512  # query rows per attention block
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the reduced axis of the matmul it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, low: bool):
    """a (S, d) @ w (d, ...) in float32 at HIGHEST, or with both operands
    in float8 when ``low``."""
    if low:
        a = _q8(a, -1)
        w = _q8(w.reshape(w.shape[0], -1), 0).reshape(w.shape)
    return jnp.tensordot(a, w, axes=1, precision=HIGHEST)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(t, theta):
    S, _, hd = t.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    t1, t2 = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("kind", "low", "eps", "theta"))
def _layer(x, p, ap, *, kind: str, low: bool, eps: float, theta: float):
    """One llama layer over a whole sequence (S, d), float32."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    p = jax.tree.map(f32, p)
    ap = jax.tree.map(f32, ap)
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    q, k, v = (_mm(h, p[w], low) for w in ("wq", "wk", "wv"))
    if kind == "lora":
        q = q + (_mm(_mm(h, ap["a_q"], low), ap["b_q"], low)
                 * ap["scaling"]).reshape(q.shape)
        v = v + (_mm(_mm(h, ap["a_v"], low), ap["b_v"], low)
                 * ap["scaling"]).reshape(v.shape)
    if kind == "bitfit":
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    H, KVH, hd = q.shape[1], k.shape[1], q.shape[2]
    k, v = (jnp.repeat(t, H // KVH, axis=1) for t in (k, v))
    if low:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    pos = jnp.arange(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * CHUNK, CHUNK)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) / hd ** 0.5
        mask = (i * CHUNK + jnp.arange(CHUNK))[:, None] >= pos[None, :]
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if low:
            a = _q8(a, -1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(S // CHUNK)).reshape(S, H, hd)
    x = x + _mm(o.reshape(S, H * hd), p["wo"].reshape(H * hd, -1), low)
    h = _norm(x, p["ln2"], eps)
    y = x + _mm(jax.nn.silu(_mm(h, p["w_gate"], low)) * _mm(h, p["w_up"], low),
                p["w_down"], low)
    if kind == "adapter":
        y = y + _mm(jax.nn.gelu(_mm(y, ap["down"], low), approximate=True),
                    ap["up"], low)
    return y


@functools.partial(jax.jit, static_argnames=("low", "eps"))
def _head(x, ln, w, *, low: bool, eps: float):
    return _mm(_norm(x, ln.astype(jnp.float32), eps), w.astype(jnp.float32),
               low)


def tenant_of(cfg: dict, app: str) -> dict:
    return next(t for t in cfg["tenants"] if t["name"] == app)


def logits(cfg: dict, weights: Dict, app: str, seq: np.ndarray,
           positions: np.ndarray, low: bool = False) -> np.ndarray:
    """Logits (len(positions), V) of ``app``'s chain over the token
    sequence ``seq``, teacher-forced, at ``positions``."""
    t = tenant_of(cfg, app)
    base = weights["base"]
    S = len(seq)
    Sp = -(-S // CHUNK) * CHUNK  # causal: the padded tail is inert
    tok = np.zeros(Sp, np.int32)
    tok[:S] = seq
    x = jnp.take(base["embed"], jnp.asarray(tok), axis=0).astype(jnp.float32)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    for i in range(cfg["num_hidden_layers"]):
        p = base["layers"][i]
        kind, ap = "none", {}
        if t["kind"] == "fpft":
            p = weights[app].get(str(i), p)
        elif t["kind"] != "foundation":
            kind, ap = t["kind"], weights[app][i]
        x = _layer(x, p, ap, kind=kind, low=low, eps=eps, theta=theta)
    h = x[jnp.asarray(positions)]
    return np.asarray(_head(h, base["final_ln"], base["lm_head"], low=low,
                            eps=eps))


def widest_gaps(cfg: dict, weights: Dict, rec: dict,
                control: bool = False) -> Dict[str, float]:
    """For one served request: the widest gap of its served tokens, and
    with ``control`` that of the float8 reference's first choices."""
    toks = np.asarray(rec["tokens"], np.int32)
    seq = np.concatenate([rec["prompt"], toks[:-1]])
    pos = np.arange(len(rec["prompt"]) - 1, len(seq))
    ref = logits(cfg, weights, rec["app"], seq, pos)
    best = ref.max(-1)
    out = {"served": float((best - ref[np.arange(len(toks)), toks]).max())}
    if control:
        low = logits(cfg, weights, rec["app"], seq, pos, low=True)
        first = low.argmax(-1)
        out["control"] = float((best - ref[np.arange(len(toks)), first]).max())
    return out


def pick_sample(done: List[dict], seed: int, target_tokens: int,
                kinds: Dict[str, str]) -> List[dict]:
    """The longest finished request, then one of each tenant kind not yet
    in (``kinds`` maps app to kind), then others, each drawn from the seed,
    until ``target_tokens`` served tokens are in the sample."""
    if not done:
        return []
    recs = sorted(done, key=lambda r: (r["prompt_len"] + r["n_out"], r["rid"]))
    out = [recs.pop()]
    rng = np.random.default_rng([int(seed) % 2 ** 63, 11])
    order = [recs[i] for i in rng.permutation(len(recs))]
    for kind in dict.fromkeys(kinds.values()):
        if all(kinds[r["app"]] != kind for r in out):
            out += [r for r in order if kinds[r["app"]] == kind][:1]
    for r in order:
        if sum(o["n_out"] for o in out) >= target_tokens:
            break
        if not any(r is o for o in out):
            out.append(r)
    return out


def check(cfg: dict, weights: Dict, sample: List[dict], *,
          control: bool = False, log=print) -> Optional[dict]:
    """Widest gaps over ``sample``; None when it is empty."""
    if not sample:
        return None
    served, ctrl = 0.0, 0.0
    for rec in sample:
        g = widest_gaps(cfg, weights, rec, control)
        served = max(served, g["served"])
        ctrl = max(ctrl, g.get("control", 0.0))
        log(f"reference: rid {rec['rid']} {rec['app']} prompt "
            f"{rec['prompt_len']} out {rec['n_out']}: widest gap "
            f"{g['served']:.4f}" + (f", float8 control {g['control']:.4f}"
                                    if control else ""))
    out = {"served": served, "tokens": sum(r["n_out"] for r in sample)}
    if control:
        out["control"] = ctrl
    return out
