"""The check that decides ``correct``, against the plain float32
reference of the served chain, which the configuration's architecture
module gives (``logits`` in ``archs/<model_type>.py``), and the helpers
those references share.  It imports nothing of the program and reads
only the weights the benchmark made.

``widest gap``: for every served token, the reference's best logit at
that position minus the reference's logit of the served token (greedy
decoding serves the argmax, so a sound program reads a rounding-sized
gap).  The control is this same reference computed in float8 (e4m3,
per-channel scales) for every matmul operand: it reports the gap of the
token that float8 puts first."""
from __future__ import annotations

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


def tenant_of(cfg: dict, app: str) -> dict:
    return next(t for t in cfg["tenants"] if t["name"] == app)


def widest_gaps(cell, weights: Dict, rec: dict,
                control: bool = False) -> Dict[str, float]:
    """For one served request of ``cell``: the widest gap of its served
    tokens, and with ``control`` that of the float8 reference's first
    choices."""
    toks = np.asarray(rec["tokens"], np.int32)
    seq = np.concatenate([rec["prompt"], toks[:-1]])
    pos = np.arange(len(rec["prompt"]) - 1, len(seq))
    arch, cfg = cell.arch, cell.cfg
    ref = arch.logits(cfg, weights, rec["app"], seq, pos)
    best = ref.max(-1)
    out = {"served": float((best - ref[np.arange(len(toks)), toks]).max())}
    if control:
        low = arch.logits(cfg, weights, rec["app"], seq, pos, low=True)
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


def check(cell, weights: Dict, sample: List[dict], *,
          control: bool = False, log=print) -> Optional[dict]:
    """Widest gaps over ``sample``; None when it is empty."""
    if not sample:
        return None
    served, ctrl = 0.0, 0.0
    for rec in sample:
        g = widest_gaps(cell, weights, rec, control)
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
