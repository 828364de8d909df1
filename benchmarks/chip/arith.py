"""The benchmark's own arithmetic: device peaks, percentiles, latency
definitions, rooflines, and the definitions of model FLOPs that the
architecture modules (``archs/``) fill with their own counts.

Nothing here imports the program: a later change to the program cannot
move the yardstick."""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence

# Published peaks per chip, keyed by JAX's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (bf16 197 TFLOP/s, HBM 16 GB at 819 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default method); None for an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def ttft_s(rec: dict) -> float:
    """Due time to first token back on the host."""
    return rec["t_first"] - rec["t_due"]


def tpot_s(rec: dict) -> Optional[float]:
    """(result time - first-token time) / (outputs - 1); None for a
    one-token request."""
    n = rec["n_out"]
    if n < 2:
        return None
    return (rec["t_done"] - rec["t_first"]) / (n - 1)


def tokens_in_window(rec: dict, w0: float, w1: float) -> float:
    """Output tokens of one request that fell inside [w0, w1).  The first
    token lands at ``t_first``; the rest are spread evenly up to
    ``t_done``, since decoded tokens reach the host in batches."""
    n = rec["n_out"]
    if n == 0 or rec.get("t_done") is None:
        return 0.0
    t0, t1 = rec["t_first"], rec["t_done"]
    first = 1.0 if w0 <= t0 < w1 else 0.0
    if n == 1 or t1 <= t0:
        return first + (n - 1 if w0 <= t1 < w1 else 0.0)
    lo, hi = max(t0, w0), min(t1, w1)
    rest = (n - 1) * max(0.0, hi - lo) / (t1 - t0)
    return first + rest


# -- the model's work ---------------------------------------------------------


def roofline_s(flops: float, nbytes: float, pk: Dict[str, float]):
    """(least time, bound) for ``flops`` and ``nbytes`` on a chip."""
    tc, tm = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_token_flops(chain_matmul_params: int,
                       attn_flops: Iterable[float]) -> float:
    """Model FLOPs of one decoded token: 2 x the matmul parameters of its
    chain plus attention over its context in each attention layer
    (``attn_flops`` lists the attention FLOPs per attention layer)."""
    return 2.0 * chain_matmul_params + sum(attn_flops)


def prefill_flops(chain_matmul_params: int, head_params: int,
                  prompt_len: int, n_attn: int, pair_flops: float) -> float:
    """Model FLOPs of one prompt: every token through the chain's layers,
    causal attention over the prompt in each attention layer (``pair_flops``
    for each query and key pair), and the head once, for the last
    position."""
    body = 2.0 * (chain_matmul_params - head_params) * prompt_len
    attn = n_attn * pair_flops * (prompt_len * (prompt_len + 1) / 2)
    return body + attn + 2.0 * head_params
