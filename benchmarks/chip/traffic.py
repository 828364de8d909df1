"""One generator for every traffic mix.

A mix is a JSON file ``traffic/<name>.json`` of parameters; nothing else
about it is code.  Keys:

- ``loop``: ``open`` (arrivals on a schedule at ``rate_rps``) or
  ``closed`` (``clients`` callers, each sending its next request as soon as
  its last one is answered);
- ``apps``: ``{"pick": "zipf", "s": 1.0}`` over the configuration's
  ``tenants`` in their listed order, ``{"pick": "even"}``, or
  ``{"pick": "only", "app": "<name>"}``;
- ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}`` (tokens);
- ``warmup_s`` (open loop) or ``warmup_rounds`` (closed loop: until
  every client has had that many requests answered): traffic served in
  set-up before the window, from a seed stream of its own;
  ``drain_cap_s``: how long after the window closes its requests may take
  to finish; ``traced_s``: the profiled stretch of a ``--trace 1`` run.

A closed-loop client replays one fixed request shape: client ``c`` sends
the c-th app, the c-th prompt length and its own output length every
time (outputs paired with prompts by a fixed permutation, the same for
every seed); only the token ids change.  The program compiles one
program per (group size, block-table width) and per prefill (group size,
bucket), so a client whose shapes never change is what lets set-up warm
every shape the window uses.

Every seed gets the same set of sizes, apps and gaps: sizes are the
distribution's quantiles at the midpoints (i + 0.5) / n, gaps those of
the exponential, apps a fixed count per tenant.  The seed picks the
open loop's order and every request's token ids.
"""
from __future__ import annotations

import json
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, List, Sequence

import numpy as np

PHASES = {"warmup": 1, "window": 2, "tail": 3}
_NORMAL = statistics.NormalDist()


@dataclass
class TrafficRequest:
    due: float          # seconds after the phase starts (open loop)
    app: str
    prompt_len: int
    out_len: int
    key: tuple          # seeds this request's token ids


def load_mix(path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    return mix


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's midpoint quantiles, ascending."""
    p = _midpoints(n)
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in p])
        v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif spec["dist"] == "uniform":
        v = np.floor(spec["min"] + p * (spec["max"] - spec["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def app_counts(pick: dict, tenants: Sequence[str], n: int) -> List[str]:
    """``n`` app names in fixed proportions (largest remainder)."""
    if pick["pick"] == "only":
        if pick["app"] not in tenants:
            raise KeyError(f"app {pick['app']!r} is not a tenant")
        return [pick["app"]] * n
    if pick["pick"] == "even":
        w = np.ones(len(tenants))
    elif pick["pick"] == "zipf":
        w = 1.0 / np.arange(1, len(tenants) + 1) ** pick["s"]
    else:
        raise ValueError(f"unknown app pick {pick['pick']!r}")
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [a for a, c in zip(tenants, counts) for _ in range(c)]


def interleave(apps: Sequence[str]) -> List[str]:
    """``apps`` reordered round robin over its distinct names, in order
    of first appearance: a, a, b, b -> a, b, a, b."""
    names = list(dict.fromkeys(apps))
    left = {a: list(apps).count(a) for a in names}
    out: List[str] = []
    while len(out) < len(apps):
        for a in names:
            if left[a]:
                out.append(a)
                left[a] -= 1
    return out


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


def open_schedule(mix: dict, tenants: Sequence[str], *, seed: int,
                  phase: str, seconds: float) -> List[TrafficRequest]:
    """Requests due in one phase of ``seconds`` at ``rate_rps``: exactly
    round(rate x seconds) of them, the gaps summing to ``seconds``."""
    n = max(1, round(mix["rate_rps"] * seconds))
    ph = PHASES[phase]
    rng = _rng(seed, ph, 0)
    gaps = -np.log1p(-_midpoints(n))
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = rng.permutation(lengths(mix["prompt"], n))
    outs = rng.permutation(lengths(mix["output"], n))
    apps = rng.permutation(np.array(app_counts(mix["apps"], tenants, n),
                                    dtype=object))
    return [TrafficRequest(float(due[i]), str(apps[i]), int(prompts[i]),
                           int(outs[i]), (ph, i)) for i in range(n)]


def closed_streams(mix: dict, tenants: Sequence[str]
                   ) -> List[Iterator[TrafficRequest]]:
    """One endless request stream per client, each replaying its own
    fixed (app, prompt length, output length)."""
    C = mix["clients"]
    prompts = lengths(mix["prompt"], C)
    outs = lengths(mix["output"], C)[np.random.default_rng(0).permutation(C)]
    apps = interleave(app_counts(mix["apps"], tenants, C))

    def fixed(c: int) -> Iterator[TrafficRequest]:
        for k in range(10 ** 9):
            yield TrafficRequest(0.0, apps[c], int(prompts[c]),
                                 int(outs[c]), (PHASES["window"], c, k))

    return [fixed(c) for c in range(C)]


def prompt_tokens(req: TrafficRequest, seed: int, vocab: int) -> np.ndarray:
    """Token ids of one request, uniform over the vocabulary."""
    return _rng(seed, 7, *req.key).integers(
        0, vocab, size=req.prompt_len, dtype=np.int64).astype(np.int32)


def longest_context(mix: dict) -> int:
    return int(mix["prompt"]["max"] + mix["output"]["max"])

