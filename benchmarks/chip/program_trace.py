"""Reduce a profiler trace to the program's own units: device time per
named program, and the device's idle time put down to the program span
the host was in.

Every jitted program of the serving path is named, so its module on the
device plane's ``XLA Modules`` line reads ``jit_<name>(<hash>)``.  The
program's spans (``engine.*``, ``sched.*``, ``kv.*``, ``exec.*``, written
with ``jax.profiler.TraceAnnotation``) sit on the host plane beside the
run loop's ``bench.*`` spans, on the same clock.  The traced stretch has
the edges ``xplane.reduce`` gives it: the first to the last ``bench.*``
span.  A program's device time is the busy time (the union of its
``XLA Ops`` events) inside its module events, so the programs' shares of
busy time sum to at most 1.  Idle time is cut at span edges, and each
piece goes to the innermost span over it: the one that started last.  All
times are in seconds; per-device sums are averaged over the devices."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmarks.chip import xplane

MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("engine.", "sched.", "kv.", "exec.")
NO_SPAN = "no host span"
DECODE = ("jit_chain_decode", "jit_chain_decode_spec")
PREFILL = ("jit_chain_prefill", "jit_block_prefill")
KV_WRITE = ("jit_kv_write_prefill",)

_CACHE: Dict[str, Optional[dict]] = {}


def module_name(event: str) -> str:
    """``jit_chain_decode(1234)`` -> ``jit_chain_decode``."""
    return event.split("(")[0].strip()


def _owners(spans: List[Tuple[str, float, float]], lo: float, hi: float
            ) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut at every span edge, each piece with its innermost
    span's name (the latest start; of equal starts, the earliest end)."""
    edges = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                               if lo < x < hi})
    order = sorted(spans, key=lambda sp: sp[1])
    out, live, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= a:
            live.append(order[i])
            i += 1
        live = [sp for sp in live if sp[2] > a]
        best = max(live, key=lambda sp: (sp[1], -sp[2]), default=None)
        out.append((a, b, best[0] if best else NO_SPAN))
    return out


def reduce(planes: dict) -> dict:
    """``window_s``, ``busy_s``, ``module_s`` (module -> device time inside
    the stretch), ``module_events`` (module -> device time of each of its
    events wholly inside the stretch), ``idle_s`` (innermost span name ->
    device idle time under it) and ``spans`` (program span names
    present)."""
    host = [(n, s, s + d) for evs in planes.get(xplane.HOST_PLANE, {}).values()
            for n, s, d in evs
            if n.startswith(SPAN_PREFIXES + (xplane.HOST_SPAN_PREFIX,))]
    bench = [sp for sp in host if sp[0].startswith(xplane.HOST_SPAN_PREFIX)]
    devices = [p for p in planes if p.startswith(xplane.DEVICE_PREFIX)]
    if not bench or not devices:
        raise ValueError("trace holds no bench.* host spans or no device "
                         f"plane (planes: {sorted(planes)})")
    lo, hi = min(s for _, s, _ in bench), max(e for _, _, e in bench)
    pieces = _owners(host, lo, hi)
    busy = 0.0
    module_s: Dict[str, float] = {}
    module_events: Dict[str, List[float]] = {}
    idle_s: Dict[str, float] = {}
    for dev in devices:
        merged = [(max(s, lo), min(s + d, hi)) for _, s, d in
                  planes[dev].get(xplane.OPS_LINE, []) if s + d > lo and s < hi]
        merged = xplane.union(merged)
        busy += sum(b - a for a, b in merged)
        mods = sorted(planes[dev].get(MODULES_LINE, []), key=lambda e: e[1])
        j = 0
        for n, s, d in mods:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            while j < len(merged) and merged[j][1] <= a:
                j += 1
            t, k = 0.0, j
            while k < len(merged) and merged[k][0] < b:
                t += min(b, merged[k][1]) - max(a, merged[k][0])
                k += 1
            m = module_name(n)
            module_s[m] = module_s.get(m, 0.0) + t
            if s >= lo and s + d <= hi:
                module_events.setdefault(m, []).append(t)
        # idle = the stretch minus busy, walked against the owned pieces
        j = 0
        for a, b, owner in pieces:
            idle = b - a
            while j < len(merged) and merged[j][1] <= a:
                j += 1
            k = j
            while k < len(merged) and merged[k][0] < b:
                idle -= min(b, merged[k][1]) - max(a, merged[k][0])
                k += 1
            if idle > 0:
                idle_s[owner] = idle_s.get(owner, 0.0) + idle
    n = len(devices)
    return {
        "window_s": hi - lo,
        "busy_s": busy / n,
        "module_s": {m: v / n for m, v in module_s.items()},
        "module_events": module_events,
        "idle_s": {k: v / n for k, v in idle_s.items()},
        "spans": sorted({sp[0] for sp in host
                         if sp[0].startswith(SPAN_PREFIXES)}),
    }


def of_run(run) -> Optional[dict]:
    """The reduction of a traced run's profile, loaded once per file;
    None for a run with no profile, or one whose program names none of
    its programs or writes no program span (a build from before them)."""
    path = (run.trace or {}).get("file")
    if not path:
        return None
    if path not in _CACHE:
        r = reduce(xplane.load(path))
        named = any(m in DECODE + PREFILL + KV_WRITE for m in r["module_s"])
        _CACHE[path] = r if named and r["spans"] else None
    return _CACHE[path]


def module_frac(run, modules: Tuple[str, ...]) -> Optional[float]:
    """Device time of ``modules`` over device busy time."""
    r = of_run(run)
    if r is None or not r["busy_s"]:
        return None
    return sum(r["module_s"].get(m, 0.0) for m in modules) / r["busy_s"]


def idle_frac(run, prefix: str) -> Optional[float]:
    """Device idle time whose innermost span starts with ``prefix``, over
    the traced stretch."""
    r = of_run(run)
    if r is None:
        return None
    return sum(v for k, v in r["idle_s"].items()
               if k.startswith(prefix)) / r["window_s"]
