"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports: busy time, per-operation time, a kernel's time, and
the idle gaps labelled by what the host was doing in them.

Device planes are named ``/device:TPU:<n>`` and their operations sit on
the line ``XLA Ops``.  The host's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation`` by the run loop) sit on the host plane
``/host:CPU``.  All times are in seconds on the trace's clock."""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPAN_PREFIX = "bench."


def load(path) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """plane name -> line name -> [(event name, start s, duration s)]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events)
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(planes: dict, kernels: Tuple[str, ...] = ("paged_attention",),
           top: int = 10) -> dict:
    """Device busy and idle over the traced window (first to last host
    ``bench.*`` span), time per device operation (named by its HLO text up
    to the layout), the summed time of
    each kernel in ``kernels`` (events whose name starts with ``%<kernel>``), and the
    ``top`` longest idle gaps, each named by the host span that overlaps
    it most."""
    host = [(n, s, s + d) for n, s, d in
            (e for evs in planes.get(HOST_PLANE, {}).values() for e in evs)
            if n.startswith(HOST_SPAN_PREFIX)]
    devices = [p for p in planes if p.startswith(DEVICE_PREFIX)]
    if not host or not devices:
        raise ValueError("trace holds no bench.* host spans or no device "
                         f"plane (planes: {sorted(planes)})")
    lo, hi = min(s for _, s, _ in host), max(e for _, _, e in host)
    busy, ops, kern = 0.0, {}, {k: 0.0 for k in kernels}
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        evs = [(n, s, s + d) for n, s, d in planes[dev].get(OPS_LINE, [])
               if s + d > lo and s < hi]
        for n, s, e in evs:
            short = n.split("{")[0].strip()  # "%copy.9 = bf16[8,128]"
            ops[short] = ops.get(short, 0.0) + (e - s)
            for k in kernels:
                if n.startswith(f"%{k}"):
                    kern[k] += e - s
        merged = _clip(union([(s, e) for _, s, e in evs]), lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)

    def label(g):
        best, name = 0.0, "no host span"
        for n, s, e in host:
            ov = min(e, g[1]) - max(s, g[0])
            if ov > best:
                best, name = ov, n
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo,
        "busy_s": busy / n_dev,
        "devices": n_dev,
        "kernel_s": {k: v / n_dev for k, v in kern.items()},
        "device_ops": [[n, v / n_dev] for n, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(g), g[1] - g[0]] for g in gaps[:top]],
    }


def reduce_dir(trace_dir) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = reduce(load(files[-1]))
    out["file"] = str(files[-1])
    return out
