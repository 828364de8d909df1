#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
BENCHMARK.json.  With ``--trace 0`` the result's metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiled stretch of the window.  The last line of stdout is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(traced) ``breakdown``, and last ``compared``: each number checked, with
its limit.  Off a TPU, or with fewer chips than the cell asks for, it
exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

CACHE = ROOT / ".bench_cache"


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def selected(bench: dict, key: str, cell: str) -> list:
    """Names of ``bench[key]`` metrics this cell reports."""
    return [m["name"] for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def sample(cell, run, seed: int) -> list:
    """The finished requests the reference reads, drawn from the seed."""
    from benchmarks.chip import reference

    kinds = {t["name"]: t["kind"] for t in cell.cfg["tenants"]}
    ok = [r for r in run.done if r["n_out"] == r["out_len"]]
    return reference.pick_sample(ok, seed, cell.cfg["check"]["sample_tokens"],
                                 kinds)


def judge(cell, run, gap) -> tuple:
    """``(correct, compared)`` of a run whose sample read the widest logit
    gap ``gap`` (None: nothing finished to compare)."""
    limit = cell.cfg["check"]["widest_logit_gap"]
    compared = {
        "widest_logit_gap": {"value": gap, "limit": limit},
        "unfinished": {"value": run.attempted - len(run.done), "limit": 0},
        "wrong_length": {"value": sum(r["n_out"] != r["out_len"]
                                      for r in run.done), "limit": 0},
    }
    correct = gap is not None and all(
        c["value"] <= c["limit"] for c in compared.values())
    return bool(correct), compared


def measure(cell, *, seed: int, seconds: float, trace: bool, bench: dict,
            device, attn_impl: str = "pallas", t_start=None,
            log=_log) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax

    from benchmarks.chip import arith, harness, reference
    from benchmarks.chip.metrics import reader

    pk = arith.peaks(device.device_kind) if device.platform == "tpu" else None
    trace_dir = CACHE / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run, weights, engine = harness.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace, attn_impl=attn_impl,
        trace_dir=trace_dir, t_start=t_start, log=log)
    run.peaks = pk
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    harness.free(engine)
    del engine

    t0 = time.perf_counter()
    got = reference.check(cell, weights, sample(cell, run, seed), log=log)
    log(f"reference in {time.perf_counter() - t0:.1f} s")
    correct, compared = judge(cell, run, got["served"] if got else None)
    key = "per_layer" if trace else "end_to_end"
    metrics = {}
    units = {m["name"]: m["unit"] for m in bench[key]}
    for name in selected(bench, key, cell.name):
        v = reader(name)(run)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": compared["unfinished"]["value"]
           + compared["wrong_length"]["value"], "metrics": metrics,
           "device": dev}
    if trace and run.trace:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["compared"] = compared
    for k, c in compared.items():
        log(f"compared {k}: {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(args.workload, ROOT / "BENCHMARK.json")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        _log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
             f"{devices[0].platform} device(s)")
        return 3
    _log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
         f"{enable_compile_cache()}")
    out = measure(cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), bench=bench, device=devices[0],
                  t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
