#!/usr/bin/env python3
"""Read the comparison's two sides on the chip: the widest logit gap of
what the program served, and that of the float8 control put in its place,
on several seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,3 --seconds <s>

Each seed is a normal run of the cell (a short window at the cell's own
load), then the reference and its float8 control over the same sample,
each judged by the same ``judge`` that decides a run's ``correct``.  The
benchmark's own runs never run the control.  One JSON line per seed on
stdout."""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def read_seed(cell, seed: int, seconds: float, attn_impl: str = "pallas",
              log=print) -> dict:
    """One run of the cell, judged twice by the benchmark's own ``judge``:
    with the program's served gap, and with the float8 control's gap in
    its place."""
    from benchmarks.chip import harness, reference
    from benchmarks.chip.run import judge, sample

    run, weights, engine = harness.run_cell(
        cell, seed=seed, seconds=seconds, attn_impl=attn_impl, log=log)
    harness.free(engine)
    del engine
    picked = sample(cell, run, seed)
    t0 = time.perf_counter()
    got = reference.check(cell, weights, picked, control=True, log=log)
    got = got or {"tokens": 0, "served": None, "control": None}
    correct, _ = judge(cell, run, got["served"])
    control_correct, compared = judge(cell, run, got["control"])
    for k, c in compared.items():
        log(f"control compared {k}: {c['value']} (limit {c['limit']})")
    return {"seed": seed, "due": run.attempted, "done": len(run.done),
            "requests": len(picked),
            "apps": sorted({r["app"] for r in picked}),
            "tokens": got["tokens"], "reference_s": time.perf_counter() - t0,
            "served": got["served"], "correct": correct,
            "control": got["control"], "control_correct": control_correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import harness
    from benchmarks.chip.run import _log, enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT / "BENCHMARK.json")
    if jax.devices()[0].platform != "tpu":
        _log("needs a TPU")
        return 3
    enable_compile_cache()
    for s in args.seeds.split(","):
        print(json.dumps(read_seed(cell, int(s), args.seconds, log=_log)),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
