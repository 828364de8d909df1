"""One reader per metric, ``<metric name>.py``, each with ``read(run)``
returning the metric's value or None when the run holds nothing for it.
The harness finds a reader by the metric's name in BENCHMARK.json."""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def reader(name: str, here=HERE):
    path = Path(here) / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
