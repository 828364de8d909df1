"""One module per architecture, ``<model_type>.py``, found by the
configuration file's ``model_type``.  Everything that reads a model's
shape keys lives in its module; the harness, the reference check and
the metric readers call these, each taking the configuration ``cfg``:

- ``make_weights(cfg, seed)``: every tenant's weights, in one jitted call
  on the device, from the seed, in the dtype they are served in;
- ``build_zoo(cfg, weights, log)``: the program's ``BlockZoo`` with every
  tenant registered;
- ``logits(cfg, weights, app, seq, positions, low=False)``: the plain
  float32 reference of ``app``'s chain at ``Precision.HIGHEST``,
  teacher-forced over ``seq``, at ``positions``; with ``low`` its float8
  control;
- ``decode_token_flops(cfg, app, contexts)`` and ``prefill_flops(cfg,
  app, prompt_len)``: the model FLOPs ``step.mfu`` counts for a decoded
  token (``contexts``: its context in each attention hop) and a prompt;
- ``decode_attn_work(cfg, context)``: ``(flops, bytes)`` of one query
  token in one attention hop over ``context`` tokens, as
  ``paged_attn_roofline`` counts it."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(model_type: str, here=HERE):
    """The module ``<here>/<model_type>.py``; a missing file is an error
    that names it."""
    path = Path(here) / f"{model_type}.py"
    if not path.is_file():
        raise KeyError(f"no architecture module {path} for model_type "
                       f"{model_type!r}")
    return _load(str(path.resolve()))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    # one module object per file, so its jitted functions keep their caches
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.archs.{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
