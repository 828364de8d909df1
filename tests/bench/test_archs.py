"""The llama architecture module gives what the benchmark gave before its
code moved behind ``archs/``: the same weights, reference and control
logits and reader numbers, recorded from commit 69ea8b56c442 (the llama
code still in ``model.py``, ``reference.py`` and ``arith.py``) on the
CPU, at the test widths of ``tiny_cell``."""
from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import archs, arith, harness, model  # noqa: E402
from benchmarks.chip.metrics import reader  # noqa: E402
from test_bench_harness import SEED, TINY, tiny_cell  # noqa: E402

# per configuration: sha1 of the weight tree; per app, sha1 of the
# reference's and of the float8 control's logits
PARENT = {
    "mistral-7b-v0.3-l8": (
        "aa6b13fb2124b57c79bbd91d9e08b27917d3e454", {
            "base": ("fafddb49d1335406df4dc554b5238198502dcfcf",
                     "1e648bd26b0b093c67af6ccfc2e41daa3a1d0f21"),
            "fpft-0": ("eb12f896c93ad8248e531dbaa37803e2f962fa49",
                       "f77e903bd093bf62eb1afd1702f7abd73d3380f0"),
            "lora-0": ("ae5c4939880a91a2ca153d8c8c4c9d189fc7d8ee",
                       "764d917a245be469e33560eaf668e836df32da05"),
            "adapter-0": ("23f473e518d570d2226510f888e0b3611f600cbd",
                          "1990f7f66401ebb429f837925af8eef84927bb90"),
            "bitfit-0": ("7d86d5c3b7775ed9e20bda0ea74216a1240626dc",
                         "29a1225e9faa1155cc5199dfb06c1c7f6b6c0c5f")}),
    "deepseek-llm-7b-l6": (
        "f1d7fbb8fb730b935c7bda7a38a38a3807e11950", {
            "base": ("5c0b58c9d33572d600fa2834f5f4b1cbd5bf031f",
                     "1873d3712e9c857e41058d384b5c9055ff2ffdcc"),
            "lora-0": ("97f302168ee1854e947b2bc9b4fd053b121db34b",
                       "67821563be0f76217e46a063c90e2859876232f9")}),
}
# step.mfu and paged_attn_roofline on ``hand_run``
PARENT_READS = {
    "mistral-l8.multiapp-closed": (17.055513314883843, 4.68341983581008),
    "deepseek-l6.longdoc-replay": (23.572176866662975, 33.914621400279934),
}


def tiny_config(name: str) -> dict:
    if name == "mistral-7b-v0.3-l8":
        return tiny_cell().cfg
    cfg = model.load_config(name)
    cfg.update(TINY)
    return cfg


def tree_sha1(tree) -> str:
    """sha1 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha1()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def logits_sha1(a) -> str:
    return hashlib.sha1(np.ascontiguousarray(
        np.asarray(a, np.float32)).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT))
def test_weights_are_the_parents(name):
    cfg = tiny_config(name)
    arch = archs.load(cfg["model_type"])
    assert tree_sha1(arch.make_weights(cfg, SEED)) == PARENT[name][0]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_reference_and_control_logits_are_the_parents(name):
    cfg = tiny_config(name)
    arch = archs.load(cfg["model_type"])
    weights = arch.make_weights(cfg, SEED)
    seq = np.random.default_rng(7).integers(0, 256, 40).astype(np.int32)
    pos = np.array([0, 13, 39])
    got = {t["name"]: tuple(
        logits_sha1(arch.logits(cfg, weights, t["name"], seq, pos, low=low))
        for low in (False, True)) for t in cfg["tenants"]}
    assert got == PARENT[name][1]


def hand_run(cell_name: str, lanes) -> harness.RunData:
    """Six steps, five of them inside the traced stretch, and one prompt
    per lane, the first before the window."""
    cell = harness.load_cell(cell_name, ROOT / "BENCHMARK.json")
    run = harness.RunData(cell=cell, seconds=45.0, w0=100.0, w1=145.0)
    run.peaks = arith.peaks("TPU v5 lite")
    for i in range(6):
        t0 = 100.0 + 0.25 * i
        run.steps.append({"t0": t0, "t1": t0 + 0.0173 * (i + 1),
                          "lanes": [(a, kv + 7 * i, h) for a, kv, h in lanes]})
    for rid, (a, kv, _) in enumerate(lanes):
        run.records[rid] = {"app": a, "prompt_len": kv + 1,
                            "t_first": 99.5 + 2.0 * rid}
    run.trace = {"kernel_s": {"paged_attention": 0.0123},
                 "host_window": (100.2, 101.3)}
    return run


@pytest.mark.parametrize("cell_name, lanes", [
    ("mistral-l8.multiapp-closed",
     [("base", 127, 8), ("fpft-0", 2045, 8), ("lora-1", 300, 8),
      ("adapter-2", 999, 8), ("bitfit-0", 16, 8)]),
    ("deepseek-l6.longdoc-replay",
     [("base", 2143, 6), ("base", 3000, 6), ("lora-0", 3487, 6)])])
def test_readers_read_the_parents_numbers(cell_name, lanes):
    run = hand_run(cell_name, lanes)
    got = (reader("step.mfu")(run), reader("paged_attn_roofline")(run))
    assert got == PARENT_READS[cell_name]
