"""The DeepSeek-V2 architecture module and its cell on the CPU: the run
loop at test widths, its float8 control, the work counts at published
widths by hand, and the cell's three readers on a hand-built run."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import arith, control, harness, model, xplane  # noqa: E402
from benchmarks.chip import run as bench_run  # noqa: E402
from benchmarks.chip.archs import deepseek_v2 as arch  # noqa: E402
from benchmarks.chip.metrics import reader  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "dsv2lite-l7.multiapp-replay"
SEED = 2 ** 40 + 29
# test widths under the published router (64 experts, top-6, 2 shared).
# A router of 8 experts top-2 gives the expert at the top-k edge a weight
# near 0.2, so where bf16 rounding moves a token across that edge its
# logits move by up to 0.3 (measured on the CPU; the float32 program reads
# no gap at all); at 64 top-6 the edge weight is near 0.03, as at full
# width, and the widest gap stays under 0.1.
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_rope_head_dim=16,
            qk_nope_head_dim=16, v_head_dim=16, moe_intermediate_size=16,
            num_hidden_layers=3, vocab_size=256)
QUIET = lambda m: None  # noqa: E731


def tiny_cell() -> harness.Cell:
    """The cell at test widths and lengths: 8 clients, 2 an app, in
    lockstep rounds."""
    cell = harness.load_cell(CELL, ROOT / "BENCHMARK.json")
    cell.cfg.update(TINY)
    cell.cfg["engine"] = dict(max_active=8, max_block_batch=4, page_size=16,
                              num_pages=200)
    cell.mix.update(clients=8, warmup_rounds=1)
    cell.mix["prompt"].update(min=20, max=40)
    cell.mix["output"].update(min=6, max=6)
    return cell


def test_cell_loop_runs_in_lockstep_and_is_correct():
    cell = tiny_cell()
    out = bench_run.measure(cell, seed=SEED, seconds=2.0, trace=False,
                            bench=BENCH, device=jax.devices()[0],
                            attn_impl="ref", log=QUIET)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "ttft_p90_s", "tpot_p50_ms",
                                   "tpot_p90_ms", "out_tok_s"}


def test_the_float8_control_is_not_correct():
    cell = tiny_cell()
    got = [control.read_seed(cell, s, 2.0, attn_impl="ref", log=QUIET)
           for s in (1, 2, 3)]
    limit = cell.cfg["check"]["widest_logit_gap"]
    assert all(g["correct"] and g["served"] <= limit for g in got)
    assert max(g["control"] for g in got) > limit
    assert not all(g["control_correct"] for g in got)


def test_the_module_refuses_a_program_without_the_layer(monkeypatch):
    """On a checkout whose program has no DeepSeek-V2 layer the cell fails
    at load, before any weights are made."""
    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda n, *a: (
        None if n == "repro.models.deepseek_v2" else real(n, *a)))
    path = ROOT / "benchmarks/chip/archs/deepseek_v2.py"
    spec = importlib.util.spec_from_file_location("dsv2_probe", path)
    with pytest.raises(ImportError, match="DeepSeek-V2"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


def test_weights_and_work_at_published_widths_by_hand():
    """DeepSeek-V2-Lite, layers 0-6: an MoE layer holds 64 x 3 x 2048 x
    1408 = 553.6 M routed, 2 x 3 x 2048 x 1408 = 17.3 M shared and 13.8 M
    attention parameters (q 2048 x 16 x 192, latent 2048 x 576, kv_b
    512 x 16 x 256, o 16 x 128 x 2048) beside its router and norms; the
    chain holds 4.01 B."""
    cfg = model.load_config("deepseek-v2-lite-l7")
    shapes = jax.eval_shape(arch._init_fn(json.dumps(cfg, sort_keys=True)),
                            jax.random.PRNGKey(0))
    layer = shapes["base"]["layers"][1]
    n = {k: int(np.prod(v.shape)) for k, v in layer.items()}
    assert n["e_gate"] + n["e_up"] + n["e_down"] == 553_648_128
    assert n["shared_gate"] + n["shared_up"] + n["shared_down"] == 17_301_504
    assert arch.attn_params(cfg) == 13_762_560 == sum(
        n[k] for k in ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj"))
    total = sum(int(np.prod(x.shape)) for x in
                jax.tree.leaves(shapes["base"]))
    assert round(total / 1e9, 2) == 4.01
    # a token through the base chain: 7 attentions, the dense layer, 6 x
    # (router + shared + 6 experts) and the head
    base = {"name": "base", "kind": "foundation"}
    assert arch.chain_matmul_params(cfg, base) == (
        7 * 13_762_560 + 3 * 2048 * 10944
        + 6 * (2048 * 64 + 17_301_504 + 6 * 8_650_752) + 2048 * 102400)
    lora = {"name": "lora-0", "kind": "lora"}
    assert arch.chain_matmul_params(cfg, lora) - arch.chain_matmul_params(
        cfg, base) == 7 * 8 * (2 * 2048 + 16 * 192 + 576)
    # one query over 1000 latent rows: 2 x 16 x (576 + 512) x 1000 FLOPs;
    # the rows (1,152 B each) plus q (16 x 576) and out (16 x 512), bf16
    assert arch.decode_attn_work(cfg, 1000) == (
        2 * 16 * 1088 * 1000, float(1152 * 1000 + 2 * 16 * 1088))
    assert arch.prefill_flops(cfg, "base", 2) == arith.prefill_flops(
        arch.chain_matmul_params(cfg, base), 2048 * 102400, 2, 7,
        2 * 16 * (192 + 128))


def hand_run(lanes) -> harness.RunData:
    """Six steps of four 8-lane megasteps, five inside the traced
    stretch, with the engine's routing counters over the window and a
    profile of two decode megasteps."""
    cell = harness.load_cell(CELL, ROOT / "BENCHMARK.json")
    run = harness.RunData(cell=cell, seconds=45.0, w0=100.0, w1=145.0)
    run.peaks = arith.peaks("TPU v5 lite")
    for i in range(6):
        t0 = 100.0 + 0.25 * i
        run.steps.append({"t0": t0, "t1": t0 + 0.04,
                          "lanes": [(a, kv + i, 7) for a, kv in lanes]})
    run.counters = {
        "start": {"moe_decode_pairs": 1000.0, "moe_decode_experts_hit": 500.0,
                  "moe_decode_layer_calls": 20.0},
        "end": {"moe_decode_pairs": 1000.0 + 48 * 240,
                "moe_decode_experts_hit": 500.0 + 35 * 240,
                "moe_decode_layer_calls": 20.0 + 240}}
    run.trace = {"file": "hand.xplane.pb", "host_window": (100.2, 101.3)}
    return run


PLANES = {
    xplane.HOST_PLANE: {"python3": [("bench.engine_step", 10.0, 1.0)]},
    "/device:TPU:0": {
        "XLA Modules": [("jit_chain_decode(11)", 10.1, 0.01),
                        ("jit_chain_prefill(12)", 10.3, 0.1),
                        ("jit_chain_decode(11)", 10.5, 0.01)],
        "XLA Ops": [("%gmm.1 = bf16[48,1408]", 10.101, 0.002),
                    ("%mla_paged_attention.3 = bf16[8,16,512]", 10.104,
                     0.001),
                    ("%gmm.2 = bf16[48,2048]", 10.106, 0.002),
                    ("%gmm.7 = bf16[98304,1408]", 10.31, 0.05),  # prefill
                    ("%gmm.1 = bf16[48,1408]", 10.501, 0.003),
                    ("%mla_paged_attention.3 = bf16[8,16,512]", 10.505,
                     0.002)]},
}


def test_the_cells_readers_on_a_hand_built_run(monkeypatch):
    monkeypatch.setattr(xplane, "load", lambda path: PLANES)
    lanes = [(a, kv) for a in ("base", "lora-0", "lora-1", "lora-2")
             for kv in (1300, 1500, 1700, 1900, 2000, 2040, 1400, 1600)]
    run = hand_run(lanes)
    cfg = run.cell.cfg
    # 48 pairs over 35 experts a megastep layer
    assert reader("moe.tokens_per_expert")(run) == pytest.approx(48 / 35)
    # steps 1-5 lie inside the stretch: 5 x 4 megasteps x 6 expert layers,
    # 5 x 32 lanes; the grouped matmuls inside decode megasteps: 7 ms
    flops = 2.0 * 160 * 6 * 6 * 3 * 2048 * 1408
    nbytes = 2.0 * (20 * 6 * 35 * 3 * 2048 * 1408
                    + 160 * 6 * 6 * 3 * (2048 + 1408))
    least = max(flops / 197e12, nbytes / 819e9)
    assert reader("moe.gmm_roofline")(run) == pytest.approx(
        100 * least / 0.007)
    f = b = 0.0
    for s in run.steps[1:]:
        for _, kv, hops in s["lanes"]:
            ff, bb = arch.decode_attn_work(cfg, kv + 1)
            f, b = f + hops * ff, b + hops * bb
    assert reader("mla.attn_roofline")(run) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 0.003)
    # a build without the counters, or a run without a profile, reads
    # nothing
    run.counters = {"start": {"decode_tokens": 1.0},
                    "end": {"decode_tokens": 2.0}}
    assert reader("moe.tokens_per_expert")(run) is None
    assert reader("moe.gmm_roofline")(run) is None
    run.trace = None
    assert reader("mla.attn_roofline")(run) is None
