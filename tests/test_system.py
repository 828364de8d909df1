"""End-to-end behaviour tests for BlockLLM: offline zoo -> online serving
-> evaluation metrics, exercising the whole public API surface."""
import jax
import pytest
from jax.sharding import AxisType

from repro.configs import SHAPES, get_config, get_reduced_config, list_configs


def test_all_assigned_archs_registered():
    expected = {
        "qwen2-vl-7b", "mixtral-8x22b", "dbrx-132b", "stablelm-12b",
        "tinyllama-1.1b", "qwen1.5-32b", "qwen2-72b", "zamba2-2.7b",
        "xlstm-125m", "seamless-m4t-medium",
    }
    assert expected <= set(list_configs())
    # exact published numbers spot-check
    c = get_config("qwen2-72b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (80, 8192, 64, 8, 29568, 152064)
    c = get_config("dbrx-132b")
    assert (c.num_experts, c.num_experts_per_tok) == (16, 4)
    assert SHAPES["long_500k"].seq_len == 524288


def test_long_context_applicability():
    runs = {a for a in list_configs()
            if get_config(a).supports_long_context}
    assert {"mixtral-8x22b", "zamba2-2.7b", "xlstm-125m"} <= runs
    assert "qwen2-72b" not in runs  # pure full attention: skipped


@pytest.mark.slow
def test_offline_to_online_lifecycle(tmp_path):
    """train (few steps) -> register into zoo -> partition -> serve with the
    real engine -> evaluate with the cluster scheduler."""
    from repro.core import peft
    from repro.core.zoo import BlockZoo
    from repro.data.pipeline import DataConfig
    from repro.serving.engine import BlockEngine
    from repro.training.train_loop import TrainConfig, train

    cfg = get_reduced_config("blockllm-demo")
    out = train(cfg, TrainConfig(steps=5, ckpt_dir=str(tmp_path / "ck")),
                DataConfig(vocab_size=cfg.vocab_size, global_batch=4,
                           seq_len=16))
    zoo = BlockZoo()
    zoo.register_foundation("base", cfg, out["params"])
    zoo.register_peft("tenant-a", cfg, "base", "lora",
                      peft.create_lora(cfg, jax.random.PRNGKey(1), rank=4))
    assert zoo.redundancy_fraction() > 0.3

    engine = BlockEngine(zoo)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab_size)
    res = engine.generate(zoo.chains["tenant-a"], prompts, gen_len=3)
    assert res.tokens.shape == (2, 3)

    from repro.serving.request import generate_trace
    from repro.serving.simulator import (
        SchedulerConfig,
        Simulation,
        build_serving_config,
    )

    scfg = build_serving_config(n_apps=8, mode="blockllm")
    trace = generate_trace(list(scfg.chains), total_requests=60,
                           duration_s=120, seed=0)
    m = Simulation(scfg, SchedulerConfig()).run(trace)
    assert m["completed"] == 60
    assert m["p95_latency"] > 0 and m["throughput_tokens_s"] > 0


@pytest.mark.slow
def test_dryrun_cell_on_tiny_mesh():
    """The dry-run machinery itself (build_cell + shardings) lowers and
    compiles on this host's 1-device mesh with a reduced config."""
    from repro.launch.hlo_analysis import cost_analysis_dict
    from repro.launch.steps import build_cell

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_reduced_config("tinyllama-1.1b")
    shape = SHAPES["train_4k"]
    shape = type(shape)("tiny_train", 32, 2, "train")
    fn, structs, in_sh, out_sh, donate = build_cell(cfg, shape, mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*structs).compile()
    # newer JAX returns a list of per-module dicts; the helper normalizes
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_hlo_analyzer_invariants():
    from repro.launch.hlo_analysis import _type_bytes

    assert _type_bytes("f32[8,16]{1,0}") == 512
    assert _type_bytes("bf16[2,2]") == 8
    assert _type_bytes("(s32[], f32[4])") == 4 + 16
    assert _type_bytes("pred[]") == 1
