"""The chip smoke check's phases at demo width on the CPU (interpret-mode
kernel), and its refusal to report success off a TPU."""
import importlib.util
import json
from pathlib import Path

import pytest


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_phases_at_demo_width(chip_smoke):
    lines = []
    report = chip_smoke.run(arch="blockllm-demo", attn_impl="interpret",
                            n_requests=3, prompt_lens=(8, 40), gen_len=4,
                            log=lines.append)
    assert report["requests"] == 3 and report["tokens"] == 12
    assert report["worst_tv"] <= chip_smoke.TV_TOL
    assert any("per-hop fallback entered 0 times" in ln for ln in lines)
    json.dumps(report)  # plain numbers only


def test_reference_rejects_wrong_probs(chip_smoke):
    """The float32 reference is discriminating: another request's
    distribution is far outside the tolerance."""
    from repro.serving.demo import build_demo_zoo
    from repro.serving.engine import BlockEngine, EngineConfig

    cfg, zoo = build_demo_zoo(seed=0)
    engine = BlockEngine(zoo, max_len=48, config=EngineConfig(attn_impl="ref"))
    reqs = chip_smoke.make_requests(cfg, ["base"], n=2, prompt_lens=(8, 40),
                                    gen_len=4, seed=0)
    for r in reqs:
        engine.submit(r)
    results = sorted(engine.drain(), key=lambda r: r.rid)
    chip_smoke.check_reference(zoo, cfg, reqs, results, max_len=48,
                               log=lambda m: None)
    results[0].probs_last = results[1].probs_last
    with pytest.raises(AssertionError, match="TV"):
        chip_smoke.check_reference(zoo, cfg, reqs, results, max_len=48,
                                   log=lambda m: None)
