"""Demo zoo shared by the launcher, examples, benchmarks, the chip smoke
check and tests: one foundation, one FPFT variant (divergent layer with an
adaptive equivalence edge) and PEFT variants over the foundation."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def build_demo_zoo(seed: int = 0, *, arch: str = "blockllm-demo",
                   peft_kinds=("lora",)):
    """Returns (cfg, zoo) with apps: base, vicuna, app-<peft>...

    Weights are random from ``seed`` at ``arch``'s full width.  The zoo
    holds one copy of them: the stacked init tree is dropped once the
    foundation is sliced into blocks, and the FPFT variant is built from
    those blocks, so its only new weights are its one divergent layer."""
    from repro.configs import get_config
    from repro.core import peft
    from repro.core.zoo import BlockZoo
    from repro.models.model import build_model

    cfg = get_config(arch)
    zoo = BlockZoo()
    base = zoo.register_foundation(
        "base", cfg, build_model(cfg).init(jax.random.PRNGKey(seed)))
    # FPFT variant: perturb one layer enough to stay its own block but keep
    # an adaptive-serving equivalence edge (cos ~ 1 - sigma^2/2)
    blocks = [zoo.blocks[s.block_id].params for s in base.steps]
    layers = blocks[1:-1]
    layers[1] = jax.tree.map(
        lambda x: x + 0.15 * jnp.std(x) * jax.random.normal(
            jax.random.PRNGKey(seed + 1), x.shape, x.dtype), layers[1])
    zoo.register_fpft("vicuna", cfg, {**blocks[0], "layers": layers,
                                      **blocks[-1]}, "base")
    makers = {"lora": peft.create_lora, "adapter": peft.create_adapter,
              "bitfit": peft.create_bitfit}
    for i, kind in enumerate(peft_kinds):
        zoo.register_peft(f"app-{kind}", cfg, "base", kind,
                          makers[kind](cfg, jax.random.PRNGKey(seed + 2 + i)))
    return cfg, zoo
