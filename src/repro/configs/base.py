"""Config system: model architecture configs + assigned input shapes.

Every assigned architecture gets a ``ModelConfig`` in ``repro/configs/<id>.py``
with the exact published numbers; ``reduced()`` derives a CPU-smoke-test-sized
variant of the same family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned; identical set for every LM-family arch).
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_impl: str = "dense"  # "dense" (scan all experts) | "dispatch" (capacity EP)
    capacity_factor: float = 1.25
    routed_scaling_factor: float = 1.0  # DeepSeekMoE: routed output scale

    # --- multi-head latent attention (DeepSeek-V2); 0 = not MLA ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN: the HF ``rope_scaling`` items (factor, beta_fast, beta_slow,
    # mscale, mscale_all_dim, original_max_position_embeddings)
    rope_scaling: Tuple[Tuple[str, float], ...] = ()

    # --- attention flavour ---
    sliding_window: int = 0  # 0 = full causal attention
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    mrope_sections: Tuple[int, ...] = ()  # M-RoPE (qwen2-vl): freq sections t/h/w

    # --- hybrid / ssm ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    shared_attn_every: int = 0  # zamba2: shared attention block period

    # --- enc-dec ---
    encoder_layers: int = 0
    decoder_layers: int = 0

    # --- vlm ---
    num_visual_tokens: int = 0  # stub frontend: precomputed patch embeds

    # --- numerics / serving ---
    norm_eps: float = 1e-5
    kv_cache_dtype: str = "bf16"  # "bf16" | "int8"
    attn_chunk: int = 512  # query-chunked reference attention
    supports_long_context: bool = False  # sub-quadratic decode state
    use_flash_kernel: bool = False  # Pallas path (TPU target; off for dry-run)

    # --- §Perf hillclimb knobs (baseline values preserve paper-faithful
    # behaviour; EXPERIMENTS.md §Perf flips them per iteration) ---
    serve_param_dtype: str = "fp32"   # "bf16": cast weights for serving
    decode_2d_params: bool = False    # ZeRO-inference: shard decode weights
    #                                   over data too (weight-gathered)
    moe_decode_gather: bool = False   # decode: gather only top-k experts
    seq_shard_attn: bool = False      # prefill: seq-sharded (ring-style)
    #                                   attention when heads don't divide TP
    vocab_chunk: int = 0              # chunked cross-entropy (train)
    decode_kv_chunk: int = 0          # decode: flash-style KV-block scan

    # documentation
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # Rough parameter counts (for MODEL_FLOPS in the roofline).
    def param_count(self) -> int:
        import math

        from repro.models.model import build_model  # lazy, avoids cycle
        import jax

        model = build_model(self)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts)."""
        total = self.param_count()
        if self.num_experts and self.num_experts_per_tok:
            hd = self.resolved_head_dim
            L = self.num_layers
            expert_params = 3 * self.d_model * self.d_ff  # gate/up/down
            inactive = L * (self.num_experts - self.num_experts_per_tok) * expert_params
            return total - inactive
        return total


_REGISTRY: dict = {}


def register(cfg: ModelConfig, reduced: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = (cfg, reduced)
    return cfg


def get_config(name: str) -> ModelConfig:
    _load_all()
    return _REGISTRY[name][0]


def get_reduced_config(name: str) -> ModelConfig:
    _load_all()
    return _REGISTRY[name][1]


def list_configs() -> list:
    _load_all()
    return sorted(_REGISTRY)


_LOADED = False


def _load_all() -> None:
    global _LOADED
    if _LOADED:
        return
    import importlib

    for mod in (
        "qwen2_vl_7b",
        "mixtral_8x22b",
        "dbrx_132b",
        "stablelm_12b",
        "tinyllama_1_1b",
        "qwen1_5_32b",
        "qwen2_72b",
        "zamba2_2_7b",
        "xlstm_125m",
        "seamless_m4t_medium",
        "blockllm_demo",
    ):
        importlib.import_module(f"repro.configs.{mod}")
    _LOADED = True


def applicable_shapes(cfg: ModelConfig) -> list:
    """Shapes that apply to this arch (long_500k only for sub-quadratic)."""
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and not cfg.supports_long_context:
            continue  # pure full-attention: skip (DESIGN.md §4)
        out.append(s)
    return out
