"""Model configuration (reference ``python/triton_dist/models/config.py``).

One consolidated dataclass for the Qwen3-class dense + MoE families the
reference ships (``DenseLLM``/``Qwen3MoE``), plus the runtime knobs the
engine needs. Values default to a small test model; ``presets`` carries the
published shapes.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 1024
    hidden_size: int = 256
    intermediate_size: int = 512
    num_layers: int = 2
    num_q_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 64
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    tie_word_embeddings: bool = False
    # MoE (None → dense MLP)
    num_experts: int | None = None
    top_k: int = 8
    moe_intermediate_size: int | None = None
    norm_topk_prob: bool = True

    @property
    def is_moe(self) -> bool:
        return self.num_experts is not None


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    """A decoder of five kinds of layer (``models/hybrid_ssm.py``): Mamba-1
    and sliding-window attention alternating in the first half, then one
    full-attention layer whose K/V the cross-attention layers above it
    read, and gated memory units that read the last Mamba layer's scan.
    Defaults are the tiny preset of the CPU tests: every kind present, the
    window shorter than the prompts."""

    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 8
    num_q_heads: int = 8
    num_kv_heads: int = 4
    sliding_window: int = 8
    mb_per_layer: int = 2
    d_state: int = 8
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 4
    layer_norm_eps: float = 1e-5
    dtype: str = "float32"

    def __post_init__(self):
        assert self.mb_per_layer == 2 and self.num_layers % 4 == 0 and self.num_layers >= 8
        assert self.hidden_size % self.num_q_heads == 0
        assert self.num_kv_heads % 2 == 0 and self.num_q_heads % self.num_kv_heads == 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_q_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    def layer_kind(self, layer: int) -> str:
        """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``. The second
        half is the cross-decoder: its first layer is the last Mamba layer
        (which also hands on its scan, ``m``), its second the one
        full-attention layer (which also hands on its K/V); from the third
        on the mixers borrow."""
        half = self.num_layers // 2
        if layer % self.mb_per_layer == 0:
            return "mamba" if layer <= half else "gmu"
        if layer < half:
            return "window"
        return "full" if layer == half + 1 else "cross"

    def layers_of(self, kind: str) -> tuple:
        return tuple(l for l in range(self.num_layers) if self.layer_kind(l) == kind)


PRESETS: dict[str, ModelConfig] = {
    # Qwen3-8B/32B-style dense shapes (reference e2e targets, e2e_dense.md)
    "qwen3-8b": ModelConfig(
        vocab_size=151936, hidden_size=4096, intermediate_size=12288,
        num_layers=36, num_q_heads=32, num_kv_heads=8, head_dim=128,
    ),
    "qwen3-32b": ModelConfig(
        vocab_size=151936, hidden_size=5120, intermediate_size=25600,
        num_layers=64, num_q_heads=64, num_kv_heads=8, head_dim=128,
    ),
    # Qwen3-30B-A3B-style MoE (reference qwen_moe.py target family)
    "qwen3-moe-30b-a3b": ModelConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_layers=48, num_q_heads=32, num_kv_heads=4, head_dim=128,
        num_experts=128, top_k=8, moe_intermediate_size=768,
    ),
    # Tiny configs for tests / CPU sim
    "test-dense": ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_q_heads=8, num_kv_heads=4, head_dim=32, dtype="float32",
    ),
    "test-moe": ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_q_heads=8, num_kv_heads=4, head_dim=32, dtype="float32",
        num_experts=8, top_k=2, moe_intermediate_size=48,
    ),
}

HYBRID_SSM_PRESETS: dict[str, HybridSSMConfig] = {
    # Phi-4-mini-flash-reasoning's shapes (benchmark/configs/phi-4-mini-flash.json)
    "phi4flash": HybridSSMConfig(
        vocab_size=200064, hidden_size=2560, intermediate_size=10240, num_layers=32,
        num_q_heads=40, num_kv_heads=20, sliding_window=512, d_state=16, d_conv=4,
        expand=2, dt_rank=160, dtype="bfloat16",
    ),
    "test-hybrid-ssm": HybridSSMConfig(),
}
