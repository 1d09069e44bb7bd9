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


@dataclasses.dataclass(frozen=True)
class SparseLinearConfig:
    """A decoder that mixes attention layers with linear-recurrence layers
    (``models/sparse_linear.py``), in muP form. The defaults are a toy whose
    contexts of 80-200 tokens select; the published shapes are
    ``benchmark/configs/minicpm-sala-d12.json``'s (block-sparse and
    lightning layers) and ``benchmark/configs/granite-4.0-h-micro.json``'s
    (Mamba-2 and full attention layers).

    ``mixer_types``: a layer is ``"sparse"``, ``"lightning"``, ``"mamba2"``
    or ``"full"``.
    ``published_layers`` is the depth under the residual's root
    (``scale_depth / sqrt(published_layers)``) whatever depth is held.
    Sparse layers: ``num_q_heads`` query heads over ``num_kv_heads`` K/V
    heads of ``head_dim``, no rotation; a pooled key is the mean of
    ``kernel_size`` keys every ``kernel_stride``; a query group attends
    ``topk`` blocks of ``block_size`` positions: the first ``init_blocks``,
    the ``window_size // block_size`` ending at its own, the rest by score.
    Lightning layers: ``lightning_heads`` heads of ``lightning_head_dim``,
    rotated. Full layers: grouped-query attention over every position before
    a query, the same heads as the sparse layers', no rotation, no norm on q
    or k. Mamba-2 layers: ``mamba_heads`` heads of ``mamba_head_dim``
    channels, one group of ``B`` and ``C`` of ``mamba_state``, a causal conv
    of ``mamba_conv`` taps, the chunked form over sub-chunks of
    ``mamba_chunk`` rows. ``max_len`` is the longest sequence a slot holds:
    the extent of a slot's pooled keys (``max_len // kernel_stride`` of
    them).

    The muP numbers: ``h0 = scale_emb E[token]``; a residual branch times
    ``residual_multiplier``, the logits times ``logit_multiplier``, a full
    layer's scores times ``attention_multiplier``; each left None is
    MiniCPM's (``scale_depth / sqrt(published_layers)``, ``dim_model_base /
    hidden_size``, ``head_dim ** -0.5``). ``tie_embeddings``: the head is
    the embedding's transpose. ``embed_std``: the seeded embedding's scale."""

    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    mixer_types: tuple = ("sparse", "lightning", "lightning", "sparse")
    published_layers: int = 32
    num_q_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    lightning_heads: int = 4
    lightning_head_dim: int = 16
    kernel_size: int = 4
    kernel_stride: int = 2
    block_size: int = 8
    topk: int = 4
    init_blocks: int = 1
    window_size: int = 16
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 4
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    max_len: int = 256
    dtype: str = "float32"
    mamba_heads: int = 4
    mamba_head_dim: int = 32
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_chunk: int = 16
    residual_multiplier: float | None = None
    logit_multiplier: float | None = None
    attention_multiplier: float | None = None
    tie_embeddings: bool = False
    embed_std: float = 0.02

    def __post_init__(self):
        assert set(self.mixer_types) <= {"sparse", "lightning", "mamba2", "full"}
        assert self.mixer_types
        # one pool of K/V rows: a model's attention layers are of one kind
        assert not {"sparse", "full"} <= set(self.mixer_types)
        assert self.num_q_heads % self.num_kv_heads == 0
        assert self.kernel_size % self.kernel_stride == 0
        assert self.block_size % self.kernel_stride == 0
        assert self.window_size % self.block_size == 0
        assert self.init_blocks + self.window_size // self.block_size <= self.topk
        assert self.lightning_head_dim % 2 == 0

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        if self.residual_multiplier is not None:
            return self.residual_multiplier
        return self.scale_depth / self.published_layers ** 0.5

    @property
    def logit_scale(self) -> float:
        if self.logit_multiplier is not None:
            return self.logit_multiplier
        return self.dim_model_base / self.hidden_size

    @property
    def attention_scale(self) -> float:
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        return self.head_dim ** -0.5

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def pooled_extent(self) -> int:
        return self.max_len // self.kernel_stride

    def layers_of(self, kind: str) -> tuple:
        return tuple(l for l, k in enumerate(self.mixer_types) if k == kind)


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
