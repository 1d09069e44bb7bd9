"""Models package: Qwen3-class dense + MoE, engine, HF weight loading.

Reference: ``python/triton_dist/models/__init__.py:33-60`` (``AutoLLM``
loading HF checkpoints into the TP layout).
"""

from triton_dist_tpu.models.config import (
    HYBRID_SSM_PRESETS,
    HybridSSMConfig,
    ModelConfig,
    PRESETS,
    SparseLinearConfig,
)
from triton_dist_tpu.models.kv_cache import CacheRow, KVCache, PagedKVCache, kv_rows
from triton_dist_tpu.models.dense import DenseLLM, Qwen3MoE, DenseParams, init_params
from triton_dist_tpu.models.moe import EPMoELLM, ep_specs
from triton_dist_tpu.models.latent_sparse import LatentSparseConfig, LatentSparseLLM
from triton_dist_tpu.models.hybrid_ssm import HybridSSMLLM
from triton_dist_tpu.models.sparse_linear import SparseLinearLLM
from triton_dist_tpu.models.engine import Engine
from triton_dist_tpu.models.drafter import (
    Drafter,
    GDNDrafter,
    ScriptedDrafter,
    TruncatedDrafter,
)
from triton_dist_tpu.models.weights import AutoLLM, load_hf_weights
from triton_dist_tpu.models import checkpoint

__all__ = [
    "ModelConfig",
    "PRESETS",
    "CacheRow",
    "KVCache",
    "PagedKVCache",
    "kv_rows",
    "DenseLLM",
    "Qwen3MoE",
    "EPMoELLM",
    "LatentSparseConfig",
    "LatentSparseLLM",
    "HybridSSMConfig",
    "HYBRID_SSM_PRESETS",
    "HybridSSMLLM",
    "SparseLinearConfig",
    "SparseLinearLLM",
    "ep_specs",
    "DenseParams",
    "init_params",
    "Engine",
    "Drafter",
    "TruncatedDrafter",
    "GDNDrafter",
    "ScriptedDrafter",
    "AutoLLM",
    "checkpoint",
    "load_hf_weights",
]
