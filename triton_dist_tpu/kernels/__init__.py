"""Distributed kernel library (reference: ``python/triton_dist/kernels/nvidia``).

Every op comes in two forms:

* ``*_shard`` — operates on the *local shard* inside an enclosing
  ``jax.shard_map`` over the context mesh. This is the composable form used by
  layers/models (the analog of calling a triton_dist kernel from a larger
  program).
* a standalone host wrapper that applies ``shard_map`` + ``jit`` itself,
  mirroring the reference's host-side ops (``ag_gemm``, ``gemm_rs``, ...).

Contexts (``create_*_context``) carry method selection and static config — the
TPU analog of the reference's symmetric-buffer/stream contexts (§2.4); actual
symmetric buffers are materialised by XLA as sharded arrays, so contexts here
are cheap, stateless descriptors.
"""

from triton_dist_tpu.kernels.common_ops import (
    barrier_all_on_device,
    copy_tensor_shard,
)
from triton_dist_tpu.kernels.allgather import (
    AllGatherMethod,
    AllGatherContext,
    create_allgather_context,
    get_auto_all_gather_method,
    all_gather_shard,
    all_gather,
)
from triton_dist_tpu.kernels.reduce_scatter import (
    ReduceScatterContext,
    create_reduce_scatter_context,
    reduce_scatter_shard,
    reduce_scatter,
)
from triton_dist_tpu.kernels.allreduce import (
    AllReduceMethod,
    get_auto_all_reduce_method,
    create_all_reduce_context,
    all_reduce_shard,
    all_reduce,
)
from triton_dist_tpu.kernels.p2p import p2p_put_shard, p2p_send_recv
from triton_dist_tpu.kernels.gemm import (
    GemmConfig,
    get_config_space,
    gemm,
    gemm_swiglu,
)
from triton_dist_tpu.kernels.allgather_gemm import (
    AGGemmMethod,
    AGGemmContext,
    create_ag_gemm_context,
    ag_gemm_2d_shard,
    ag_gemm_shard,
    ag_gemm,
)
from triton_dist_tpu.kernels.gemm_reduce_scatter import (
    GemmRSMethod,
    GemmRSContext,
    create_gemm_rs_context,
    gemm_rs_2d_shard,
    gemm_rs_shard,
    gemm_rs,
    reorder_2d_rows_inner_to_outer_major,
)
from triton_dist_tpu.kernels.gemm_allreduce import (
    GemmARMethod,
    GemmARContext,
    create_gemm_ar_context,
    get_auto_gemm_ar_method,
    gemm_ar_ll_call,
    gemm_ar_shard,
    gemm_ar,
)
from triton_dist_tpu.kernels.allgather import all_gather_2d_shard
from triton_dist_tpu.kernels.ep_a2a import (
    all_to_all_single_shard,
    all_to_all_2d_shard,
    ep_dispatch_shard,
    ep_combine_shard,
    create_all_to_all_context,
    fast_all_to_all,
)
from triton_dist_tpu.kernels.ep_fused import (
    ep_moe_fused_kernel_shard,
    fused_dispatch_mlp_combine_shard,
    fused_dispatch_mlp_shard,
    fused_moe_supported,
)
from triton_dist_tpu.kernels.flash_attn import flash_attention, flash_attention_varlen
from triton_dist_tpu.kernels.flash_decode import flash_decode
from triton_dist_tpu.kernels.gdn import gdn_fwd
from triton_dist_tpu.kernels.lightning_attn import lightning_chunk, lightning_step
from triton_dist_tpu.kernels.block_sparse_attn import bsa_decode, bsa_prefill
from triton_dist_tpu.kernels.memory_ops import copy_tensor, fill
from triton_dist_tpu.kernels.low_latency_a2a import (
    dequantize_fp8,
    ep_moe_ll_shard,
    ll_combine_shard,
    combine_leg_shard,
    ll_dispatch_shard,
    quantize_fp8,
)
from triton_dist_tpu.kernels.ag_attention import (
    ag_attention_supported,
    ag_flash_attention_shard,
)
from triton_dist_tpu.kernels.sp import (
    a2a_gemm_shard,
    gemm_a2a_shard,
    ring_attention_shard,
    ulysses_attention_shard,
    ulysses_o_a2a_gemm_shard,
    ulysses_qkv_gemm_a2a_shard,
)

__all__ = [
    "barrier_all_on_device",
    "copy_tensor_shard",
    "all_to_all_single_shard",
    "all_to_all_2d_shard",
    "ep_dispatch_shard",
    "ep_combine_shard",
    "create_all_to_all_context",
    "fast_all_to_all",
    "ep_moe_fused_kernel_shard",
    "fused_dispatch_mlp_combine_shard",
    "fused_dispatch_mlp_shard",
    "fused_moe_supported",
    "AllGatherMethod",
    "AllGatherContext",
    "create_allgather_context",
    "get_auto_all_gather_method",
    "all_gather_shard",
    "all_gather",
    "ReduceScatterContext",
    "create_reduce_scatter_context",
    "reduce_scatter_shard",
    "reduce_scatter",
    "AllReduceMethod",
    "get_auto_all_reduce_method",
    "create_all_reduce_context",
    "all_reduce_shard",
    "all_reduce",
    "p2p_put_shard",
    "p2p_send_recv",
    "GemmConfig",
    "get_config_space",
    "gemm",
    "gemm_swiglu",
    "AGGemmMethod",
    "AGGemmContext",
    "create_ag_gemm_context",
    "ag_gemm_2d_shard",
    "ag_gemm_shard",
    "ag_gemm",
    "GemmRSMethod",
    "GemmRSContext",
    "create_gemm_rs_context",
    "gemm_rs_2d_shard",
    "reorder_2d_rows_inner_to_outer_major",
    "gemm_rs_shard",
    "gemm_rs",
    "GemmARMethod",
    "GemmARContext",
    "create_gemm_ar_context",
    "get_auto_gemm_ar_method",
    "gemm_ar_ll_call",
    "gemm_ar_shard",
    "gemm_ar",
    "all_gather_2d_shard",
    "flash_attention",
    "flash_attention_varlen",
    "flash_decode",
    "gdn_fwd",
    "lightning_chunk",
    "lightning_step",
    "bsa_prefill",
    "bsa_decode",
    "copy_tensor",
    "fill",
    "quantize_fp8",
    "dequantize_fp8",
    "ll_dispatch_shard",
    "ll_combine_shard",
    "combine_leg_shard",
    "ep_moe_ll_shard",
    "a2a_gemm_shard",
    "gemm_a2a_shard",
    "ag_attention_supported",
    "ag_flash_attention_shard",
    "ring_attention_shard",
    "ulysses_attention_shard",
    "ulysses_qkv_gemm_a2a_shard",
    "ulysses_o_a2a_gemm_shard",
]
