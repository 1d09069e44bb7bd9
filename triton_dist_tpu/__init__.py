"""triton_dist_tpu — a TPU-native distributed-kernel framework.

A from-scratch re-design (NOT a port) of the capabilities of Triton-distributed
(ByteDance-Seed) for TPUs on top of JAX / XLA / Pallas:

* ``triton_dist_tpu.shmem``    — symmetric-memory + one-sided put/get/signal layer
  over Pallas remote DMA and ICI semaphores (the NVSHMEM-equivalent; reference:
  ``shmem/nvshmem_bind`` and ``python/triton_dist/utils.py:169-260``).
* ``triton_dist_tpu.language`` — the ``tpl`` device language: ``rank`` /
  ``num_ranks`` / ``wait`` / ``notify`` / ``consume_token`` / put-with-signal
  primitives usable inside Pallas kernels (reference:
  ``python/triton_dist/language/distributed_ops.py:57-111``).
* ``triton_dist_tpu.kernels``  — distributed kernel library: collectives built
  from one-sided primitives, and compute–communication-overlapped fused ops
  (AG-GEMM, GEMM-RS, GEMM-AR, MoE EP all-to-all, distributed flash-decode,
  sequence-parallel attention; reference: ``python/triton_dist/kernels/nvidia``).
* ``triton_dist_tpu.layers``   — TP / PP / EP / SP model layers
  (reference: ``python/triton_dist/layers/nvidia``).
* ``triton_dist_tpu.models``   — Qwen3-class dense + MoE models and a
  jit-compiled inference engine (reference: ``python/triton_dist/models``).
* ``triton_dist_tpu.tools``    — autotuner, tune cache, profiler, perf models,
  AOT export (reference: ``python/triton_dist/{autotuner,tune}.py``, ``tools/``).

Everything is designed TPU-first: SPMD over ``jax.sharding.Mesh``, collectives
riding ICI, Pallas kernels feeding the MXU, static shapes, functional APIs.
"""

from triton_dist_tpu.version import __version__

from triton_dist_tpu.runtime.mesh import (
    DistContext,
    initialize_distributed,
    finalize_distributed,
    get_default_context,
)
from triton_dist_tpu.runtime import utils

__all__ = [
    "__version__",
    "DistContext",
    "initialize_distributed",
    "finalize_distributed",
    "get_default_context",
    "utils",
]
