"""Plain tiled Pallas GEMM with a tunable config space.

Reference: ``python/triton_dist/kernels/nvidia/gemm.py`` (907 LoC) — persistent
GEMM + ``get_config_space``. TPU redesign: a (bm, bk, bn)-blocked MXU matmul
with fp32 accumulation in VMEM scratch; the grid is (m/bm, n/bn, k/bk) with
the K dimension innermost ("arbitrary" semantics) so each (i, j) accumulates
in-place — XLA/Mosaic double-buffers the HBM→VMEM streams automatically.
Epilogues (bias, gelu/silu, gated-mul) fuse into the same kernel, which is the
TPU analog of the reference fusing swiglu into the GEMM tail
(``kernels/nvidia/swiglu.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime.platform import interpret_mode_default


#: Rows of one Mosaic sublane tile: a block's second-to-last dim must be a
#: multiple of this, or the whole array dim.
SUBLANES = 8


def fit_block(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``want``, preferring lane-aligned
    (multiple-of-128) divisors. ALWAYS a divisor ≤ want (degenerate 1 for
    prime lengths, like the old power-of-two shrink): callers never trip
    divisibility, blocks never exceed the requested VMEM footprint, and
    shrink loops (``fit_block(n, b // 2)``) strictly make progress."""
    b = min(want, n)
    for c in range(b, 0, -1):
        if n % c == 0 and c % 128 == 0:
            return c
    return max(c for c in range(b, 0, -1) if n % c == 0)


@dataclasses.dataclass(frozen=True)
class GemmConfig:
    """One point of the tuning space (reference ``get_config_space``)."""

    block_m: int = 512
    block_n: int = 512
    block_k: int = 512
    # Scoped-VMEM budget for this kernel (None = Mosaic's default 16 MiB).
    # Large row-panel configs need more; the chip has far more physical VMEM.
    vmem_limit_mb: int | None = None

    def key(self) -> str:
        return f"bm{self.block_m}_bn{self.block_n}_bk{self.block_k}"


def get_config_space(max_m: int | None = None) -> list[GemmConfig]:
    """Candidate configs for the autotuner (MXU-aligned tile sizes).

    ``max_m`` caps the M-tile at the problem's M (small-M decode regime);
    the space is never empty — bm=128 survives any cap."""
    space = []
    for bm in (128, 256, 512, 1024):
        for bn in (256, 512, 1024):
            for bk in (512, 1024, 2048):
                if max_m is not None and bm > max(max_m, 128):
                    continue
                space.append(GemmConfig(bm, bn, bk))
    return space


def gemm_config_for(m: int, k: int, n: int, dtype) -> GemmConfig:
    """Trace-time tuned-config lookup (offline ``tools.tune_gemm`` fills the
    cache; reference ``tune.py:175-255``). Falls back to the default tile."""
    import jax

    from triton_dist_tpu.tools.tune import lookup

    hit = lookup(
        "gemm",
        [jax.ShapeDtypeStruct((m, k), dtype), jax.ShapeDtypeStruct((k, n), dtype)],
    )
    return GemmConfig(**hit) if hit else GemmConfig()


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, epilogue):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _():
        out = acc_ref[...]
        if epilogue is not None:
            out = epilogue(out)
        o_ref[...] = out.astype(o_ref.dtype)


def gemm(
    a: jax.Array,  # (m, k)
    b: jax.Array,  # (k, n)
    *,
    config: GemmConfig | None = None,
    out_dtype=None,
    epilogue: Callable[[jax.Array], jax.Array] | None = None,
) -> jax.Array:
    """Tiled MXU matmul ``a @ b`` with optional fused epilogue on the fp32
    accumulator (applied per output tile before the final cast)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    cfg = config or GemmConfig()
    bm, bn, bk = (min(cfg.block_m, m), min(cfg.block_n, n), min(cfg.block_k, k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"gemm shapes ({m},{k})x({k},{n}) not divisible by tile ({bm},{bn},{bk})"
    )
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, epilogue=epilogue),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(
                cfg.vmem_limit_mb * 1024 * 1024 if cfg.vmem_limit_mb else None
            ),
        ),
        interpret=interpret_mode_default(),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * a.dtype.itemsize
            + k * n * b.dtype.itemsize
            + m * n * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
    )(a, b)


def gemm_swiglu(
    x: jax.Array,  # (m, k)
    w_gate: jax.Array,  # (k, n)
    w_up: jax.Array,  # (k, n)
    *,
    config: GemmConfig | None = None,
    out_dtype=None,
) -> jax.Array:
    """Fused gate/up projections + SwiGLU: ``silu(x@w_gate) * (x@w_up)``.

    Reference: ``TP_MLP`` gate_up AG-GEMM + swiglu kernel
    (``layers/nvidia/tp_mlp.py:143-204``, ``kernels/nvidia/swiglu.py``).
    Both matmuls share the A-tile stream; the mul happens on fp32 accumulators.
    """
    m, k = x.shape
    k2, n = w_gate.shape
    assert w_up.shape == (k2, n)
    out_dtype = out_dtype or x.dtype
    cfg = config or GemmConfig()
    bm, bn, bk = (min(cfg.block_m, m), min(cfg.block_n, n), min(cfg.block_k, k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk

    def kernel(a_ref, wg_ref, wu_ref, o_ref, acc_g, acc_u):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_g[...] = jnp.zeros_like(acc_g)
            acc_u[...] = jnp.zeros_like(acc_u)

        a = a_ref[...]
        acc_g[...] += jnp.dot(a, wg_ref[...], preferred_element_type=jnp.float32)
        acc_u[...] += jnp.dot(a, wu_ref[...], preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _():
            o_ref[...] = (jax.nn.silu(acc_g[...]) * acc_u[...]).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(
                cfg.vmem_limit_mb * 1024 * 1024 if cfg.vmem_limit_mb else None
            ),
        ),
        interpret=interpret_mode_default(),
        cost_estimate=pl.CostEstimate(
            flops=4 * m * n * k,
            bytes_accessed=m * k * x.dtype.itemsize
            + 2 * k * n * w_gate.dtype.itemsize
            + m * n * jnp.dtype(out_dtype).itemsize,
            transcendentals=m * n,
        ),
    )(x, w_gate, w_up)
