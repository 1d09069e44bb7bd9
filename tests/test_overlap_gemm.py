"""Overlapped collective-matmul tests (AG-GEMM / GEMM-RS / GEMM-AR).

Parity model: reference ``test/nvidia/test_ag_gemm.py``, ``test_gemm_rs.py``,
``test_gemm_ar.py`` — build the unfused reference (all_gather + matmul etc.)
and assert allclose. Shapes stay small for the CPU-sim substrate
(see conftest note on interpret-mode buffer limits).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels import (
    AGGemmMethod,
    GemmARMethod,
    GemmRSMethod,
    ag_gemm_shard,
    gemm_ar_shard,
    gemm_rs_shard,
)

WORLD = 8


def shard(ctx, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    )


@pytest.mark.parametrize(
    "method",
    [AGGemmMethod.XLA_RING, AGGemmMethod.PALLAS_FUSED, AGGemmMethod.XLA_AG_THEN_GEMM],
)
def test_ag_gemm_shard(ctx8, rng, method):
    m_shard, k, n = 8, 64, 128  # full A: (64, 64); B col-shard: (64, 16)
    a = jnp.asarray(rng.standard_normal((WORLD * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, WORLD * 16)), jnp.float32)

    f = shard(
        ctx8,
        lambda a_s, b_s: ag_gemm_shard(a_s, b_s, axis="tp", method=method),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    out = np.asarray(f(a, b))
    expect = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_ag_gemm_return_gathered(ctx8, rng):
    m_shard, k = 8, 64
    a = jnp.asarray(rng.standard_normal((WORLD * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, WORLD * 16)), jnp.float32)

    def fn(a_s, b_s):
        out, ag = ag_gemm_shard(
            a_s, b_s, axis="tp", method=AGGemmMethod.XLA_RING, return_gathered=True
        )
        return out, ag

    f = shard(ctx8, fn, (P("tp"), P(None, "tp")), (P(None, "tp"), P()))
    out, ag = f(a, b)
    np.testing.assert_allclose(np.asarray(ag), np.asarray(a), rtol=0, atol=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a) @ np.asarray(b), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize(
    "method",
    [GemmRSMethod.XLA_RING, GemmRSMethod.PALLAS_FUSED, GemmRSMethod.PALLAS, GemmRSMethod.XLA],
)
def test_gemm_rs_shard(ctx8, rng, method):
    m, k, n = 32, 8 * 32, 128  # K sharded: each rank (32, 32) @ .. -> rows 4
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    f = shard(
        ctx8,
        lambda a_s, b_s: gemm_rs_shard(a_s, b_s, axis="tp", method=method),
        (P(None, "tp"), P("tp")),
        P("tp"),
    )
    out = np.asarray(f(a, b))
    expect = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_gemm_rs_fused_tiled(ctx8, rng):
    """Multi-tile fused GEMM-RS: chunk Mt=2, Nt=2, Kt=2 so tile→send-buffer
    DMAs, slot reuse, and credit backpressure all engage."""
    from triton_dist_tpu.kernels.gemm import GemmConfig

    m, k, n = 8 * 16, 8 * 16, 32  # chunk = 16 rows/rank
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    f = shard(
        ctx8,
        lambda a_s, b_s: gemm_rs_shard(
            a_s, b_s, axis="tp", method=GemmRSMethod.PALLAS_FUSED,
            gemm_config=GemmConfig(block_m=8, block_n=16, block_k=8),
        ),
        (P(None, "tp"), P("tp")),
        P("tp"),
    )
    out = np.asarray(f(a, b))
    expect = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "method",
    [GemmARMethod.RS_AG, GemmARMethod.ONE_SHOT, GemmARMethod.XLA,
     GemmARMethod.PALLAS_FUSED, GemmARMethod.LL_ONE_SHOT],
)
def test_gemm_ar_shard(ctx8, rng, method):
    m, k, n = 16, 8 * 32, 128
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    f = shard(
        ctx8,
        lambda a_s, b_s: gemm_ar_shard(a_s, b_s, axis="tp", method=method)[None],
        (P(None, "tp"), P("tp")),
        P("tp"),
    )
    out = np.asarray(f(a, b))
    expect = np.asarray(a) @ np.asarray(b)
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-4, err_msg=f"rank {r}")


@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
@pytest.mark.parametrize("shape", ["square", "tiny_m"])
@pytest.mark.parametrize(
    "method", [GemmARMethod.PALLAS_FUSED, GemmARMethod.LL_ONE_SHOT]
)
def test_gemm_ar_matches_dot_psum(request, rng, ctx_name, world, shape, method):
    """fp32-accum parity vs ``dot + psum`` computed INSIDE the same
    shard_map, at world 4 and 8, square and tiny-M shapes. ll_one_shot
    keeps fp32 partials on the wire and reduces in rank order 0..w-1 —
    the same order the psum reference uses — so it must be EXACT. The
    fused ring starts each chunk's accumulation at a rotated rank
    (chunk c sums c+1, c+2, ..., c), so its fp32 sum can differ from the
    reference by a few ulps of the LARGEST partial sum (|partials| reach ~30
    here, ulp 1.9e-6, while the result may cancel to ~1) — nothing looser."""
    ctx = request.getfixturevalue(ctx_name)
    m, n = (32, 32) if shape == "square" else (8, 64)
    k = world * 16
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def fn(a_s, b_s):
        ref = jax.lax.psum(
            jax.lax.dot_general(
                a_s, b_s, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
            "tp",
        ).astype(a_s.dtype)
        out = gemm_ar_shard(a_s, b_s, axis="tp", method=method)
        return out[None], ref[None]

    f = shard(ctx, fn, (P(None, "tp"), P("tp")), (P("tp"), P("tp")))
    out, ref = f(a, b)
    out, ref = np.asarray(out), np.asarray(ref)
    for r in range(world):
        if method is GemmARMethod.LL_ONE_SHOT:
            np.testing.assert_array_equal(out[r], ref[r], err_msg=f"rank {r}")
        else:
            np.testing.assert_allclose(out[r], ref[r], rtol=1e-6, atol=1e-5,
                                       err_msg=f"rank {r}")


@pytest.mark.parametrize("ctx_name,world,m", [("ctx8", 8, 12), ("ctx4", 4, 6)])
def test_gemm_ar_ll_ragged_m(request, rng, ctx_name, world, m):
    """Ragged decode M (not divisible by world — the shape that forces AUTO
    off the fused ring): the ll kernel carries full-M panels so any row
    count works, and stays exact vs the fp32-accum dot+psum reference."""
    ctx = request.getfixturevalue(ctx_name)
    k, n = world * 16, 64
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def fn(a_s, b_s):
        ref = jax.lax.psum(
            jax.lax.dot_general(
                a_s, b_s, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
            "tp",
        ).astype(a_s.dtype)
        # AUTO must route the ragged shape here (ll_one_shot) by itself.
        out = gemm_ar_shard(a_s, b_s, axis="tp", method=GemmARMethod.AUTO)
        return out[None], ref[None]

    f = shard(ctx, fn, (P(None, "tp"), P("tp")), (P("tp"), P("tp")))
    out, ref = f(a, b)
    out, ref = np.asarray(out), np.asarray(ref)
    for r in range(world):
        np.testing.assert_array_equal(out[r], ref[r], err_msg=f"rank {r}")


def test_gemm_ar_fused_tiled(ctx8, rng):
    """Multi-tile fused GEMM-AR: Mt=2, Nt=2, Kt=2 per ring step so the
    tile→send-buffer DMAs, output-tile staging, RS slot reuse + credit
    backpressure, AND the AG broadcast ring all engage (the GEMM-AR analog
    of test_gemm_rs_fused_tiled)."""
    from triton_dist_tpu.kernels.gemm import GemmConfig

    m, k, n = 8 * 16, 8 * 16, 32  # chunk = 16 rows/rank
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    f = shard(
        ctx8,
        lambda a_s, b_s: gemm_ar_shard(
            a_s, b_s, axis="tp", method=GemmARMethod.PALLAS_FUSED,
            gemm_config=GemmConfig(block_m=8, block_n=16, block_k=8),
        )[None],
        (P(None, "tp"), P("tp")),
        P("tp"),
    )
    out = np.asarray(f(a, b))
    expect = np.asarray(a) @ np.asarray(b)
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-4,
                                   err_msg=f"rank {r}")


def test_gemm_ar_auto_routing():
    """AUTO's M/world crossover (pure trace-time routing, no devices):
    decode-sized M takes the low-latency one-shot kernel, large M the
    fused RS+AG ring when its row chunks are sublane-aligned, else XLA. Uses the static default
    crossover (cold tune cache)."""
    from triton_dist_tpu.kernels.gemm_allreduce import (
        DEFAULT_GEMM_AR_CROSSOVER_M,
        get_auto_gemm_ar_method,
    )

    for world in (4, 8):
        # Decode shapes: tiny M, at/below the crossover.
        assert get_auto_gemm_ar_method(8, world) is GemmARMethod.LL_ONE_SHOT
        assert (get_auto_gemm_ar_method(DEFAULT_GEMM_AR_CROSSOVER_M, world)
                is GemmARMethod.LL_ONE_SHOT)
        # Prefill-sized M above the crossover: the fused ring.
        assert get_auto_gemm_ar_method(4096, world) is GemmARMethod.PALLAS_FUSED
        # Large ragged M (a prompt of arbitrary length): the ring can't
        # chunk it into sublane-aligned row blocks and the one-shot kernel
        # would hold all of it in VMEM — dot + psum.
        assert get_auto_gemm_ar_method(4096 + 1, world) is GemmARMethod.XLA
        assert get_auto_gemm_ar_method(1500, world) is GemmARMethod.XLA
        # Ragged decode M stays on the one-shot kernel.
        assert get_auto_gemm_ar_method(6, world) is GemmARMethod.LL_ONE_SHOT


def test_ag_gemm_pallas_tiled(ctx8, rng):
    """Multi-tile grid through the fused kernel: per-shard M, N, K all larger
    than the tile so Mt=2, Nt=2, Kt=2 — exercises the panel double-buffering,
    B/out streaming, and per-chunk arrival waits at prefill-like structure
    (tiny absolute sizes per the interpret-substrate ceiling)."""
    from triton_dist_tpu.kernels.gemm import GemmConfig

    m_shard, k, n_shard = 16, 32, 32
    a = jnp.asarray(rng.standard_normal((WORLD * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, WORLD * n_shard)), jnp.float32)

    f = shard(
        ctx8,
        lambda a_s, b_s: ag_gemm_shard(
            a_s, b_s, axis="tp", method=AGGemmMethod.PALLAS_FUSED,
            config=GemmConfig(block_m=8, block_n=16, block_k=16),
        ),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    out = np.asarray(f(a, b))
    expect = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_ag_gemm_bf16_pallas(ctx8, rng):
    """bf16 wire/compute dtype through the fused kernel (MXU dtype)."""
    m_shard, k = 8, 64
    a = jnp.asarray(rng.standard_normal((WORLD * m_shard, k)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((k, WORLD * 16)), jnp.bfloat16)

    f = shard(
        ctx8,
        lambda a_s, b_s: ag_gemm_shard(a_s, b_s, axis="tp", method=AGGemmMethod.PALLAS_FUSED),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    out = np.asarray(f(a, b), np.float32)
    expect = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    np.testing.assert_allclose(out, expect, rtol=5e-2, atol=5e-1)


# ------------------------------------------------- DCN-aware 2D hierarchy


def test_ag_gemm_2d_shard(ctx24, rng):
    """Hierarchical AG-GEMM on a (2,4) mesh: DCN XLA gather + fused ICI
    ring (reference inter-node AG-GEMM, allgather.py:387-489). Output rows
    must come back in outer-major global order."""
    from triton_dist_tpu.kernels import AGGemmMethod, ag_gemm_2d_shard

    wo, wi = 2, 4
    m_shard, k, n_shard = 4, 32, 16
    a = jnp.asarray(rng.standard_normal((wo * wi * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, wo * wi * n_shard)), jnp.float32)

    for method in (AGGemmMethod.PALLAS_FUSED, AGGemmMethod.XLA_RING):
        f = jax.jit(
            jax.shard_map(
                lambda a_s, b_s: ag_gemm_2d_shard(
                    a_s, b_s, axes=("dp", "tp"), method=method
                ),
                mesh=ctx24.mesh,
                in_specs=(P(("dp", "tp")), P(None, ("dp", "tp"))),
                out_specs=P(None, ("dp", "tp")),
                check_vma=False,
            )
        )
        out = np.asarray(f(a, b))
        expect = np.asarray(a) @ np.asarray(b)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4,
                                   err_msg=str(method))


def test_gemm_rs_2d_shard(ctx24, rng):
    """Hierarchical GEMM-RS on a (2,4) mesh: fused ICI ring + one DCN
    reduce-scatter (reference 2D reduce_scatter context,
    reduce_scatter.py:472-640). Row-block layout: rank (d, i) holds global
    block i*wo + d."""
    from triton_dist_tpu.kernels import GemmRSMethod, gemm_rs_2d_shard

    wo, wi = 2, 4
    world = wo * wi
    m, k, n = world * 4, world * 8, 16
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    for method in (GemmRSMethod.PALLAS_FUSED, GemmRSMethod.XLA_RING):
        f = jax.jit(
            jax.shard_map(
                lambda a_s, b_s: gemm_rs_2d_shard(
                    a_s, b_s, axes=("dp", "tp"), method=method
                )[None],
                mesh=ctx24.mesh,
                in_specs=(P(None, ("dp", "tp")), P(("dp", "tp"))),
                out_specs=P(("dp", "tp")),
                check_vma=False,
            )
        )
        out = np.asarray(f(a, b))  # (world, m/world, n) stacked per rank
        expect = np.asarray(a) @ np.asarray(b)
        rows = m // world
        for d in range(wo):
            for i in range(wi):
                rank = d * wi + i  # mesh order: dp-major
                blk = i * wo + d  # layout: inner-major then outer
                np.testing.assert_allclose(
                    out[rank], expect[blk * rows : (blk + 1) * rows],
                    rtol=1e-4, atol=1e-4, err_msg=f"rank ({d},{i}) {method}",
                )


def test_gemm_rs_2d_reorder_to_outer_major(ctx24, rng):
    """reorder_2d_rows_inner_to_outer_major fixes the 2D GEMM-RS layout
    hazard (r3 advisor): after the permute, assembling under
    out_specs=P(("dp","tp")) yields exactly A @ B in global row order."""
    from triton_dist_tpu.kernels import (
        GemmRSMethod, gemm_rs_2d_shard, reorder_2d_rows_inner_to_outer_major,
    )

    wo, wi = 2, 4
    world = wo * wi
    m, k, n = world * 4, world * 8, 16
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    f = jax.jit(
        jax.shard_map(
            lambda a_s, b_s: reorder_2d_rows_inner_to_outer_major(
                gemm_rs_2d_shard(
                    a_s, b_s, axes=("dp", "tp"),
                    method=GemmRSMethod.XLA_RING,
                ),
                axes=("dp", "tp"),
            ),
            mesh=ctx24.mesh,
            in_specs=(P(None, ("dp", "tp")), P(("dp", "tp"))),
            out_specs=P(("dp", "tp")),
            check_vma=False,
        )
    )
    np.testing.assert_allclose(
        np.asarray(f(a, b)), np.asarray(a) @ np.asarray(b),
        rtol=1e-4, atol=1e-4,
    )


# ==================================================== prefill overlap v2
#
# The fused double-buffered AG-GEMM (+SwiGLU epilogue) and fused GEMM-RS vs
# the XLA references, the tuned AUTO routing, and the ragged/tiny-M coverage.

from triton_dist_tpu.kernels.allgather_gemm import ag_gemm_swiglu_shard


def _swiglu_ref(a, wg, wu):
    g = np.asarray(a, np.float32) @ np.asarray(wg, np.float32)
    u = np.asarray(a, np.float32) @ np.asarray(wu, np.float32)
    return g / (1.0 + np.exp(-g)) * u


@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
@pytest.mark.parametrize(
    "method",
    [AGGemmMethod.XLA_RING, AGGemmMethod.XLA_AG_THEN_GEMM,
     AGGemmMethod.PALLAS_FUSED],
)
def test_ag_gemm_swiglu_parity(request, rng, ctx_name, world, method):
    """``silu(AG(x) @ w_gate) * (AG(x) @ w_up)`` across all three routes at
    world 4 and 8 — the XLA ring and ag-then-gemm compositions are the
    references the one-kernel SwiGLU epilogue must match."""
    ctx = request.getfixturevalue(ctx_name)
    m_shard, k, n_shard = 8, 64, 16
    x = jnp.asarray(rng.standard_normal((world * m_shard, k)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((k, world * n_shard)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((k, world * n_shard)), jnp.float32)

    f = shard(
        ctx,
        lambda x_s, g_s, u_s: ag_gemm_swiglu_shard(
            x_s, g_s, u_s, axis="tp", method=method),
        (P("tp"), P(None, "tp"), P(None, "tp")),
        P(None, "tp"),
    )
    np.testing.assert_allclose(
        np.asarray(f(x, wg, wu)), _swiglu_ref(x, wg, wu), rtol=1e-4, atol=1e-4
    )


def test_ag_gemm_swiglu_fused_tiled(ctx8, rng):
    """Multi-tile SwiGLU epilogue (Mt=2, Nt=2, Kt=2): both weight operands
    stream through the same double-buffered ring pass, the gate/up fp32
    accumulators live side by side, and the epilogue fires once per output
    tile on the last K step."""
    from triton_dist_tpu.kernels.gemm import GemmConfig

    m_shard, k, n_shard = 16, 32, 32
    x = jnp.asarray(rng.standard_normal((WORLD * m_shard, k)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((k, WORLD * n_shard)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((k, WORLD * n_shard)), jnp.float32)

    f = shard(
        ctx8,
        lambda x_s, g_s, u_s: ag_gemm_swiglu_shard(
            x_s, g_s, u_s, axis="tp", method=AGGemmMethod.PALLAS_FUSED,
            config=GemmConfig(block_m=8, block_n=16, block_k=16)),
        (P("tp"), P(None, "tp"), P(None, "tp")),
        P(None, "tp"),
    )
    np.testing.assert_allclose(
        np.asarray(f(x, wg, wu)), _swiglu_ref(x, wg, wu), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
@pytest.mark.parametrize("m_shard", [8, 6])  # tiny and ragged-odd shards
def test_ag_gemm_auto_tiny_ragged_m(request, rng, ctx_name, world, m_shard):
    """Tiny / ragged local M shards: AUTO must route below the crossover to
    the XLA ring (which carries ANY row count — no divisibility demand) and
    stay exact vs the all_gather + dot reference, at world 4 and 8."""
    ctx = request.getfixturevalue(ctx_name)
    k, n_shard = 64, 16
    a = jnp.asarray(rng.standard_normal((world * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, world * n_shard)), jnp.float32)

    f = shard(
        ctx,
        lambda a_s, b_s: ag_gemm_shard(a_s, b_s, axis="tp",
                                       method=AGGemmMethod.AUTO),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    np.testing.assert_allclose(
        np.asarray(f(a, b)), np.asarray(a) @ np.asarray(b),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
def test_ag_gemm_fused_parity_worlds(request, rng, ctx_name, world):
    """The double-buffered fused kernel vs the plain dot reference at both
    world sizes (ctx8 coverage exists piecemeal above; this pins the pair
    the acceptance bar names)."""
    ctx = request.getfixturevalue(ctx_name)
    m_shard, k, n_shard = 8, 64, 16
    a = jnp.asarray(rng.standard_normal((world * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, world * n_shard)), jnp.float32)

    f = shard(
        ctx,
        lambda a_s, b_s: ag_gemm_shard(a_s, b_s, axis="tp",
                                       method=AGGemmMethod.PALLAS_FUSED),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    np.testing.assert_allclose(
        np.asarray(f(a, b)), np.asarray(a) @ np.asarray(b),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
def test_gemm_rs_fused_parity_worlds(request, rng, ctx_name, world):
    """Fused tile-streaming GEMM-RS vs the dot + psum_scatter reference
    computed inside the same shard_map, at world 4 and 8."""
    ctx = request.getfixturevalue(ctx_name)
    m, k, n = world * 8, world * 16, 32
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def fn(a_s, b_s):
        ref = jax.lax.psum_scatter(
            jax.lax.dot_general(
                a_s, b_s, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
            "tp", scatter_dimension=0, tiled=True,
        ).astype(a_s.dtype)
        out = gemm_rs_shard(a_s, b_s, axis="tp",
                            method=GemmRSMethod.PALLAS_FUSED)
        return out, ref

    f = shard(ctx, fn, (P(None, "tp"), P("tp")), (P("tp"), P("tp")))
    out, ref = f(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ag_gemm_auto_routing():
    """AUTO's m_shard crossover for AG-GEMM (pure trace-time routing, no
    devices): decode-sized shards at/below the tuned threshold ride the XLA
    ring; prefill-sized shards above it take the fused double-buffered
    kernel; shapes with no VMEM-fitting tiling fall back to the ring no
    matter how large. Uses the static default crossover (cold tune cache)."""
    from triton_dist_tpu.kernels.allgather_gemm import (
        DEFAULT_AG_GEMM_CROSSOVER_M,
        get_auto_ag_gemm_method,
    )
    from triton_dist_tpu.runtime import telemetry

    for world in (4, 8):
        assert (get_auto_ag_gemm_method(8, 64, 64, jnp.float32, world)
                is AGGemmMethod.XLA_RING)
        assert (get_auto_ag_gemm_method(
                    DEFAULT_AG_GEMM_CROSSOVER_M, 64, 64, jnp.float32, world)
                is AGGemmMethod.XLA_RING)
        assert (get_auto_ag_gemm_method(256, 64, 64, jnp.float32, world)
                is AGGemmMethod.PALLAS_FUSED)
        # The SwiGLU pair (two weight operands sharing the ring) routes too.
        assert (get_auto_ag_gemm_method(256, 64, 64, jnp.float32, world,
                                        n_mats=2)
                is AGGemmMethod.PALLAS_FUSED)
        # No VMEM-fitting tiling (panel scratch alone overflows the budget):
        # the ring regardless of M.
        assert (get_auto_ag_gemm_method(256, 1 << 20, 128, jnp.float32, world)
                is AGGemmMethod.XLA_RING)
    # Every resolution ticks the routing counter series.
    assert telemetry.counter_value(
        "tdt_kernels_auto_route_total", collective="ag_gemm",
        method=AGGemmMethod.PALLAS_FUSED.value,
    ) >= 1.0


def test_gemm_rs_auto_routing():
    """AUTO's M crossover for GEMM-RS (pure trace-time routing, no devices):
    small M and ragged M (the fused ring chunks rows over ranks) ride the
    XLA ring; large divisible M takes the fused tile-streaming kernel."""
    from triton_dist_tpu.kernels.gemm_reduce_scatter import (
        DEFAULT_GEMM_RS_CROSSOVER_M,
        get_auto_gemm_rs_method,
    )
    from triton_dist_tpu.runtime import telemetry

    for world in (4, 8):
        assert get_auto_gemm_rs_method(64, world) is GemmRSMethod.XLA_RING
        assert (get_auto_gemm_rs_method(DEFAULT_GEMM_RS_CROSSOVER_M, world)
                is GemmRSMethod.XLA_RING)
        assert get_auto_gemm_rs_method(2048, world) is GemmRSMethod.PALLAS_FUSED
        # Ragged M can't chunk over ranks — the ring regardless of size.
        assert get_auto_gemm_rs_method(2048 + 1, world) is GemmRSMethod.XLA_RING
    assert telemetry.counter_value(
        "tdt_kernels_auto_route_total", collective="gemm_rs",
        method=GemmRSMethod.PALLAS_FUSED.value,
    ) >= 1.0
