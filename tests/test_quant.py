"""Quantization tests (``models/quant.py`` + the quantized operand paths).

Four tiers, mirroring docs/quantization.md:

* host tier — the exponent-snapped power-of-two quantizer itself: per-row
  round-trip error inside ``ERROR_BOUND``, BITWISE-stable requantization
  (the quantize-once invariant is only meaningful if re-deriving a scale
  from dequantized rows is a no-op), lane-replicated scale layout;
* collective tier (8- and 4-device CPU mesh) — quantized AG-GEMM /
  GEMM-RS / GEMM-AR vs the fp32 oracle built on the DEQUANTIZED operand,
  which isolates the collective path's error (documented per-op bands)
  from the quantization error itself.  Fused/LL routes execute only on
  the TPU interpret substrate and are gated like the bf16 fused tests;
* paged-KV tier — the in-kernel table-walk dequant of
  ``paged_flash_decode`` must be BYTE-identical to the gather-dequant
  oracle (power-of-two scales make f32 dequant exact), and a CoW copy of
  a quantized block moves the (payload, scale) pair verbatim — byte
  stable against a never-shared twin, no scale re-derivation;
* serving tier (world=1, same harness as tests/test_paged_kv.py) —
  fp8/int8-KV greedy token streams byte-identical to the bf16-KV run on
  the pinned parity family (prompts whose argmax margin exceeds the
  quantization band — see bench.py's ``serving_quant``), and prefix-trie
  borrowing across quantized blocks parity vs never-shared twins.
"""

import json
import os

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
from triton_dist_tpu.models.quant import (
    ERROR_BOUND,
    LANES,
    QuantTensor,
    dequantize_kv,
    dequantize_rows,
    dequantize_tensor,
    quantize_kv_rows,
    quantize_rows,
    quantize_tensor,
    wire_dtype,
    wire_itemsize,
)
from triton_dist_tpu.runtime import resilience, telemetry

WIRES = ("int8", "fp8")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    yield
    telemetry.reset()
    resilience.reset_degradation()


# ============================================================ host tier


@pytest.mark.parametrize("wire", WIRES)
def test_roundtrip_error_bound(wire, rng):
    """Per-row relative error of quantize -> dequantize stays inside the
    documented band: 2^-7 for int8, 2^-4 for fp8 (power-of-two scales are
    exact in f32, so the only error is the payload rounding)."""
    x = rng.standard_normal((64, 256)).astype(np.float32)
    # Mixed per-row magnitudes: the scale must adapt row by row.
    x *= np.exp2(rng.integers(-12, 12, size=(64, 1))).astype(np.float32)
    q, scale = quantize_rows(jnp.asarray(x), wire)
    assert q.dtype == wire_dtype(wire)
    back = np.asarray(dequantize_rows(q, scale))
    absmax = np.abs(x).max(axis=1, keepdims=True)
    err = np.abs(back - x)
    assert (err <= ERROR_BOUND[wire] * absmax + 1e-12).all()


@pytest.mark.parametrize("wire", WIRES)
def test_roundtrip_zero_rows_exact(wire):
    x = jnp.zeros((4, 128), jnp.float32)
    q, scale = quantize_rows(x, wire)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(scale), 1.0)
    np.testing.assert_array_equal(np.asarray(dequantize_rows(q, scale)), 0.0)


@pytest.mark.parametrize("wire", WIRES)
def test_requantization_bitwise_stable(wire, rng):
    """quantize(dequantize(quantize(x))) == quantize(x) byte for byte —
    the property that makes quantize-once structural: a re-derived scale
    over already-quantized rows changes nothing, so a CoW copy and a
    donor block can never drift apart."""
    x = jnp.asarray(rng.standard_normal((32, 256)), jnp.float32)
    t1 = quantize_tensor(x, wire)
    t2 = quantize_tensor(dequantize_tensor(t1, jnp.float32), wire)
    np.testing.assert_array_equal(
        np.asarray(t1.q).view(np.uint8), np.asarray(t2.q).view(np.uint8)
    )
    np.testing.assert_array_equal(np.asarray(t1.scale), np.asarray(t2.scale))


@pytest.mark.parametrize("wire", WIRES)
def test_scale_layout(wire, rng):
    """QuantTensor carries a lane-replicated (rows, 128) f32 scale whose
    values are exact powers of two (frexp mantissa 0.5)."""
    x = jnp.asarray(rng.standard_normal((16, 256)), jnp.float32)
    t = quantize_tensor(x, wire)
    assert isinstance(t, QuantTensor)
    assert t.wire == wire
    assert t.shape == x.shape
    assert t.scale.shape == (16, LANES)
    assert t.scale.dtype == jnp.float32
    s = np.asarray(t.scale)
    np.testing.assert_array_equal(s, np.broadcast_to(s[:, :1], s.shape))
    mant, _ = np.frexp(s)
    np.testing.assert_array_equal(mant, 0.5)  # exact powers of two
    assert wire_itemsize(wire) == 1


# ====================================================== collective tier
#
# Oracle discipline (same as the bf16 overlap tests, test_overlap_gemm.py):
# build the unfused reference on the DEQUANTIZED operand so the asserted
# band measures the collective path, not the quantizer. Bands per op are
# the ones documented in docs/quantization.md.


def _shard(ctx, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(
            fn, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )


AG_METHODS = [
    AGGemmMethod.XLA_RING,
    AGGemmMethod.XLA_AG_THEN_GEMM,
    AGGemmMethod.PALLAS_FUSED,
]


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("method", AG_METHODS)
@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
def test_ag_gemm_quant_parity(request, ctx_name, world, method, wire, rng):
    """Quantized AG-GEMM: int8/fp8 payload + (m, 128) scales ride the ring,
    dequant happens in the gather/panel stage, fp32 accumulate."""
    ctx = request.getfixturevalue(ctx_name)
    m_shard, k, n = 8, 64, 128
    a = jnp.asarray(rng.standard_normal((world * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    aq = quantize_tensor(a, wire)
    expect = np.asarray(dequantize_tensor(aq, jnp.float32)) @ np.asarray(b)

    f = _shard(
        ctx,
        lambda a_s, b_s: ag_gemm_shard(a_s, b_s, axis="tp", method=method),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    out = np.asarray(f(aq, b))
    np.testing.assert_allclose(out, expect, rtol=0, atol=1e-3)


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize(
    "method",
    [AGGemmMethod.XLA_RING,
     AGGemmMethod.PALLAS_FUSED],
)
def test_ag_gemm_swiglu_quant_parity(ctx8, method, wire, rng):
    """Quantized AG-GEMM + SwiGLU epilogue: both weight mats consume the
    same dequantized panel."""
    from triton_dist_tpu.kernels.allgather_gemm import ag_gemm_swiglu_shard

    world, m_shard, k, nff = 8, 8, 64, 16
    x = jnp.asarray(rng.standard_normal((world * m_shard, k)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((k, nff * world)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((k, nff * world)), jnp.float32)
    xq = quantize_tensor(x, wire)
    x_deq = np.asarray(dequantize_tensor(xq, jnp.float32))
    expect = np.asarray(
        jax.nn.silu(x_deq @ np.asarray(g)) * (x_deq @ np.asarray(u))
    )

    f = _shard(
        ctx8,
        lambda x_s, g_s, u_s: ag_gemm_swiglu_shard(
            x_s, g_s, u_s, axis="tp", method=method
        ),
        (P("tp"), P(None, "tp"), P(None, "tp")),
        P(None, "tp"),
    )
    out = np.asarray(f(xq, g, u))
    np.testing.assert_allclose(out, expect, rtol=0, atol=1e-2)


RS_METHODS = [
    GemmRSMethod.XLA,
    GemmRSMethod.XLA_RING,
    GemmRSMethod.PALLAS_FUSED,
]


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("method", RS_METHODS)
@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
def test_gemm_rs_quant_parity(request, ctx_name, world, method, wire, rng):
    """Quantized GEMM-RS: the A operand is quantized per-shard inside
    shard_map (the wire itself stays fp32 partials — the win is the
    operand's HBM/VMEM footprint)."""
    ctx = request.getfixturevalue(ctx_name)
    mm, k, n = 8 * world, 32 * world, 48
    a = jnp.asarray(rng.standard_normal((mm, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def fn(a_s, b_s):
        return gemm_rs_shard(quantize_tensor(a_s, wire), b_s,
                             axis="tp", method=method)

    f = _shard(ctx, fn, (P(None, "tp"), P("tp")), P("tp"))
    out = np.asarray(f(a, b))

    expect = np.zeros((mm, n), np.float32)
    for a_s, b_s in zip(np.split(np.asarray(a), world, axis=1),
                        np.split(np.asarray(b), world, axis=0)):
        deq = np.asarray(
            dequantize_tensor(quantize_tensor(jnp.asarray(a_s), wire))
        )
        expect += deq @ b_s
    np.testing.assert_allclose(out, expect, rtol=0, atol=1e-3)


AR_METHODS = [
    GemmARMethod.XLA,
    GemmARMethod.PALLAS_FUSED,
    GemmARMethod.LL_ONE_SHOT,
]


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("method", AR_METHODS)
@pytest.mark.parametrize("ctx_name,world", [("ctx8", 8), ("ctx4", 4)])
def test_gemm_ar_quant_parity(request, ctx_name, world, method, wire, rng):
    ctx = request.getfixturevalue(ctx_name)
    mm, k, n = 16, 32 * world, 48
    a = jnp.asarray(rng.standard_normal((mm, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def fn(a_s, b_s):
        return gemm_ar_shard(quantize_tensor(a_s, wire), b_s,
                             axis="tp", method=method)

    f = _shard(ctx, fn, (P(None, "tp"), P("tp")), P(None, None))
    out = np.asarray(f(a, b))

    expect = np.zeros((mm, n), np.float32)
    for a_s, b_s in zip(np.split(np.asarray(a), world, axis=1),
                        np.split(np.asarray(b), world, axis=0)):
        deq = np.asarray(
            dequantize_tensor(quantize_tensor(jnp.asarray(a_s), wire))
        )
        expect += deq @ b_s
    np.testing.assert_allclose(out, expect, rtol=0, atol=1e-3)


def test_quant_dispatch_telemetry(ctx8, rng):
    """Every world>1 quantized dispatch ticks tdt_quant_ops_total and the
    byte counters; the AG wire counter carries (world-1) ring hops."""
    world, m_shard, k, n = 8, 8, 64, 128
    a = jnp.asarray(rng.standard_normal((world * m_shard, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    aq = quantize_tensor(a, "fp8")
    f = _shard(
        ctx8,
        lambda a_s, b_s: ag_gemm_shard(
            a_s, b_s, axis="tp", method=AGGemmMethod.XLA_RING
        ),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )
    f(aq, b)
    assert telemetry.counter_value(
        "tdt_quant_ops_total", collective="ag_gemm", wire="fp8"
    ) >= 1.0
    per_rank = m_shard * k * 1 + m_shard * 4  # payload + (m, 1) f32 scale
    assert telemetry.counter_value(
        "tdt_quant_wire_bytes_total", collective="ag_gemm", wire="fp8"
    ) == float((world - 1) * per_rank)


def test_wire_keyed_crossover(tmp_path, monkeypatch):
    """The |wire= tune entry steers AUTO independently of the bf16 one:
    with ag_gemm_crossover|world=8|wire=fp8 raised above a shard size that
    the bf16 entry routes fused, the SAME shape routes to the ring when
    the operand is quantized."""
    from triton_dist_tpu.kernels.allgather_gemm import (
        get_auto_ag_gemm_method,
    )
    from triton_dist_tpu.tools import tune

    cache_file = tmp_path / "tune.json"
    cache_file.write_text(json.dumps({
        "__schema__": {"version": tune.SCHEMA_VERSION},
        "ag_gemm_crossover|world=8|wire=fp8": {
            "cfg": {"crossover_m": 512}, "time_s": 0.0, "version": "0"},
    }))
    monkeypatch.setenv("TDT_TUNE_CACHE", str(cache_file))
    tune._default_cache = None
    try:
        # 256 rows: above the bf16 default crossover (fused), below the
        # fp8-keyed entry (ring).
        assert (get_auto_ag_gemm_method(256, 64, 64, jnp.float32, 8)
                is AGGemmMethod.PALLAS_FUSED)
        assert (get_auto_ag_gemm_method(256, 64, 64, jnp.float32, 8,
                                        wire="fp8")
                is AGGemmMethod.XLA_RING)
    finally:
        tune._default_cache = None


# ======================================================== paged-KV tier


@pytest.mark.parametrize("wire", WIRES)
def test_paged_decode_quant_oracle(wire, rng):
    """The in-kernel table-walk dequant is BYTE-identical to the
    gather-dequant oracle (same accumulation partition, power-of-two
    scales exact in f32), and the quantized result sits inside the
    per-dtype band of the fp32-pool reference."""
    from triton_dist_tpu.kernels.flash_decode import paged_flash_decode

    b, hq, hkv, d, bs, nb, mb = 2, 4, 2, 64, 16, 9, 4
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    lengths = jnp.asarray([37, 61], jnp.int32)

    kq, ks = quantize_kv_rows(kc, wire)
    vq, vs = quantize_kv_rows(vc, wire)
    o_pal = paged_flash_decode(q, kq, vq, tables, lengths,
                               k_scale=ks, v_scale=vs, impl="pallas")
    o_gat = paged_flash_decode(q, kq, vq, tables, lengths,
                               k_scale=ks, v_scale=vs, impl="gather")
    o_ref = paged_flash_decode(q, kc, vc, tables, lengths, impl="gather")
    np.testing.assert_array_equal(np.asarray(o_pal), np.asarray(o_gat))
    # Attention renormalizes, so the output error tracks the per-row KV
    # band loosely; 4x the bound is comfortably tight for unit-normal KV.
    assert np.abs(np.asarray(o_gat) - np.asarray(o_ref)).max() \
        <= 4 * ERROR_BOUND[wire]


@pytest.mark.parametrize("wire", WIRES)
def test_quant_block_cow_byte_stable(wire, rng):
    """A CoW copy of a quantized block moves the (payload, scale) pair
    verbatim: the copy is byte-identical to a never-shared twin and the
    donor's bytes never change — no scale is ever re-derived."""
    from triton_dist_tpu.models.kv_cache import BlockAllocator

    bs, hkv, d = 16, 2, 64
    rows = jnp.asarray(rng.standard_normal((hkv, bs, d)), jnp.float32)
    q, s = quantize_kv_rows(rows, wire)
    pool_q = np.zeros((4, hkv, bs, d), np.asarray(q).dtype)
    pool_s = np.ones((4, hkv, bs, 1), np.float32)

    alloc = BlockAllocator(4)
    (donor,) = alloc.alloc(1)
    pool_q[donor], pool_s[donor] = np.asarray(q), np.asarray(s)
    donor_q, donor_s = pool_q[donor].copy(), pool_s[donor].copy()

    alloc.incref([donor])  # borrower joins -> shared
    fresh, copied = alloc.ensure_exclusive(donor)
    assert copied and fresh != donor
    # The CoW contract: copy the pair, never requantize.
    pool_q[fresh], pool_s[fresh] = pool_q[donor], pool_s[donor]

    np.testing.assert_array_equal(pool_q[donor].view(np.uint8),
                                  donor_q.view(np.uint8))
    np.testing.assert_array_equal(pool_s[donor], donor_s)
    np.testing.assert_array_equal(pool_q[fresh].view(np.uint8),
                                  donor_q.view(np.uint8))
    np.testing.assert_array_equal(pool_s[fresh], donor_s)
    # And both dequantize to the identical f32 rows.
    np.testing.assert_array_equal(
        np.asarray(dequantize_kv(jnp.asarray(pool_q[fresh]),
                                 jnp.asarray(pool_s[fresh]))),
        np.asarray(dequantize_kv(jnp.asarray(donor_q),
                                 jnp.asarray(donor_s))),
    )


# ========================================================= serving tier

MAX_LEN = 96

#: The pinned parity family (bench.py serving_quant uses the same
#: construction): candidate i has plen 4 + (i % 5)*7 and tokens
#: (3 + 5i + j) % 251 + 1. These indices are the candidates whose
#: 16-token greedy streams are byte-identical across bf16/fp8/int8 KV at
#: the shipped test-dense preset — the argmax margin exceeds the
#: quantization band, so any quant-path regression flips them.
PARITY_IDX = (0, 2, 4, 6, 7, 9)


def _parity_prompt(i):
    return [(3 + 5 * i + j) % 251 + 1 for j in range(4 + (i % 5) * 7)]


@pytest.fixture(scope="module")
def model1():
    from triton_dist_tpu.models import PRESETS, DenseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    return DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def engine(model1):
    from triton_dist_tpu.models import Engine

    return Engine(model1, backend="xla", max_len=MAX_LEN)


def _serve_all(engine, requests, kv_wire, monkeypatch, **srv_kw):
    from triton_dist_tpu.serving import InferenceServer

    if kv_wire is None:
        monkeypatch.delenv("TDT_QUANT_KV", raising=False)
    else:
        monkeypatch.setenv("TDT_QUANT_KV", kv_wire)
    srv = InferenceServer(engine, **srv_kw)
    handles = [srv.submit(p, g) for p, g in requests]
    srv.run()
    assert all(h.done for h in handles)
    return [list(h.tokens) for h in handles]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("wire", WIRES)
def test_serving_greedy_parity_quant_kv(engine, monkeypatch, wire):
    """fp8/int8-KV serving produces byte-identical greedy token streams to
    the bf16-KV run across the staggered parity family (the ISSUE's
    shipped acceptance bar; bench.py gates the same invariant as
    serving_quant_greedy_parity)."""
    reqs = [(_parity_prompt(i), 6 + 2 * n) for n, i in enumerate(PARITY_IDX)]
    base = _serve_all(engine, reqs, None, monkeypatch, num_slots=4)
    quant = _serve_all(engine, reqs, wire, monkeypatch, num_slots=4)
    assert quant == base


@pytest.mark.timeout(600)
def test_serving_prefix_trie_quant_byte_stable(engine, monkeypatch):
    """Prefix-trie borrowing across QUANTIZED blocks: requests sharing a
    full-block prompt head borrow the donor's quantized block and still
    produce streams byte-identical to never-shared twins (each served
    alone on a fresh server — no donor to borrow from), because a shared
    block's (payload, scale) pair was quantized exactly once at append."""
    prefix = _parity_prompt(2)[:16]  # one full default-size KV block
    shared = [(prefix + [10 + i], 4) for i in range(3)]
    twins = [
        _serve_all(engine, [rq], "fp8", monkeypatch, num_slots=1)[0]
        for rq in shared
    ]
    telemetry.reset()
    got = _serve_all(engine, shared, "fp8", monkeypatch,
                     num_slots=1, chunk=2)  # serialize joins
    assert got == twins
    assert telemetry.counter_value("tdt_kv_prefix_hits_total") >= float(
        len(shared) - 1
    )
    assert telemetry.counter_value("tdt_kv_prefix_blocks_reused_total") > 0
