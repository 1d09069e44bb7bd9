"""Attention kernel tests: flash prefill, GQA decode, distributed decode.

Parity model: reference ``test/nvidia/test_flash_decode.py`` — torch-eager
attention reference vs kernel output; inter-rank combine checked on the
sequence-sharded path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels.flash_attn import flash_attention, attention_reference
from triton_dist_tpu.kernels.flash_decode import (
    flash_decode,
    dist_flash_decode_shard,
)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(rng, causal):
    b, hq, hkv, s, d = 1, 4, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_attention_continuation(rng):
    """sq < sk (cache continuation): causal mask must be end-aligned so the
    new queries attend to the entire cached prefix."""
    b, hq, hkv, sq, sk, d = 1, 2, 2, 128, 256, 64
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), jnp.float32)
    o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mode", ["causal", "full", "lse", "chunk"])
def test_flash_attention_pads_illegal_lengths(rng, mode):
    """A length with no Mosaic-legal divisor block (44 under 16-row blocks:
    the largest divisor is 11, no multiple of 8) is padded to the lane width
    and sliced back: padded keys are masked in-kernel, positions keep the
    original lengths. Covers the prefill, the LSE output (whose block must
    be lane-aligned), and a prefill CHUNK against a longer context buffer
    with a dynamic offset — the server's arbitrary prompt lengths."""
    from triton_dist_tpu.kernels.flash_attn import _legal_len

    assert _legal_len(44, 16, 8) == 128 and _legal_len(1500, 1024, 8) == 1536
    assert _legal_len(64, 16, 8) == 64 and _legal_len(61, 1024, 8) == 61
    hq, hkv, s, d = 4, 2, 44, 32
    k = jnp.asarray(rng.standard_normal((1, hkv, s, d)), jnp.float32) * 0.5
    v = jnp.asarray(rng.standard_normal((1, hkv, s, d)), jnp.float32) * 0.5
    if mode == "chunk":
        c, off = 20, 12
        q = jnp.asarray(rng.standard_normal((1, hq, c, d)), jnp.float32) * 0.5
        o = flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            q_offset=jnp.int32(off), kv_offset=jnp.int32(0),
        )
        kx, vx = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, kx) * d ** -0.5
        mask = (off + jnp.arange(c))[:, None] >= jnp.arange(s)[None]
        ref = jnp.einsum(
            "bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(mask, sc, -1e30), -1), vx)
    else:
        q = jnp.asarray(rng.standard_normal((1, hq, s, d)), jnp.float32) * 0.5
        causal = mode != "full"
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                            return_lse=mode == "lse")
        if mode == "lse":
            o, lse = o
            assert lse.shape == (1, hq, s) and np.isfinite(np.asarray(lse)).all()
        ref = attention_reference(q, k, v, causal=causal)
    assert o.shape == ref.shape
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_decode_matches_reference(rng):
    b, hq, hkv, s, d = 2, 8, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lengths = jnp.asarray([s, 100], jnp.int32)  # one full, one partial cache

    o = flash_decode(q, k, v, lengths, block_k=128)

    # Reference: masked softmax attention per batch over valid prefix.
    group = hq // hkv
    kx = np.repeat(np.asarray(k), group, axis=1)
    vx = np.repeat(np.asarray(v), group, axis=1)
    qn = np.asarray(q)
    for bi in range(b):
        L = int(lengths[bi])
        sc = np.einsum("hd,hkd->hk", qn[bi], kx[bi, :, :L]) * (d ** -0.5)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hk,hkd->hd", p, vx[bi, :, :L])
        np.testing.assert_allclose(np.asarray(o)[bi], ref, rtol=2e-4, atol=2e-4)


def test_dist_flash_decode(ctx8, rng):
    """KV sharded over sequence across 8 ranks; combined result must match a
    single-device decode over the full cache (reference flash-decode scaling
    test, README.md:207-211)."""
    b, hq, hkv, d = 2, 8, 2, 32
    s_shard = 64
    s = 8 * s_shard
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lengths = jnp.asarray([s, 300], jnp.int32)  # 300 ends mid-shard on rank 4

    def fn(q_, k_, v_, lens):
        return dist_flash_decode_shard(q_, k_, v_, lens, axis="tp", block_k=64)

    f = jax.jit(
        jax.shard_map(
            fn,
            mesh=ctx8.mesh,
            in_specs=(P(), P(None, None, "tp"), P(None, None, "tp"), P()),
            out_specs=P(),
            check_vma=False,
        )
    )
    out = np.asarray(f(q, k, v, lengths))
    ref = np.asarray(flash_decode(q, k, v, lengths, block_k=64))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_fully_masked_rows(rng):
    """Rows placed entirely BEFORE the kv window via the public
    q_offset/kv_offset args are fully masked and must produce o=0 and
    lse≈-inf — not mean(v) (r2 review: an unguarded exp2(NEG_INF-NEG_INF)=1
    row-fill; the varlen kernel always had the guard)."""
    b, h, s, d = 1, 2, 128, 64
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    # Queries start 64 rows before the keys: rows 0..63 see no valid key.
    o, lse = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64,
        q_offset=jnp.int32(0), kv_offset=jnp.int32(64), return_lse=True,
    )
    np.testing.assert_array_equal(np.asarray(o[:, :, :64]), 0.0)
    assert np.all(np.asarray(lse[:, :, :64]) < -1e25)
    # Rows at/after the kv start behave exactly like an offset-free call on
    # the visible prefix.
    ref = attention_reference(q[:, :, 64:], k[:, :, : s - 64], v[:, :, : s - 64], causal=True)
    np.testing.assert_allclose(np.asarray(o[:, :, 64:]), np.asarray(ref), rtol=2e-4, atol=2e-4)
