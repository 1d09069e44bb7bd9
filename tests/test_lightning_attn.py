"""``kernels/lightning_attn.py``: the chunk kernel interpreted on the CPU at
heads of 128 lanes, its ``jax.numpy`` twin and the decode step, against the
recurrence a position at a time; across a chunk boundary with a carried
state, and with a padded final chunk.

Tolerance: float32 on every side; the chunked form sums a sub-chunk's
products in another order and rebuilds decays as ``exp(-s n)``: 2e-4 on
outputs and states of size ~10-100 (the state holds ``k^T v`` sums).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import lightning_attn as la

H, D = 2, 128
TOL = 2e-4


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(56, H, D)), jnp.float32) for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(H, D, D)), jnp.float32)
    return q, k, v, s0


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_chunks_with_a_carried_state_match_the_recurrence(monkeypatch, rows, form):
    """56 rows as chunks of 24, 24 and a final one of 8 padded to 24, each
    from the state the one before left: outputs and final state are the
    recurrence's over the 56; a chunk with no real row changes nothing."""
    monkeypatch.setattr(la, "SUB", 16)  # a chunk of 24 is two sub-chunks, the second ragged
    q, k, v, s0 = rows
    slope = la.slopes(H)
    want_o, want_s = la.lightning_scan(q, k, v, s0, slope)
    flat = lambda z: z.reshape(z.shape[0], H * D)
    if form == "kernel":
        chunk = jax.jit(lambda q, k, v, s, n: la.lightning_chunk(
            flat(q), flat(k), flat(v), s, slope, n))
    else:
        chunk = jax.jit(lambda q, k, v, s, n: la.lightning_chunk_xla(q, k, v, s, slope, n))
    s, outs = s0, []
    for off in (0, 24, 48):
        take = lambda z: jnp.pad(z[off:off + 24], ((0, 24 - len(z[off:off + 24])), (0, 0), (0, 0)),
                                 constant_values=1.0)
        n = min(56 - off, 24)
        o, s = chunk(take(q), take(k), take(v), s, jnp.int32(n))
        outs.append(np.asarray(o).reshape(24, H, D)[:n])
    np.testing.assert_allclose(np.concatenate(outs), np.asarray(want_o), atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=TOL)
    _, same = chunk(q[:24], k[:24], v[:24], s, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(s))


def test_decode_steps_match_the_recurrence_and_freeze_an_inactive_slot(rows):
    q, k, v, s0 = rows
    slope = la.slopes(H)
    want_o, want_s = la.lightning_scan(q[:5], k[:5], v[:5], s0, slope)
    S = jnp.stack([s0, s0])
    step = jax.jit(la.lightning_step)
    for t in range(5):
        two = lambda z: jnp.stack([z[t], z[t]])
        o, S = step(two(q), two(k), two(v), S, slope, jnp.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want_o[t]), atol=TOL)
    np.testing.assert_allclose(np.asarray(S[0]), np.asarray(want_s), atol=TOL)
    np.testing.assert_array_equal(np.asarray(S[1]), np.asarray(s0))


def test_the_state_is_not_rounded_to_the_operands_type(rows):
    """bfloat16 operands, float32 state: ``Q S_0`` through the state's two
    parts keeps what a bfloat16 copy of the state would lose."""
    q, k, v, s0 = rows
    slope = la.slopes(H)
    bf = lambda z: z[:16].astype(jnp.bfloat16)
    zeros = jnp.zeros_like(bf(k))
    o, _ = jax.jit(la.lightning_chunk_xla)(bf(q), zeros, zeros, s0, slope, jnp.int32(16))
    exact, _ = la.lightning_scan(bf(q), zeros, zeros, s0, slope)
    rounded, _ = la.lightning_scan(bf(q), zeros, zeros, s0.astype(jnp.bfloat16), slope)
    err = np.abs(np.asarray(o) - np.asarray(exact)).max()
    lost = np.abs(np.asarray(rounded) - np.asarray(exact)).max()
    assert err < 2e-3 and lost > 10 * err, (err, lost)
