"""The selective-scan kernel (``kernels/ssm_scan.py``, interpreted here)
against its XLA oracle, the same recurrence as a ``lax.scan``: whole blocks,
a ragged last block of rows, several blocks of channels, a carried state,
and rows whose ``dt`` is 0 (a padded chunk's): they leave the state alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import ssm_scan as K


def _inputs(t, din, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, din)) - 3.0)
    a_t = -jnp.exp(0.3 * jax.random.normal(ks[2], (n, din)))
    b, c = jax.random.normal(ks[3], (t, n)), jax.random.normal(ks[4], (t, n))
    return x, dt, a_t, b, c, jnp.ones((din,)), jax.random.normal(ks[5], (n, din))


@pytest.mark.parametrize("t,din,n", [(5, 128, 8), (24, 256, 16), (136, 1024, 16), (300, 128, 16)],
                         ids=["under_a_tile", "three_tiles", "ragged_rows_two_channel_blocks",
                              "three_row_blocks"])
def test_kernel_matches_the_scan(t, din, n):
    args = _inputs(t, din, n, seed=t)
    want_y, want_s = jax.jit(K.ssm_scan_xla)(*args)
    got_y, got_s = jax.jit(K.ssm_scan)(*args)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=2e-5)


def test_two_chunks_with_the_state_carried_are_one_scan():
    x, dt, a_t, b, c, d, s0 = _inputs(48, 128, 16, seed=1)
    whole_y, whole_s = K.ssm_scan(x, dt, a_t, b, c, d, s0)
    y1, s1 = K.ssm_scan(x[:20], dt[:20], a_t, b[:20], c[:20], d, s0)
    y2, s2 = K.ssm_scan(x[20:], dt[20:], a_t, b[20:], c[20:], d, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2])), np.asarray(whole_y),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(whole_s), atol=2e-5)


def test_rows_without_dt_leave_the_state_as_it_was():
    x, dt, a_t, b, c, d, s0 = _inputs(16, 128, 8, seed=2)
    _, s_real = K.ssm_scan(x[:11], dt[:11], a_t, b[:11], c[:11], d, s0)
    _, s_padded = K.ssm_scan(x, dt.at[11:].set(0.0), a_t, b, c, d, s0)
    np.testing.assert_array_equal(np.asarray(s_padded), np.asarray(s_real))


def test_tiles_are_whole_sublanes_and_lanes():
    assert K.tiles(512, 5120) == (128, 512, 512)
    assert K.tiles(5, 128) == (8, 8, 128)
    assert K.tiles(136, 1024) == (128, 256, 512)
