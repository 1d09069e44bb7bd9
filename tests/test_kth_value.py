"""The exact k-th largest value a row without a sort (``kernels/kth_value.py``)
and the selection built on it (``layers/latent_sparse.py:select_mask``), on
the CPU through the Pallas interpreter.

Oracles kept here: ``lax.top_k(s, k)[0][:, -1]`` for the value, and for the
mask the formula ``select_mask`` had while it sorted (the k-th value from
``top_k``, a running count over the scores equal to it on every call). No
tolerance anywhere: the value is one of the row's own floats and the mask a
set, so equal means equal. ``+0.0`` and ``-0.0`` compare equal as floats,
which is how the mask reads the value; every other value is also held to
the same bits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import kth_value as kv
from triton_dist_tpu.layers import latent_sparse as ls

NEG = -np.inf


def _normal(rng, t, s):
    return rng.normal(size=(t, s))


def _few_levels(rng, t, s):
    """Five levels: any boundary falls inside a run of equal scores."""
    return np.round(rng.normal(size=(t, s)))


def _one_value(rng, t, s):
    return np.broadcast_to(rng.normal(size=(t, 1)), (t, s))


def _signed_zeros(rng, t, s):
    """``+0.0`` beside ``-0.0`` astride the boundary, between +1 and -1."""
    x = np.where(rng.random((t, s)) < 0.5, 0.0, -0.0)
    far = rng.random((t, s))
    return np.where(far < 0.2, 1.0, np.where(far > 0.8, -1.0, x))


def _few_visible(rng, t, s):
    """Row i sees i + 1 positions; ``-inf`` fills the rest, so the rows
    with fewer than k visible have -inf for their k-th value."""
    x = rng.normal(size=(t, s))
    return np.where(np.arange(s)[None, :] <= np.arange(t)[:, None], x, NEG)


#: (maker, T, S, k). With ``ROW_TILE`` rows a tile: S is no multiple of 128
#: in most, T no multiple of the tile in most (a last tile runs past T).
CASES = {
    "normal": (_normal, 40, 256, 32),
    "few_levels": (_few_levels, 21, 200, 50),
    "one_value": (_one_value, 9, 130, 64),
    "signed_zeros": (_signed_zeros, 24, 96, 40),
    "few_visible": (_few_visible, 37, 70, 16),
    "k_is_1": (_normal, 16, 300, 1),
    "k_is_1_few_levels": (_few_levels, 11, 300, 1),
    "k_is_S_minus_1": (_few_levels, 13, 129, 128),
    "k_is_S": (_normal, 8, 64, 64),
    "wide_range": (lambda rng, t, s: rng.normal(size=(t, s)) * 10.0 ** rng.integers(
        -30, 30, size=(t, s)), 19, 384, 100),
}
ROW_TILE = 8


@pytest.fixture
def small_tiles(monkeypatch):
    """Several row tiles at these sizes (the chip's tile holds megabytes)."""
    monkeypatch.setattr(kv, "row_tile", lambda t, s: min(t, ROW_TILE))


def _same_value(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got, want)  # as floats: -0.0 == +0.0
    nonzero = want != 0
    np.testing.assert_array_equal(got.view(np.int32)[nonzero], want.view(np.int32)[nonzero])


@pytest.mark.parametrize("case", CASES)
def test_kth_value_is_top_ks_last(case, small_tiles):
    make, t, s, k = CASES[case]
    x = jnp.asarray(make(np.random.default_rng(len(case)), t, s), jnp.float32)
    want = jax.lax.top_k(x, k)[0][:, -1:]
    _same_value(jax.jit(kv.kth_value, static_argnums=1)(x, k), want)
    _same_value(jax.jit(kv.kth_value_xla, static_argnums=1)(x, k), want)


def test_keys_order_as_the_floats_do():
    x = jnp.asarray([NEG, -3e38, -1.5, -1e-45, -0.0, 0.0, 1e-45, 2.0, 3e38, np.inf], jnp.float32)
    keys = np.asarray(kv.float_to_key(x))
    assert (np.diff(keys.astype(np.int64)) > 0).all()
    np.testing.assert_array_equal(
        np.asarray(kv.key_to_float(jnp.asarray(keys))).view(np.int32), np.asarray(x).view(np.int32))


def test_row_tile_follows_the_shape():
    """Eights of rows within ``TILE_BYTES``, the whole of a short matrix."""
    assert kv.row_tile(2048, 16384) == 64 and kv.row_tile(2048, 16640) == 56
    assert kv.row_tile(2048, 4096) == 256 and kv.row_tile(5, 4096) == 5
    assert kv.row_tile(2048, 1 << 20) == 8
    for s in (4096, 8192, 16384, 16640):
        assert 4 * s * kv.row_tile(2048, s) <= kv.TILE_BYTES


# ----------------------------------------------------------------- the mask


def _sorted_select_mask(scores, visible, k):
    """``select_mask`` as it was: the k-th value from a sort, the tie walk
    on every call. -> (mask, rows whose ties overflowed)."""
    s = jnp.where(visible, scores, NEG)
    kth = jax.lax.top_k(s, k)[0][:, -1:]
    above = s > kth
    tied = (s == kth) & visible
    room = k - above.sum(axis=-1, keepdims=True)
    mask = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    return mask, (tied.sum(axis=-1, keepdims=True) > room)[:, 0]


def _causal(t, s, off):
    return jnp.arange(s)[None, :] <= (off + jnp.arange(t))[:, None]


MASK_CASES = {
    # (maker, visible, ties overflow on some row)
    "no_tie": (_normal, _causal(48, 160, 100), False),
    "ties_overflow": (_few_levels, _causal(48, 160, 100), True),
    "signed_zeros": (_signed_zeros, _causal(48, 160, 112), True),
    "one_row_overflows": (
        lambda rng, t, s: np.where(np.arange(t)[:, None] == 7, 1.0, rng.normal(size=(t, s))),
        _causal(48, 160, 100), True),
    "scattered_visible": (
        _few_levels, jnp.asarray(np.random.default_rng(1).random((30, 200)) < 0.6), True),
    "chunk_at_offset_0": (_normal, _causal(64, 96, 0), False),  # rows of under k visible
}


@pytest.mark.parametrize("case", MASK_CASES)
def test_select_mask_is_the_sorted_selection(case, small_tiles):
    make, visible, overflow = MASK_CASES[case]
    k = 24
    t, s = visible.shape
    scores = jnp.asarray(make(np.random.default_rng(len(case)), t, s), jnp.float32)
    want, want_rows = _sorted_select_mask(scores, visible, k)
    got, got_rows = jax.jit(ls.select_mask, static_argnums=2)(scores, visible, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    assert bool(np.asarray(got_rows).any()) is overflow
    n_visible = np.asarray(visible).sum(axis=-1)
    np.testing.assert_array_equal(np.asarray(got).sum(axis=-1), np.minimum(n_visible, k))
    if case == "signed_zeros":
        # The mask compares floats, to which the two zeros are one value, as
        # it did when it sorted; the CPU's lax.top_k puts +0.0 over -0.0.
        return
    # ... and it is the set lax.top_k's positions make
    idx, real = ls.select_positions(scores, visible, k)
    by_position = np.zeros((t, s), bool)
    rows = np.broadcast_to(np.arange(t)[:, None], idx.shape)
    by_position[rows[np.asarray(real)], np.asarray(idx)[np.asarray(real)]] = True
    np.testing.assert_array_equal(np.asarray(got), by_position)


def test_no_more_positions_than_k_selects_everything_visible():
    visible = _causal(8, 24, 10)
    got, rows = ls.select_mask(jnp.zeros((8, 24)), visible, 24)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(visible))
    assert not np.asarray(rows).any()
