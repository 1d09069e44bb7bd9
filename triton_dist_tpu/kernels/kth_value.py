"""The k-th largest value of every row of a float32 matrix, exactly and
without a sort: bisection over the float's 32 bits.

A float32 maps to an int32 *key* that orders as the floats do (the bits of
a non-negative float as they are, the low 31 bits of a negative one
flipped), so "the k-th largest" can be built a bit at a time from the top:
with ``prefix`` the bits decided so far, bit ``b`` is kept where at least
``k`` keys are ``>= prefix | 1 << b``. After 32 steps ``prefix`` is the key
of the k-th largest element itself, whatever the ties, ``-inf`` included,
and goes back to the float it came from. ``-0.0`` orders under ``+0.0``
here; a caller that compares floats with the result (``s > kth``, ``s ==
kth``) sees the two as one value.

:func:`kth_value` is the Pallas kernel: a tile of rows is read from HBM
once, turned into keys in VMEM, and the 32 counting passes run there; the
same bisection in plain XLA, :func:`kth_value_xla`, reads the matrix 32
times and is the kernel's oracle in ``tests/test_kth_value.py``. XLA's own
``lax.top_k`` lowers to a full sort with indices on the chip (27.6 ms for
``f32[2048, 16384]``; PERF.md section 6, PR 29).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime.platform import interpret_mode_default

INT_MIN = -(2 ** 31)
#: Bytes of float32 scores a row tile may hold. The tile is in VMEM three
#: times (the input block double-buffered, and its keys) beside a counting
#: pass's temporaries, and the kernel asks for eight times this. 64 rows of
#: 16384: 0.79 ms for 2048 rows, where 32 take 1.02 and 256 0.70 (my chip
#: run, PR 29).
TILE_BYTES = 4 * 1024 * 1024


def float_to_key(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def key_to_float(key):
    """The inverse of :func:`float_to_key` (the map is an involution)."""
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32)


def _bisect(count_at_least, k: int, rows: int):
    """The key (rows, 1) int32 of each row's k-th largest, given
    ``count_at_least(threshold (rows, 1) int32) -> (rows, 1) int32``, the
    keys of the row that are >= the threshold. ``prefix`` is kept in the
    offset form (key ^ INT_MIN), in which the order is the unsigned one and
    a larger value is a set bit."""

    def step(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count_at_least(cand ^ INT_MIN) >= k, cand, prefix)

    prefix = jax.lax.fori_loop(0, 32, step, jnp.zeros((rows, 1), jnp.int32))
    return prefix ^ INT_MIN


def _count(keys, threshold):
    return jnp.sum((keys >= threshold).astype(jnp.int32), axis=-1, keepdims=True)


def kth_value_xla(x, k: int):
    """The k-th largest of each row of ``x`` (T, S) float32 -> (T, 1), by
    the bisection in plain XLA: every step reads ``x`` again."""
    keys = float_to_key(x)
    return key_to_float(_bisect(functools.partial(_count, keys), k, x.shape[0]))


def _kernel(x_ref, out_ref, keys_ref, *, k: int):
    keys_ref[...] = float_to_key(x_ref[...])
    key = _bisect(lambda thr: _count(keys_ref[...], thr), k, keys_ref.shape[0])
    out_ref[...] = key_to_float(key)


def row_tile(t: int, s: int) -> int:
    """Rows a tile: as many as :data:`TILE_BYTES` of scores hold, in eights
    (Mosaic's sublanes), at least 8, and no more than there are."""
    return min(t, max(8, TILE_BYTES // (4 * s) // 8 * 8))


def kth_value(x, k: int):
    """The k-th largest of each row of ``x`` (T, S) float32 -> (T, 1)
    float32, ``1 <= k <= S``. Rows are independent; a last tile that runs
    past T computes on whatever lies there and its rows are dropped."""
    t, s = x.shape
    assert x.dtype == jnp.float32 and 1 <= k <= s, (x.dtype, k, s)
    rows = row_tile(t, s)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(pl.cdiv(t, rows),),
        in_specs=[pl.BlockSpec((rows, s), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=8 * TILE_BYTES),
        interpret=interpret_mode_default(),
        name="dsa_kth_value",
    )(x)
