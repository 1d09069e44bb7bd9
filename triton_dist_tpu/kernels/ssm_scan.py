"""The selective scan of a Mamba-1 layer over a chunk of rows, with the state
carried in and out: ``s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) B_t^T``,
``y_t = s_t C_t + D * x_t``, for ``t`` over the chunk's rows, ``s`` a
``[N, d_in]`` float32 state (the channels on the lanes).

The recurrence is thousands of small sequential steps. As a ``lax.scan``
each step is a handful of XLA operations over ``f32[N, d_in]`` with the
state going through HBM between them; as an associative scan the
``f32[T, N, d_in]`` products go through HBM a dozen times a layer. Here a
block of channels keeps its state in registers for the whole chunk: the grid
is (blocks of channels, blocks of rows), the rows' axis sequential, and a
step reads one row of ``x`` and ``dt``, one column of ``B`` and ``C``, and
writes one row of ``y``. The state leaves the chip once, at the end.

A row whose ``dt`` is 0 leaves the state as it was (``exp(0) = 1``, nothing
added): that is how a padded final chunk's rows past the prompt, and the
rows this wrapper pads ``T`` with, are kept out of the state. Their ``y`` is
of no use to anybody.

:func:`ssm_scan_xla` is the same recurrence as a plain ``lax.scan``: the
kernel's oracle (``tests/test_ssm_scan.py``) and what a model whose channel
count is not whole lanes runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime.platform import interpret_mode_default

F32 = jnp.float32
#: Channels a block (lanes) and rows a block. The state of a block is
#: ``N x CHANNELS`` float32 in registers: 8 vregs at N = 16.
CHANNELS = 512
ROWS = 128


def ssm_scan_xla(x, dt, a_t, b, c, d, s0):
    """x, dt (T, Din) float32; a_t (N, Din) = A^T; b, c (T, N); d (Din,);
    s0 (N, Din) -> (y (T, Din) float32, s_T (N, Din) float32)."""

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t[None, :] * a_t) * s + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + d * x_t

    s, y = jax.lax.scan(step, s0.astype(F32), (x.astype(F32), dt, b, c))
    return y, s


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref, y_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    a = a_ref[...]
    d = d_ref[...]
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (8, a.shape[1]), 0)

    def eight_rows(g, s):
        r0 = pl.multiple_of(g * 8, 8)
        xg = x_ref[pl.ds(r0, 8), :]
        dtg = dt_ref[pl.ds(r0, 8), :]
        yg = jnp.zeros_like(xg)
        for j in range(8):
            x_t, dt_t = xg[j:j + 1, :], dtg[j:j + 1, :]
            s = jnp.exp(dt_t * a) * s + (dt_t * x_t) * b_ref[r0 + j]
            y_t = jnp.sum(s * c_ref[r0 + j], axis=0, keepdims=True) + d * x_t
            yg = jnp.where(lane_row == j, y_t, yg)
        y_ref[pl.ds(r0, 8), :] = yg
        return s

    s_ref[...] = jax.lax.fori_loop(0, x_ref.shape[0] // 8, eight_rows, s_ref[...])


def tiles(t: int, din: int) -> tuple[int, int, int]:
    """(rows a block, rows after padding, channels a block)."""
    rows = min(ROWS, -(-t // 8) * 8)
    return rows, -(-t // rows) * rows, CHANNELS if din % CHANNELS == 0 else din


def ssm_scan(x, dt, a_t, b, c, d, s0):
    """:func:`ssm_scan_xla`'s arguments and results, as one kernel. ``Din``
    is whole lanes (a multiple of 128)."""
    t, din = x.shape
    n = a_t.shape[0]
    assert din % 128 == 0, din
    rows, t_pad, ch = tiles(t, din)
    pad = lambda z: jnp.pad(z.astype(F32), ((0, t_pad - t), (0, 0)))
    x, dt, b, c = pad(x), pad(dt), pad(b), pad(c)
    by_rows = pl.BlockSpec((rows, ch), lambda i, j: (j, i))
    by_channels = pl.BlockSpec((n, ch), lambda i, j: (0, i))
    column = pl.BlockSpec((rows, n, 1), lambda i, j: (j, 0, 0))
    y, s = pl.pallas_call(
        _kernel,
        grid=(din // ch, t_pad // rows),
        in_specs=[by_rows, by_rows, by_channels, column, column,
                  pl.BlockSpec((1, ch), lambda i, j: (0, i)), by_channels],
        out_specs=[by_rows, by_channels],
        out_shape=[jax.ShapeDtypeStruct((t_pad, din), F32),
                   jax.ShapeDtypeStruct((n, din), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret_mode_default(),
        name="ssm_scan",
    )(x, dt, a_t.astype(F32), b[:, :, None], c[:, :, None],
      d.astype(F32)[None, :], s0.astype(F32))
    return y[:t], s
