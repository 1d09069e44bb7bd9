"""Lightning (decayed linear) attention with the state carried in and out.

A head keeps a float32 state ``S`` (D, D) and decays it by ``lam = exp(-s)``
a position: ``S_t = lam S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t * scale``.

:func:`lightning_chunk` is a prefill chunk of ``C`` rows as one kernel, in
the chunked form over sub-chunks of ``c`` rows at in-chunk index ``t``::

    O  = diag(lam^(t+1)) Q S_0 + ((Q K^T) * D) V,   D[t, j] = lam^(t-j), j <= t
    S' = lam^c S_0 + (diag(lam^(c-1-j)) K)^T V

Grid (heads, sub-chunks), the sub-chunks sequential: a head's ``S`` stays in
VMEM (the state's output block, revisited) from the chunk's first row to its
last and leaves the chip once. The four products run on the MXU in the
operands' type with float32 accumulation; the decay factors are float32 and
``Q S_0`` takes ``S_0`` as the sum of two operand-type parts (its rounding
and the rest), so the state is never rounded to bfloat16. ``q``, ``k``, ``v``
are read as they lie, ``(C, heads * D)``: a head is a block of columns.

Only the first ``n_real`` rows are somebody's (a prompt's padded final
chunk, and the rows this wrapper pads ``C`` with): a row past them neither
decays the state nor adds to it, and its output is of no use to anybody.

:func:`lightning_chunk_xla` is the same chunked form in plain ``jax.numpy``
(what a model whose head is not whole lanes runs), :func:`lightning_scan`
the recurrence a position at a time (the oracle of both), and
:func:`lightning_step` one decode step of every slot at once: a batched
rank-one update and read, elementwise in float32, which XLA fuses into one
pass over the slots' states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime.platform import interpret_mode_default

F32 = jnp.float32
LANES = 128
#: Rows a sub-chunk: the intra-chunk products grow with it, the grid's
#: steps (and the state's trips through the MXU) shrink.
SUB = 256


def slopes(heads: int):
    """``s_h = 2^(-8 (h + 1) / heads)``: the decay is ``exp(-s_h)``."""
    return 2.0 ** (-8.0 * (jnp.arange(heads, dtype=F32) + 1.0) / heads)


def takes(head_dim: int) -> bool:
    """Whether the kernel takes heads of this size: whole lanes."""
    return head_dim % LANES == 0


def lightning_scan(q, k, v, s0, slope, n_real=None):
    """The recurrence. q, k, v (T, H, D); s0 (H, D, D) float32; slope (H,)
    -> (o (T, H, D) float32 unscaled, S_T)."""
    lam = jnp.exp(-slope)[:, None, None]
    n = q.shape[0] if n_real is None else n_real

    def step(S, row):
        t, q_t, k_t, v_t = row
        S1 = lam * S + k_t[:, :, None] * v_t[:, None, :]
        S1 = jnp.where(t < n, S1, S)
        return S1, jnp.sum(q_t[:, :, None] * S1, axis=1)

    S, o = jax.lax.scan(step, s0.astype(F32), (
        jnp.arange(q.shape[0]), q.astype(F32), k.astype(F32), v.astype(F32)))
    return o, S


def _sub_chunk(q, k, v, S, slope, r, dt):
    """One sub-chunk of one head: q, k, v (c, D) in ``dt``, S (D, D) float32,
    ``slope`` a float32 scalar, ``r`` how many of the rows are real ->
    (o (c, D) float32 unscaled, S')."""
    c = q.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32)
    diff = t - j
    decay = jnp.where(diff >= 0, jnp.exp(-slope * jnp.maximum(diff, 0).astype(F32)), 0.0)
    intra = jnp.dot((a * decay).astype(dt), v, preferred_element_type=F32)
    s_hi = S.astype(dt)
    s_lo = (S - s_hi.astype(F32)).astype(dt)
    inter = (jnp.dot(q, s_hi, preferred_element_type=F32)
             + jnp.dot(q, s_lo, preferred_element_type=F32))
    o = inter * jnp.exp(-slope * (t + 1).astype(F32)) + intra
    left = r - 1 - t  # positions between a row and the last real one
    kd = jnp.where(left >= 0, jnp.exp(-slope * jnp.maximum(left, 0).astype(F32)), 0.0)
    kd = (k.astype(F32) * kd).astype(dt)
    S1 = jnp.exp(-slope * r.astype(F32)) * S + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=F32)
    return o, S1


def lightning_chunk_xla(q, k, v, s0, slope, n_real, sub: int = SUB):
    """:func:`lightning_chunk`'s arguments and results, in plain XLA."""
    C, H, D = q.shape
    c = min(sub, C)
    pad = -C % c
    cut = lambda z: jnp.pad(z, ((0, pad), (0, 0), (0, 0))).reshape(-1, c, H, D).transpose(
        0, 2, 1, 3)  # (sub-chunks, H, c, D)
    one = jax.vmap(functools.partial(_sub_chunk, dt=q.dtype), in_axes=(0, 0, 0, 0, 0, None))

    def step(S, xs):
        i, qc, kc, vc = xs
        o, S = one(qc, kc, vc, S, slope, jnp.clip(n_real - i * c, 0, c))
        return S, o

    S, o = jax.lax.scan(step, s0.astype(F32),
                        (jnp.arange((C + pad) // c), cut(q), cut(k), cut(v)))
    return o.transpose(0, 2, 1, 3).reshape(C + pad, H, D)[:C], S


def _kernel(n_ref, slope_ref, q_ref, k_ref, v_ref, s0_ref, o_ref, s_ref, *, c: int):
    h, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_ref[...] = s0_ref[...]

    r = jnp.clip(n_ref[0] - i * c, 0, c)
    o, S = _sub_chunk(q_ref[...], k_ref[...], v_ref[...], s_ref[0], slope_ref[h], r,
                      q_ref.dtype)
    o_ref[...] = o
    s_ref[0] = S


def lightning_chunk(q, k, v, s0, slope, n_real):
    """q, k, v (C, H * D) in the model's type, a head a block of ``D``
    columns; ``s0`` (H, D, D) float32, the state before the chunk; ``slope``
    (H,) float32; ``n_real`` int32 scalar, the rows that are somebody's.
    Returns (o (C, H * D) float32, unscaled; the state after row ``n_real -
    1``)."""
    H, D, _ = s0.shape
    C = q.shape[0]
    assert q.shape[1] == H * D and D % LANES == 0, (q.shape, s0.shape)
    c = min(SUB, -(-C // 16) * 16)
    pad = -C % c
    if pad:
        q, k, v = (jnp.pad(z, ((0, pad), (0, 0))) for z in (q, k, v))
    rows = pl.BlockSpec((c, D), lambda h, i, *_: (i, h))
    state = pl.BlockSpec((1, D, D), lambda h, i, *_: (h, 0, 0))
    o, S = pl.pallas_call(
        functools.partial(_kernel, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the real rows, the heads' slopes
            grid=(H, (C + pad) // c),
            in_specs=[rows, rows, rows, state],
            out_specs=[rows, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((C + pad, H * D), F32),
                   jax.ShapeDtypeStruct((H, D, D), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode_default(),
        name="lightning_chunk",
    )(jnp.reshape(n_real, (1,)).astype(jnp.int32), slope.astype(F32), q, k, v, s0.astype(F32))
    return o[:C], S


def lightning_step(q, k, v, S, slope, active):
    """One decode step of every slot. q, k, v (B, H, D); ``S`` (B, H, D, D)
    float32; ``active`` (B,) bool: an inactive slot's state stays as it was.
    Returns (o (B, H, D) float32 unscaled, S')."""
    lam = jnp.exp(-slope)[None, :, None, None]
    kf, vf, qf = k.astype(F32), v.astype(F32), q.astype(F32)
    S1 = lam * S + kf[..., :, None] * vf[..., None, :]
    o = jnp.sum(qf[..., :, None] * S1, axis=-2)
    return o, jnp.where(active[:, None, None, None], S1, S)
