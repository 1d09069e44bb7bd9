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
pass over the slots' states. Mamba-2's kernels follow, in a section of their own.
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


# -------------------------------------------------------------- Mamba-2 (SSD)
#
# **Mamba-2's state-space duality** (SSD) is the same chunked form with a
# decay that is data: head ``h`` of ``P`` channels keeps ``S_h`` (N, P)
# float32 and, at a row with step ``dt_t,h > 0`` and the head's ``A_h < 0``,
# ``S_t = exp(dt A) S_{t-1} + B_t^T (dt x_t)``, ``y_t = C_t S_t + D_h x_t``;
# ``B`` and ``C`` (N wide) are shared by every head (one group). Over a
# sub-chunk, with ``a_t`` the running sum of ``dt A`` inside it::
#
#     Y  = diag(exp(a)) C S_0 + ((C B^T) * L) (dt * X) + D X,  L[t, j] = exp(a_t - a_j), j <= t
#     S' = exp(a_last) S_0 + (B * exp(a_last - a_j) dt_j)^T X
#
# ``L`` is taken from differences of the running sums, never as ``exp(a_t)
# exp(-a_j)``: ``a`` reaches some -400 over a sub-chunk and ``exp(-a_j)``
# would overflow. A slot's state lies as ``(N, H * P)`` (the transpose of
# ``[H, N, P]`` taken head by head: head ``h`` is the columns ``h P ... h P +
# P - 1``), so that every array the kernels touch is whole lanes.
# :func:`ssd_chunk` is a prefill chunk as one kernel (named ``ssd_chunk``),
# :func:`ssd_chunk_xla` the same form in plain XLA, :func:`ssd_scan` the
# recurrence a row at a time (the oracle of both), and :func:`ssd_step` one
# decode step of every slot as one kernel (named ``ssd_step``) that reads and
# writes the state of the slots that advance, in place, and no other.


def ssd_scan(x, dt, A, B, C, D, s0, n_real=None):
    """The recurrence a row at a time. x (T, H, P); dt (T, H) float32 (the
    step after its softplus); A, D (H,) float32; B, C (T, N); s0 (N, H * P)
    float32. Rows from ``n_real`` on change nothing. Returns (y (T, H, P)
    float32, the state after the last real row)."""
    T, H, P = x.shape
    n = T if n_real is None else n_real
    Af, Df = A.astype(F32), D.astype(F32)

    def step(S, row):
        t, x_t, dt_t, b_t, c_t = row
        S3 = S.reshape(-1, H, P)
        S1 = (jnp.exp(dt_t * Af)[None, :, None] * S3
              + b_t[:, None, None] * (dt_t[:, None] * x_t)[None])
        S1 = jnp.where(t < n, S1, S3)
        y = jnp.einsum("n,nhp->hp", c_t, S1) + Df[:, None] * x_t
        return S1.reshape(S.shape), y

    S, y = jax.lax.scan(step, s0.astype(F32), (
        jnp.arange(T), x.astype(F32), dt.astype(F32), B.astype(F32), C.astype(F32)))
    return y, S


def _ssd_inputs(dt, A, n_real, T: int, c: int):
    """dt with its rows from ``n_real`` on zeroed (no decay, nothing added),
    padded to a whole number of sub-chunks of ``c``, and ``a``: the running
    sum of ``dt A`` within each sub-chunk. Both (T + pad, H) float32."""
    pad = -T % c
    dt = jnp.where((jnp.arange(T) < n_real)[:, None], dt.astype(F32), 0.0)
    dt = jnp.pad(dt, ((0, pad), (0, 0)))
    a = jnp.cumsum((dt * A.astype(F32)).reshape(-1, c, dt.shape[1]), axis=1)
    return dt, a.reshape(dt.shape)


def ssd_chunk_xla(x, dt, A, B, C, D, s0, n_real, sub: int = SUB):
    """:func:`ssd_chunk`'s arguments and results, in plain XLA, a sub-chunk
    at a time in the same form."""
    T, H, P = x.shape
    N = B.shape[1]
    c = min(sub, T)
    pad = -T % c
    dt, a = _ssd_inputs(dt, A, n_real, T, c)
    cut = lambda z: jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1)).reshape(
        (T + pad) // c, c, *z.shape[1:])
    t = jnp.arange(c)
    causal = t[:, None] >= t[None, :]

    def step(S, xs):
        xc, dtc, ac, bc, cc = xs  # (c, H, P), (c, H), (c, H), (c, N), (c, N)
        S3 = S.reshape(N, H, P).astype(F32)
        xf, bf, cf = xc.astype(F32), bc.astype(F32), cc.astype(F32)
        L = jnp.where(causal[None], jnp.exp(jnp.minimum(ac.T[:, :, None] - ac.T[:, None, :], 0.0)),
                      0.0)  # (H, c, c)
        cb = cf @ bf.T
        intra = jnp.einsum("htj,jhp->thp", cb[None] * L, dtc[:, :, None] * xf)
        inter = jnp.exp(ac)[:, :, None] * jnp.einsum("tn,nhp->thp", cf, S3)
        last = ac[-1]  # (H,)
        w = jnp.exp(last[None] - ac) * dtc  # (c, H)
        S1 = jnp.exp(last)[None, :, None] * S3 + jnp.einsum("jn,jhp->nhp", bf, w[:, :, None] * xf)
        y = intra + inter + D.astype(F32)[None, :, None] * xf
        return S1.reshape(S.shape), y

    S, y = jax.lax.scan(step, s0.astype(F32), (cut(x), dt.reshape(-1, c, H), a.reshape(-1, c, H),
                                                 cut(B), cut(C)))
    return y.reshape(-1, H, P)[:T], S


def _ssd_chunk_kernel(n_ref, d_ref, x_ref, dtc_ref, ac_ref, ar_ref, b_ref, c_ref, s0_ref,
                      y_ref, s_ref, *, hb: int, P: int, W: int, dt):
    """One grid step: ``hb`` heads (a block of ``hb * P`` columns in slabs of
    ``W`` lanes, ``W // P`` heads a slab) over one sub-chunk of ``c`` rows."""
    blk, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_ref[...] = s0_ref[...]

    c = x_ref.shape[0]
    H = dtc_ref.shape[1]
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lane_h = jax.lax.broadcasted_iota(jnp.int32, (1, H), 1)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    bm, cm = b_ref[...], c_ref[...]  # (c, N)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=F32)
    dtc, ac = dtc_ref[...], ac_ref[...]  # (c, H)
    for s in range(hb * P // W):
        cols = slice(s * W, (s + 1) * W)
        xs = x_ref[:, cols]
        xf = xs.astype(F32)
        intra = jnp.zeros((c, W), F32)
        upd = jnp.zeros((bm.shape[1], W), F32)
        ea = jnp.zeros((c, W), F32)
        e_last = jnp.zeros((1, W), F32)
        d_row = jnp.zeros((1, W), F32)
        for k in range(W // P):
            local = s * (W // P) + k
            h = blk * hb + local
            pick = (lane_h == h).astype(F32)
            dt_col = jnp.sum(dtc * pick, axis=1, keepdims=True)  # (c, 1)
            a_col = jnp.sum(ac * pick, axis=1, keepdims=True)
            a_row = ar_ref[local:local + 1, :]  # (1, c)
            a_last = jnp.min(a_row, axis=1, keepdims=True)  # a falls: the last real row's
            L = jnp.where(t >= j, jnp.exp(jnp.minimum(a_col - a_row, 0.0)), 0.0)
            mine = (lane_w >= k * P) & (lane_w < (k + 1) * P)
            xm = jnp.where(mine, xf, 0.0)
            intra += jnp.dot((cb * L).astype(dt), (xm * dt_col).astype(dt),
                             preferred_element_type=F32)
            bw = (bm.astype(F32) * (jnp.exp(a_last - a_col) * dt_col)).astype(dt)
            upd += jax.lax.dot_general(bw, xm.astype(dt), (((0,), (0,)), ((), ())),
                                       preferred_element_type=F32)
            ea = jnp.where(mine, jnp.exp(a_col), ea)
            e_last = jnp.where(mine, jnp.exp(a_last), e_last)
            d_row = jnp.where(mine, d_ref[h], d_row)
        S = s_ref[:, cols]
        s_hi = S.astype(dt)
        s_lo = (S - s_hi.astype(F32)).astype(dt)
        inter = (jnp.dot(cm, s_hi, preferred_element_type=F32)
                 + jnp.dot(cm, s_lo, preferred_element_type=F32))
        y_ref[:, cols] = intra + ea * inter + d_row * xf
        s_ref[:, cols] = e_last * S + upd


def _ssd_block(H: int, P: int) -> tuple[int, int]:
    """(heads a grid step, lanes a slab) for ``H`` heads of ``P``: up to 512
    columns a step, heads side by side in slabs of a whole lane tile."""
    hb = max(1, min(H, 512 // P))
    while H % hb:
        hb -= 1
    return hb, min(hb * P, max(P, LANES))


def ssd_chunk(x, dt, A, B, C, D, s0, n_real, sub: int = SUB):
    """A prefill chunk of one Mamba-2 layer as one kernel. x (T, H, P) in
    the model's type; dt (T, H) float32 (after its softplus); A, D (H,)
    float32; B, C (T, N) in the model's type; s0 (N, H * P) float32, the
    state before the chunk; ``n_real`` int32 scalar, the rows that are
    somebody's. Returns (y (T, H, P) float32, the state after row ``n_real -
    1``).

    Grid (blocks of heads, sub-chunks of ``min(sub, T)`` rows), the
    sub-chunks sequential: a block's state stays in VMEM (the state's output
    block, revisited) from the chunk's first row to its last. ``C B^T`` is
    computed once a grid step for the block; the decay mask, the masked
    product, ``C S_0`` and the state's update once a head. The products run
    in the operands' type with float32 accumulation, ``a`` and ``L`` in
    float32, and ``S_0`` enters ``C S_0`` as two parts of the operands' type
    (its rounding and the rest), so the state is never rounded. Rows from
    ``n_real`` on neither decay the state nor add to it."""
    c = min(sub, -(-x.shape[0] // 16) * 16)
    return _ssd_chunk_call(x, dt, A, B, C, D, s0, jnp.asarray(n_real, jnp.int32), c=c,
                           interpret=interpret_mode_default())


# A program calls these once a Mamba-2 layer at the same shapes: under their
# own jit the kernel is traced and lowered once a program, not once a layer.
@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _ssd_chunk_call(x, dt, A, B, C, D, s0, n_real, *, c: int, interpret):
    T, H, P = x.shape
    N = B.shape[1]
    pad = -T % c
    dt, a = _ssd_inputs(dt, A, n_real, T, c)
    hb, W = _ssd_block(H, P)
    x2 = jnp.pad(x.reshape(T, H * P), ((0, pad), (0, 0)))
    B, C = (jnp.pad(z, ((0, pad), (0, 0))) for z in (B, C))
    rows = pl.BlockSpec((c, hb * P), lambda h, i, *_: (i, h))
    per_row = pl.BlockSpec((c, H), lambda h, i, *_: (i, 0))
    shared = pl.BlockSpec((c, N), lambda h, i, *_: (i, 0))
    state = pl.BlockSpec((N, hb * P), lambda h, i, *_: (0, h))
    y, S = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, hb=hb, P=P, W=W, dt=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the real rows, the heads' D
            grid=(H // hb, (T + pad) // c),
            in_specs=[rows, per_row, per_row,
                      pl.BlockSpec((hb, c), lambda h, i, *_: (h, i)), shared, shared, state],
            out_specs=[rows, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((T + pad, H * P), F32),
                   jax.ShapeDtypeStruct((N, H * P), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(jnp.reshape(n_real, (1,)), D.astype(F32), x2, dt, a, a.T, B, C, s0.astype(F32))
    return y[:T].reshape(T, H, P), S


def _ssd_step_kernel(order_ref, n_ref, e_ref, xdt_ref, dx_ref, bt_ref, ct_ref, s_in, y_ref,
                     s_out, buf, sems, *, slab: int):
    """Grid step ``k`` advances the ``k``-th active slot (``order[k]``) in
    ``buf[k % 2]``: its state was fetched a step before, the next one's
    fetch starts under this one's arithmetic, and its write back under the
    next one's."""
    k = pl.program_id(0)
    n = n_ref[0]
    fetch = lambda i: pltpu.make_async_copy(s_in.at[order_ref[i]], buf.at[i % 2],
                                            sems.at[i % 2, 0])
    store = lambda i: pltpu.make_async_copy(buf.at[i % 2], s_out.at[order_ref[i]],
                                            sems.at[i % 2, 1])

    @pl.when(k == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(n > 0)
        def _():
            fetch(0).start()

    @pl.when(k < n)
    def _():
        slot = order_ref[k]

        @pl.when(k >= 1)
        def _():
            store(k - 1).wait()  # buf[(k + 1) % 2] is free again

        @pl.when(k + 1 < n)
        def _():
            fetch(k + 1).start()

        fetch(k).wait()
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bt_ref.shape[1]), 1)
        pick = (lane == slot).astype(F32)
        b_col = jnp.sum(bt_ref[...] * pick, axis=1, keepdims=True)  # (N, 1)
        c_col = jnp.sum(ct_ref[...] * pick, axis=1, keepdims=True)
        for s in range(buf.shape[2] // slab):
            cols = pl.ds(s * slab, slab)
            S = buf[k % 2, :, cols]
            S = e_ref[pl.ds(slot, 1), cols] * S + b_col * xdt_ref[pl.ds(slot, 1), cols]
            buf[k % 2, :, cols] = S
            y_ref[pl.ds(slot, 1), cols] = (jnp.sum(c_col * S, axis=0, keepdims=True)
                                           + dx_ref[pl.ds(slot, 1), cols])
        store(k).start()

        @pl.when(k == n - 1)
        def _():
            store(k).wait()


def ssd_step(x, dt, A, B, C, D, S, active):
    """One decode step of every slot of one Mamba-2 layer as one kernel.
    x (B, H, P); dt (B, H) float32; A, D (H,) float32; B, C (B, N); S
    (slots, N, H * P) float32, aliased in and out; ``active`` (B,) bool.
    An active slot's state is read once and written once, ``S' = exp(dt A)
    S + B^T (dt x)``, and its row is ``y = C S' + D x``; an inactive slot's
    state is neither read nor written (its ``y`` row is zeros). The conv
    step, the gate and the norm are the caller's. Returns (y (B, H, P)
    float32, S', the slots whose state it moved)."""
    return _ssd_step_call(x, dt, A, B, C, D, S, active, interpret=interpret_mode_default())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_step_call(x, dt, A, B, C, D, S, active, *, interpret):
    b, H, P = x.shape
    rep = lambda z: jnp.repeat(z.astype(F32), P, axis=-1)  # (.., H) -> (.., H * P)
    xf = x.astype(F32).reshape(b, H * P)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)  # the active slots first
    n = jnp.sum(active, dtype=jnp.int32)
    whole = lambda shape: pl.BlockSpec(shape, lambda k, *_: (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    N = B.shape[1]
    y, S = pl.pallas_call(
        functools.partial(_ssd_step_kernel, slab=min(H * P, 4 * LANES)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # which slots, how many
            grid=(b,),
            in_specs=[whole((b, H * P))] * 3 + [whole((N, b))] * 2 + [hbm],
            out_specs=[whole((b, H * P)), hbm],
            scratch_shapes=[pltpu.VMEM((2,) + S.shape[1:], F32), pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, H * P), F32), jax.ShapeDtypeStruct(S.shape, F32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssd_step",
    )(order, jnp.reshape(n, (1,)), rep(jnp.exp(dt * A.astype(F32))), rep(dt) * xf,
      rep(jnp.broadcast_to(D.astype(F32), dt.shape)) * xf, B.astype(F32).T, C.astype(F32).T, S)
    return y.reshape(b, H, P), S, n
