"""Fused per-block decode kernels (the megakernel's generated groups).

Reference: the megakernel's task types — rmsnorm/linear/activation fused into
one persistent kernel per model (``mega_triton_kernel/tasks/*``,
``core/code_generator.py:101-180``). TPU: one Pallas kernel per decode block;
weights stream HBM→VMEM exactly once and no intermediate touches HBM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.flash_attn import LANES, NEG_INF
from triton_dist_tpu.runtime.platform import interpret_mode_default


def _rmsnorm_rows(x32: jax.Array, w32: jax.Array, eps: float, out_dtype):
    """Qwen3 RMSNorm, matching layers.tp.RMSNorm bit-for-bit: normalize in
    f32, cast to model dtype, THEN scale by the weight."""
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(out_dtype)
    return normed * w32.astype(out_dtype)


def _mlp_block_kernel(x_ref, lnw_ref, wg_ref, wu_ref, wd_ref, o_ref, xn, acc,
                      *, eps: float, n_f: int, residual: bool):
    fi = pl.program_id(0)

    @pl.when(fi == 0)
    def _():
        xn[...] = _rmsnorm_rows(
            x_ref[...].astype(jnp.float32), lnw_ref[0], eps, xn.dtype
        )
        acc[...] = jnp.zeros_like(acc)

    g = jnp.dot(xn[...], wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(xn[...], wu_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(xn.dtype)
    acc[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(fi == n_f - 1)
    def _():
        out = acc[...]
        if residual:
            out = out + x_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def fused_mlp_block(
    x: jax.Array,  # (B, d) block input (pre-norm residual stream)
    ln_w: jax.Array,  # (d,)
    w_gate: jax.Array,  # (d, ff)
    w_up: jax.Array,  # (d, ff)
    w_down: jax.Array,  # (ff, d)
    *,
    eps: float = 1e-6,
    block_f: int | None = None,
    residual: bool = False,
    vmem_limit_mb: int | None = 100,
) -> jax.Array:
    """RMSNorm → gate/up → SwiGLU → down in ONE kernel: a single sweep over
    the ff dimension with the (B, d) f32 output accumulating in VMEM. Each
    weight tile is read exactly once and no intermediate ever visits HBM —
    the decode-MLP task group of the generated megakernel. Output is the
    down-projection partial (caller all-reduces over tp); ``residual`` adds
    x before the final cast (fusing the skip connection too)."""
    from triton_dist_tpu.kernels.gemm import fit_block

    b, d = x.shape
    ff = w_gate.shape[1]
    if block_f is None:
        # On-chip sweep (v5e, d=4096 ff=12288): bsz=1 peaks at 512-wide
        # tiles (793 GB/s vs 742 at 384); bsz>=8 prefers 768 (766 GB/s).
        block_f = 512 if b <= 4 else 768
    bf = fit_block(ff, block_f)
    n_f = ff // bf

    return pl.pallas_call(
        functools.partial(_mlp_block_kernel, eps=eps, n_f=n_f, residual=residual),
        grid=(n_f,),
        in_specs=[
            pl.BlockSpec((b, d), lambda fi: (0, 0)),
            pl.BlockSpec((1, d), lambda fi: (0, 0)),
            pl.BlockSpec((d, bf), lambda fi: (0, fi)),
            pl.BlockSpec((d, bf), lambda fi: (0, fi)),
            pl.BlockSpec((bf, d), lambda fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((b, d), lambda fi: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, d), x.dtype),
            pltpu.VMEM((b, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_mb * 1024 * 1024 if vmem_limit_mb else None,
        ),
        interpret=interpret_mode_default(),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * d * ff,
            bytes_accessed=3 * d * ff * w_gate.dtype.itemsize + 2 * b * d * x.dtype.itemsize,
            transcendentals=b * ff,
        ),
    )(x, ln_w.reshape(1, d), w_gate, w_up, w_down)


def _ln_qkv_rope_kernel(x_ref, lnw_ref, w_ref, qn_ref, kn_ref, pos_ref,
                        o_ref, xn_sc, cos_sc, sin_sc, *, eps, hq, hkv, hd,
                        theta, n_heads_tile):
    """One grid step = one (B, bc) column tile of the fused projection, so
    the Mosaic pipeliner overlaps the next weight-tile DMA with this tile's
    MXU work (a monolithic grid=(1,) load left ~20 % of HBM bandwidth idle
    at decode shapes). Tile width divides every head-type segment, so each
    step is uniformly q, k, or v typed (static thresholds, dynamic pid)."""
    pid = pl.program_id(0)
    nh = n_heads_tile
    nq_t = hq // nh  # tiles spanning the q segment
    nk_t = hkv // nh

    @pl.when(pid == 0)
    def _():
        # Normed input and rope phases are tile-invariant: compute once.
        xn_sc[...] = _rmsnorm_rows(
            x_ref[...].astype(jnp.float32), lnw_ref[0], eps, x_ref.dtype
        )
        half_ = hd // 2
        # Mosaic iota must be integer-typed; cast for the fp exponent.
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, half_), 1).astype(jnp.float32)
        freqs = theta ** (-iota / half_)
        angles = pos_ref[...].astype(jnp.float32) * freqs  # (B, half)
        cos_sc[...] = jnp.cos(angles)
        sin_sc[...] = jnp.sin(angles)

    # Round the projection to model dtype BEFORE the head norms — the layer
    # path does (TP_Attn.decode: dot().astype(x.dtype) then _split_qkv), and
    # bf16 parity with the other backends requires the same rounding point.
    qkv = jnp.dot(xn_sc[...], w_ref[...], preferred_element_type=jnp.float32).astype(
        x_ref.dtype
    ).astype(jnp.float32)  # (B, nh*hd)

    b = qkv.shape[0]
    half = hd // 2
    cos = cos_sc[...][:, None, :]  # (B, 1, half)
    sin = sin_sc[...][:, None, :]

    hh = qkv.reshape(b, nh, hd)
    is_q = pid < nq_t
    is_v = pid >= nq_t + nk_t
    # Per-head RMSNorm then rotate-half RoPE, matching layers.tp._split_qkv
    # + apply_rope exactly (norm before rope; product in model dtype).
    nw = jnp.where(is_q, qn_ref[...], kn_ref[...])  # (1, hd)
    var = jnp.mean(hh * hh, axis=-1, keepdims=True)
    normed = (
        (hh * jax.lax.rsqrt(var + eps)).astype(x_ref.dtype)
        * nw[None].astype(x_ref.dtype)
    ).astype(jnp.float32)
    x1, x2 = normed[..., :half], normed[..., half:]
    roped = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.where(is_v, hh, roped)  # v tiles pass the raw projection through
    o_ref[...] = out.reshape(b, nh * hd).astype(o_ref.dtype)


def fused_ln_qkv_rope(
    x: jax.Array,  # (B, d)
    ln_w: jax.Array,  # (d,)
    wqkv: jax.Array,  # (d, (hq + 2*hkv) * hd)
    q_norm: jax.Array,  # (hd,)
    k_norm: jax.Array,  # (hd,)
    pos: jax.Array,  # (B,) int32 absolute positions
    *,
    num_q_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e6,
    eps: float = 1e-6,
    vmem_limit_mb: int | None = 100,
):
    """RMSNorm → QKV projection → per-head q/k RMSNorm → RoPE in ONE kernel
    (the attention-front task group). Returns q (B, hq·hd), k, v (B, hkv·hd)
    flat — callers reshape to heads for the cache/attention (free in XLA)."""
    b, d = x.shape
    hq, hkv, hd = num_q_heads, num_kv_heads, head_dim
    cols = (hq + 2 * hkv) * hd
    assert wqkv.shape == (d, cols), (wqkv.shape, (d, cols))

    # Tile width must divide each head-type segment so every grid step is
    # uniformly typed: nh | gcd(hq, hkv), capped so a (d, nh*hd) weight tile
    # stays in the single-digit-MB DMA sweet spot.
    g = math.gcd(hq, hkv)
    fits = [c for c in range(g, 0, -1) if g % c == 0 and c * hd <= 1024]
    # Prefer a lane-aligned column tile (nh*hd % 128 == 0) — an unaligned
    # BlockSpec width pads badly (or is rejected) under Mosaic even when
    # interpret mode accepts it; fall back to the widest fit otherwise.
    aligned = [c for c in fits if (c * hd) % 128 == 0]
    nh = (aligned or fits or [1])[0]
    bc = nh * hd
    n_c = cols // bc

    flat = pl.pallas_call(
        functools.partial(
            _ln_qkv_rope_kernel, eps=eps, hq=hq, hkv=hkv, hd=hd,
            theta=rope_theta, n_heads_tile=nh,
        ),
        grid=(n_c,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, bc), lambda i: (0, i)),
            pl.BlockSpec((1, hd), lambda i: (0, 0)),
            pl.BlockSpec((1, hd), lambda i: (0, 0)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, bc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, cols), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, d), x.dtype),
            pltpu.VMEM((b, hd // 2), jnp.float32),
            pltpu.VMEM((b, hd // 2), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_mb * 1024 * 1024 if vmem_limit_mb else None,
        ),
        interpret=interpret_mode_default(),
    )(x, ln_w.reshape(1, d), wqkv, q_norm.reshape(1, hd), k_norm.reshape(1, hd),
      pos.reshape(b, 1).astype(jnp.float32))
    q = flat[:, : hq * hd]
    k = flat[:, hq * hd : (hq + hkv) * hd]
    v = flat[:, (hq + hkv) * hd :]
    return q, k, v


def _moe_block_kernel(xe_ref, wg_ref, wu_ref, wd_ref, y_ref, acc, *, n_f: int):
    f_i = pl.program_id(1)

    @pl.when(f_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    x = xe_ref[0]  # (C, d)
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f_i == n_f - 1)
    def _():
        y_ref[0] = acc[...]


def fused_moe_block(
    xe: jax.Array,  # (E, C, d) capacity-padded dispatched token panels
    w_gate: jax.Array,  # (E, d, ff)
    w_up: jax.Array,  # (E, d, ff)
    w_down: jax.Array,  # (E, ff, d)
    *,
    block_f: int | None = None,
    vmem_limit_mb: int | None = 100,
) -> jax.Array:
    """Routed-experts panel compute in ONE kernel: per expert, gate/up →
    SwiGLU → down with the f32 (C, d) accumulator resident in VMEM and the
    SwiGLU intermediate never touching HBM — the mega backend's ``moe``
    task group (BEYOND the reference megakernel, which is dense-only:
    ``mega_triton_kernel/models/model_builder.py``). Each expert's weight
    tiles stream exactly once; grid order (expert, ff-tile) keeps one
    expert's accumulator live at a time. Returns f32 (E, C, d) down-GEMM
    partials — the caller all-reduces over tp and runs the weighted
    unpermute, exactly ``TP_MoE``'s rounding points."""
    from triton_dist_tpu.kernels.gemm import fit_block

    e, cap, d = xe.shape
    ff = w_gate.shape[-1]
    if block_f is None:
        block_f = 512
    bf = fit_block(ff, block_f)
    n_f = ff // bf

    return pl.pallas_call(
        functools.partial(_moe_block_kernel, n_f=n_f),
        grid=(e, n_f),
        in_specs=[
            pl.BlockSpec((1, cap, d), lambda ei, fi: (ei, 0, 0)),
            pl.BlockSpec((1, d, bf), lambda ei, fi: (ei, 0, fi)),
            pl.BlockSpec((1, d, bf), lambda ei, fi: (ei, 0, fi)),
            pl.BlockSpec((1, bf, d), lambda ei, fi: (ei, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, cap, d), lambda ei, fi: (ei, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((e, cap, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cap, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_mb * 1024 * 1024 if vmem_limit_mb else None,
        ),
        interpret=interpret_mode_default(),
        cost_estimate=pl.CostEstimate(
            flops=e * (6 * cap * d * ff),
            bytes_accessed=3 * e * d * ff * w_gate.dtype.itemsize
            + 2 * e * cap * d * xe.dtype.itemsize,
            transcendentals=e * cap * ff,
        ),
    )(xe, w_gate, w_up, w_down)


def _attn_back_kernel(
    lengths_ref,  # SMEM (B,)
    q_ref,  # (1, 1, group, d)
    kn_ref,  # (1, 1, d) — new K token for this (b, kv head)
    vn_ref,  # (1, 1, d)
    k_ref,  # (1, 1, bk, d) — cache block (pre-append)
    v_ref,  # (1, 1, bk, d)
    wo_ref,  # (group*d, n) — o-proj rows for this kv head's query group
    o_ref,  # (B, n) f32 — o-proj partial (pre-allreduce)
    acc_scr,  # VMEM (group, d) f32
    m_scr,  # VMEM (group, LANES) f32
    l_scr,  # VMEM (group, LANES) f32
    out_acc,  # VMEM (B, n) f32
    *,
    scale: float,
    block_k: int,
    n_kv: int,
    nb: int,
    nh: int,
    group: int,
    hd: int,
):
    h = pl.program_id(0)
    bi = pl.program_id(1)
    ik = pl.program_id(2)
    length = lengths_ref[bi]

    @pl.when((h == 0) & (bi == 0) & (ik == 0))
    def _():
        out_acc[...] = jnp.zeros_like(out_acc)

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(ik * block_k < length + 1)  # +1: the appended token is valid
    def _():
        q = q_ref[0, 0]  # (group, d)
        kblk = k_ref[0, 0]  # (bk, d)
        vblk = v_ref[0, 0]
        # In-kernel KV append: the new token lands in cache slot `length`;
        # if this block covers it, splice the row into the VMEM tile. The
        # sweep then runs the EXACT math of append-then-attend (same block
        # order, same mask) so results are bit-identical to the standalone
        # cache_update → flash_decode pair — while the HBM cache append
        # happens elsewhere as a 1-row scatter that no longer gates the
        # attention sweep. Full-cache boundary (length == S): JAX scatters
        # DROP out-of-bounds updates, so the standalone cache_update drops
        # the new token; here `row == S − ik·block_k` then lands outside
        # every block and the splice likewise inserts nowhere — the two
        # lowerings agree bit-for-bit (boundary-tested in
        # test_fused_attn_back_matches_composition).
        row = length - ik * block_k
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        insert = row_ids == row
        kblk = jnp.where(insert, kn_ref[0], kblk)
        vblk = jnp.where(insert, vn_ref[0], vblk)

        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group, bk)
        k_ids = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_ids < length + 1, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape
        )
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # Round to model dtype exactly where the standalone flash_decode
        # writes its output, then feed the o-projection without an HBM trip.
        o_tile = (acc_scr[...] / l_safe).astype(q_ref.dtype)  # (group, d)
        part = jnp.dot(
            o_tile.reshape(1, group * hd), wo_ref[...],
            preferred_element_type=jnp.float32,
        )
        out_acc[pl.ds(bi, 1), :] = out_acc[pl.ds(bi, 1), :] + part

    @pl.when((h == nh - 1) & (bi == nb - 1) & (ik == n_kv - 1))
    def _():
        o_ref[...] = out_acc[...]


def fused_attn_back(
    q: jax.Array,  # (B, Hq, D) — roped decode queries
    k_new: jax.Array,  # (B, Hkv, D) — this step's K token (pre-append)
    v_new: jax.Array,  # (B, Hkv, D)
    k_cache: jax.Array,  # (B, Hkv, S, D) — cache BEFORE this step's append
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) int32 valid length BEFORE the append
    wo: jax.Array,  # (Hq*D, n) — o-projection shard (TP rows)
    *,
    scale: float | None = None,
    block_k: int | None = None,
    vmem_limit_mb: int | None = 100,
) -> jax.Array:
    """cache_update → flash_decode → o-proj partial in ONE kernel (the
    attention back-leg task group; reference
    ``mega_triton_kernel/tasks/flash_decode.py`` + ``core/code_generator.py``
    :158-166 lower these as consecutive tasks of the persistent kernel).

    The new token's K/V rows are spliced into the cache tile **in VMEM**
    (bit-identical to appending first), the online-softmax sweep runs over
    the cache, and each (batch, kv-head)'s normalized output feeds the
    o-projection accumulation while ``wo``'s row panel for that head group
    streams in exactly once per head. Returns the f32 o-proj PARTIAL
    (B, n) — the caller all-reduces over tp and adds the residual; the HBM
    cache append stays the caller's in-place 1-row scatter, now off the
    attention critical path."""
    b, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    assert hq % hkv == 0
    group = hq // hkv
    n = wo.shape[1]
    assert wo.shape[0] == hq * d, (wo.shape, hq, d)
    scale = scale if scale is not None else d ** -0.5
    from triton_dist_tpu.kernels.flash_decode import flash_decode_config_for
    from triton_dist_tpu.kernels.gemm import fit_block

    if block_k is None:
        # Same tune-cache key as the standalone flash_decode — both
        # lowerings of the attention back-leg land on the same swept block
        # (bit-parity requires identical partitioning).
        block_k = flash_decode_config_for(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        )
    block_k = fit_block(s, block_k)
    n_kv = s // block_k

    qr = q.reshape(b, hkv, group, d)

    return pl.pallas_call(
        functools.partial(
            _attn_back_kernel, scale=scale, block_k=block_k, n_kv=n_kv,
            nb=b, nh=hkv, group=group, hd=d,
        ),
        grid=(hkv, b, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, d), lambda h, bi, ik: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h, bi, ik: (bi, h, 0)),
            pl.BlockSpec((1, 1, d), lambda h, bi, ik: (bi, h, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda h, bi, ik: (bi, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda h, bi, ik: (bi, h, ik, 0)),
            pl.BlockSpec((group * d, n), lambda h, bi, ik: (h, 0)),
        ],
        out_specs=pl.BlockSpec((b, n), lambda h, bi, ik: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((b, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_mb * 1024 * 1024 if vmem_limit_mb else None,
        ),
        interpret=interpret_mode_default(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * hq * s * d * 2 + 2 * b * hq * d * n,
            bytes_accessed=(
                2 * b * hkv * s * d * k_cache.dtype.itemsize
                + hq * d * n * wo.dtype.itemsize
            ),
            transcendentals=b * hq * s,
        ),
    )(lengths.astype(jnp.int32), qr, k_new, v_new, k_cache, v_cache, wo)


def fused_paged_attn_back(
    q: jax.Array,  # (B, Hq, D) — roped decode queries
    k_new: jax.Array,  # (B, Hkv, D) — this step's K token
    v_new: jax.Array,  # (B, Hkv, D)
    pk: jax.Array,  # (L, num_blocks, Hkv, bs, D) — stacked block pool
    pv: jax.Array,
    li: int,  # layer index into the pool's leading dim
    tables: jax.Array,  # (B, max_blocks) int32 physical block ids
    lengths: jax.Array,  # (B,) int32 valid length BEFORE this step
    active: jax.Array,  # (B,) bool — serving slot mask (DATA, not shape)
    wo: jax.Array,  # (Hq*D, n) — o-projection shard (TP rows)
    *,
    scale: float | None = None,
):
    """Paged attention back-leg: pool scatter → block-table walk →
    o-projection partial, the serving-shaped analog of ``fused_attn_back``.

    The table walk IS the Pallas kernel here (``paged_flash_decode``: the
    stacked pool stays in HBM, the layer's index and the tables are scalar
    operands, tiles of pages are DMA'd by the table); the one-row scatter
    (``paged_kv_append``) and the o-proj GEMM ride the same jit step.
    Unlike the contiguous leg there is no in-VMEM splice — a paged write
    lands at ``tables[b, pos//bs]`` which only the same step's walk reads,
    so scatter-then-attend IS append-then-attend. The walk's tile resolves
    like the contiguous sweep's ``block_k`` (pin, tune cache, 256), so the
    two share one accumulation partition and this path is
    bitwise-comparable with the contiguous decode (the megakernel parity
    contract, docs/megakernel.md).

    ``active`` is per-slot DATA: inactive slots redirect their write to the
    reserved NULL block 0 (a freed slot's old blocks may already belong to
    another tenant — the contiguous mode's "harmless junk write" would be
    cross-slot corruption here) and attend only their frozen ``lengths``
    rows. Returns ``(o_proj_partial (B, n) f32, pk', pv')``; the caller
    all-reduces the partial over tp and adds the residual.

    ``pk``/``pv`` may be ``QuantPool`` pairs (``models/quant.py``): the new
    token's rows are quantized ONCE, here, at append — payload and per-row
    scale scatter together, and the table walk dequantizes in-kernel. No
    stored row is ever re-quantized (the prefix-trie/CoW invariant), and
    the step stays one fused launch: quantize → scatter → walk all ride the
    same jit step."""
    from triton_dist_tpu.kernels.flash_decode import paged_flash_decode, paged_kv_append

    b, hq, d = q.shape
    pk, pv = paged_kv_append(pk, pv, li, k_new, v_new, tables, lengths, active)
    o = paged_flash_decode(
        q, pk, pv, tables, lengths + active.astype(lengths.dtype), layer=li,
        scale=scale,
    )
    part = jnp.dot(
        o.reshape(b, hq * d), wo, preferred_element_type=jnp.float32
    )
    return part, pk, pv


def _norm_head_kernel(x_ref, nw_ref, w_ref, o_ref, xn, *, eps):
    vi = pl.program_id(0)

    @pl.when(vi == 0)
    def _():
        xn[...] = _rmsnorm_rows(
            x_ref[...].astype(jnp.float32), nw_ref[0], eps, xn.dtype
        )

    o_ref[...] = jnp.dot(xn[...], w_ref[...], preferred_element_type=jnp.float32)


def fused_norm_head(
    x: jax.Array,  # (B, d) residual stream after the last layer
    norm_w: jax.Array,  # (d,)
    lm_head: jax.Array,  # (d, V)
    *,
    eps: float = 1e-6,
    block_v: int = 1024,  # on-chip sweep: 744→749 GB/s (bsz=1), 727→818 (bsz=8)
    vmem_limit_mb: int | None = 100,
) -> jax.Array:
    """Final RMSNorm → lm_head projection in ONE kernel, streaming the
    vocab-column tiles once (the lm_head is lm-head-sized — ~268 MB at 8B
    widths — so its streaming efficiency matters as much as a layer's MLP).
    Returns f32 logits (B, V)."""
    from triton_dist_tpu.kernels.gemm import fit_block

    b, d = x.shape
    v = lm_head.shape[1]
    bv = fit_block(v, block_v)
    n_v = v // bv

    return pl.pallas_call(
        functools.partial(_norm_head_kernel, eps=eps),
        grid=(n_v,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, bv), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b, bv), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, d), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_mb * 1024 * 1024 if vmem_limit_mb else None,
        ),
        interpret=interpret_mode_default(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * d * v,
            bytes_accessed=d * v * lm_head.dtype.itemsize + 4 * b * v,
            transcendentals=0,
        ),
    )(x, norm_w.reshape(1, d), lm_head)
