"""Pallas flash attention (prefill) with GQA and causal masking.

TPU-native design (not a port of the reference's triton flash kernels): grid
``(batch*q_heads, q_blocks, kv_blocks)`` with the KV dimension innermost and
"arbitrary" semantics; online-softmax running max/sum live in VMEM scratch as
``(block_q, LANES)`` tiles (the VPU-friendly layout). GQA is folded into the
BlockSpec index maps — a q head reads its kv head's block directly, no
materialised head broadcast. Optionally returns the log-sum-exp, the hook the
distributed decode / ring-attention combines need (reference
``kernels/nvidia/flash_decode.py:308-566`` combine path).

Block sizing (measured, v5e bf16 GQA causal): 1024×1024 tiles run
3.5-4.3× faster than 256×256 (27 → 81 TFLOP/s at s=2048; 26 → 121 at
s=8192) — the online-softmax VPU work amortizes against much larger MXU
matmuls per tile. The softmax runs in the exp2 domain (log2(e) folded into
the score scale; both exponentials are native VPU exp2) and fully-below-
diagonal causal blocks skip the mask select entirely — worth ~3 % together.
``fit_block`` shrinks tiles for short sequences, so the large defaults are
safe everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime.platform import interpret_mode_default

LANES = 128
NEG_INF = -1e30
#: log2(e): folds nat-domain scores into the exp2-domain softmax everywhere.
LOG2E = 1.4426950408889634


# Re-exported for backward compatibility; canonical home is kernels/gemm.py.
from triton_dist_tpu.kernels.gemm import SUBLANES, fit_block  # noqa: E402,F401


def _flash_kernel(
    offs_ref,  # SMEM (2,) int32 [q_offset, kv_offset] or None (static offsets)
    q_ref,  # (1, bq, d)
    k_ref,  # (1, bk, d)
    v_ref,  # (1, bk, d)
    o_ref,  # (1, bq, d)
    lse_ref,  # (1, 1, bq) or None
    acc_scr,  # VMEM (bq, d) f32
    m_scr,  # VMEM (bq, LANES) f32
    l_scr,  # VMEM (bq, LANES) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_kv: int,
    kv_len: int,
    sq: int,
    pad_k: bool = False,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    if offs_ref is not None:
        # Dynamic global positions (ring attention): query rows start at
        # offs[0], keys at offs[1], in one shared coordinate system. Every
        # rank/step runs this same program — masking is data, not control
        # flow, so ring steps stay uniform across devices (no divergent
        # branches around the collective rendezvous).
        q_off = offs_ref[0] - offs_ref[1]  # relative offset: mask is q_off+qi >= ki
    else:
        q_off = kv_len - sq

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    # Softmax runs in the exp2 domain: fold log2(e) into the score scale once
    # per tile so both exponentials are native VPU exp2 ops with no extra
    # (bq, bk)-sized multiply (m/l scratch then hold base-2 logs; only the
    # final LSE converts back to nats).

    def compute(masked):
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        s *= scale * LOG2E

        if masked or pad_k:
            k_ids = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if masked:
            # End-aligned (KV-cache) convention: query row i sits at absolute
            # position q_off + iq*bq + i (q_off = kv_len - sq statically, or
            # the caller-supplied ring offset), so a prefill continuation
            # (sq < kv_len) still attends to the whole cached prefix.
            q_ids = q_off + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        if pad_k:
            # The wrapper padded K/V past kv_len to reach a Mosaic-legal
            # block; those columns are no keys in any coordinate system.
            s = jnp.where(k_ids < kv_len, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, LANES)
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp2(m_prev - m_new)  # (bq, LANES)
        p = jnp.exp2(s - m_new[:, :1])  # (bq, bk)
        if masked or pad_k:
            # A row with NO valid key yet has m_new == NEG_INF and would get
            # p = exp2(0) = 1 everywhere (→ mean(v) instead of 0). Re-mask
            # such rows, same guard as the varlen kernel. Reachable through
            # the public q_offset/kv_offset args (rows before the kv start).
            p = jnp.where(m_new[:, :1] <= NEG_INF * 0.5, 0.0, p)

        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape
        )
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Skip KV blocks entirely above the (end-aligned) diagonal, and run
        # blocks entirely below it without the mask select (the (bq, bk)
        # iota/compare/select is pure VPU overhead there). With dynamic
        # offsets this is runtime predication inside a uniform grid — all
        # devices still launch identical programs.
        first_q = q_off + iq * block_q
        crosses_diag = ik * block_k + block_k - 1 > first_q

        @pl.when(ik * block_k <= first_q + block_q - 1)
        def _():
            @pl.when(crosses_diag)
            def _():
                compute(masked=True)

            @pl.when(jnp.logical_not(crosses_diag))
            def _():
                compute(masked=False)
    else:
        compute(masked=False)

    @pl.when(ik == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zero output
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # m/l are base-2; LSE is published in nats (what the distributed
            # decode / ring combines expect).
            lse = (m_scr[:, 0] + jnp.log2(jnp.maximum(l_scr[:, 0], 1e-30))) / LOG2E
            lse_ref[0, 0] = lse.astype(lse_ref.dtype)


DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024


def flash_op_name(causal: bool) -> str:
    """Tune-cache op key — single source shared by the kernel lookup, the
    offline tuner, and tests (a drifting literal would silently degrade
    every lookup to the default blocks)."""
    return "flash_attn_causal" if causal else "flash_attn"


def flash_config_for(q_sds, k_sds, v_sds, causal: bool) -> tuple[int, int]:
    """Trace-time tuned-block lookup (offline ``tools.tune_gemm --flash``
    fills the cache, same discipline as ``gemm_config_for``; the cache key
    is the (q, k, v) signature ``tune_flash`` times with). Falls back to
    the measured 1024×1024 default.

    Multi-host contract (same as the reference's JSON tune cache): every
    process must see the SAME cache content — tuned blocks are baked into
    the traced program, so per-host divergence means divergent HLO inside
    one SPMD computation. Ship the cache file with the job (or point
    ``TDT_TUNE_CACHE`` at a shared path); tune offline, not mid-job."""
    from triton_dist_tpu.tools.tune import lookup

    hit = lookup(flash_op_name(causal), [q_sds, k_sds, v_sds])
    if hit:
        return int(hit["block_q"]), int(hit["block_k"])
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K


def flash_bwd_op_name(causal: bool) -> str:
    """Tune-cache op key for the backward kernels (dq + dk/dv)."""
    return "flash_attn_bwd_causal" if causal else "flash_attn_bwd"


def flash_bwd_config_for(q_sds, k_sds, v_sds, causal: bool) -> tuple[int, int]:
    """Trace-time tuned-block lookup for the backward (offline
    ``tools.tune_gemm --flash-bwd`` fills it; key = (q, k, v) signature,
    same multi-host ship-the-cache contract as :func:`flash_config_for`).
    Falls back to the forward's tuned blocks (bwd and fwd optima track each
    other on the swept shapes), then the 1024×1024 default."""
    from triton_dist_tpu.tools.tune import lookup

    hit = lookup(flash_bwd_op_name(causal), [q_sds, k_sds, v_sds])
    if hit:
        return int(hit["block_q"]), int(hit["block_k"])
    return flash_config_for(q_sds, k_sds, v_sds, causal)


def _legal_len(n: int, want: int, mult: int) -> int:
    """``n`` itself when ``fit_block(n, want)`` is a block Mosaic accepts on
    that dim (the whole dim, or a multiple of ``mult``); else ``n`` rounded
    up to the lane width, every divisor-block of which is legal."""
    blk = fit_block(n, want)
    if blk == n or blk % mult == 0:
        return n
    return -(-n // LANES) * LANES


def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    return_lse: bool = False,
    q_offset: jax.Array | None = None,
    kv_offset: jax.Array | None = None,
):
    """Flash attention forward. Returns ``o`` (B, Hq, Sq, D), plus the
    log-sum-exp (B, Hq, Sq) when ``return_lse`` (fp32).

    ``q_offset``/``kv_offset`` (traced int32 scalars) place the Q rows and KV
    columns in a shared global coordinate system for causal masking — the
    ring-attention hook: every ring step calls the *same* program with a
    step-dependent offset, keeping all devices' control flow uniform (the
    reference's consumer is likewise uniform, ``sp_ag_attention_intra_node.py:257``).
    A fully-masked shard yields o=0 and lse≈-inf, which the LSE merge weights
    to zero."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    if block_q is None or block_k is None:
        tuned_q, tuned_k = flash_config_for(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            causal,
        )
        block_q = tuned_q if block_q is None else block_q
        block_k = tuned_k if block_k is None else block_k
    # Arbitrary lengths (a server's prompts): when no divisor of the length
    # is a legal Mosaic block, pad the sequence dim and slice the output.
    # Positions keep the ORIGINAL lengths (q_off = sk - sq); padded K columns
    # are masked in-kernel, padded Q rows are computed and dropped.
    sq_p = _legal_len(sq, block_q, LANES if return_lse else SUBLANES)
    sk_p = _legal_len(sk, block_k, SUBLANES)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    block_q = fit_block(sq_p, block_q)
    block_k = fit_block(sk_p, block_k)
    n_kv = sk_p // block_k

    qr = q.reshape(b * hq, sq_p, d)
    kr = k.reshape(b * hkv, sk_p, d)
    vr = v.reshape(b * hkv, sk_p, d)

    def kv_index(bh, iq_, ik_, *_):
        # q head bh = bi*hq + h → kv row bi*hkv + h // group
        return (bh // hq) * hkv + (bh % hq) // group, ik_, 0

    out_shape = [jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0))]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((b * hq, 1, sq_p), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik, *_: (bh, 0, iq)))

    dynamic = q_offset is not None or kv_offset is not None
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        n_kv=n_kv,
        kv_len=sk,
        sq=sq,
        pad_k=sk_p != sk,
    )
    if dynamic:
        if return_lse:
            kernel_fn = kernel
        else:
            kernel_fn = lambda offs, q_, k_, v_, o_, acc, m, l: kernel(
                offs, q_, k_, v_, o_, None, acc, m, l
            )
    else:
        if return_lse:
            kernel_fn = lambda q_, k_, v_, o_, lse_, acc, m, l: kernel(
                None, q_, k_, v_, o_, lse_, acc, m, l
            )
        else:
            kernel_fn = lambda q_, k_, v_, o_, acc, m, l: kernel(
                None, q_, k_, v_, o_, None, acc, m, l
            )

    grid = (b * hq, sq_p // block_q, n_kv)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
    ]
    operands = (qr, kr, vr)
    if dynamic:
        offs = jnp.array(
            [
                0 if q_offset is None else q_offset,
                0 if kv_offset is None else kv_offset,
            ],
            jnp.int32,
        )
        operands = (offs,) + operands
    res = pl.pallas_call(
        kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if dynamic else 0,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if return_lse else out_specs[0],
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape if return_lse else out_shape[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
        name="flash_attention",
    )(*operands)

    if return_lse:
        o, lse = res
        return (o.reshape(b, hq, sq_p, d)[:, :, :sq],
                lse.reshape(b, hq, sq_p)[:, :, :sq])
    return res.reshape(b, hq, sq_p, d)[:, :, :sq]


def _flash_varlen_kernel(
    offs_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref,
    acc_scr, m_scr, l_scr, *, scale, block_q, block_k, n_kv,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    iq = pl.program_id(1)
    # Ring offsets (see flash_attention's offs): the relative q−kv offset is
    # all the mask needs; segments already carry global positions.
    q_off = offs_ref[0] - offs_ref[1] if offs_ref is not None else 0

    # Packed-causal skip: same-segment keys are never ahead of the (global)
    # diagonal. With a dynamic offset this is runtime predication inside a
    # uniform grid — all ring ranks launch identical programs.
    @pl.when(ik * block_k <= q_off + iq * block_q + block_q - 1)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        # exp2-domain softmax, same retune as `_flash_kernel`: fold log2(e)
        # into the scale once so both exponentials are native VPU exp2 ops
        # (m/l scratch hold base-2 logs; the optional LSE output converts
        # to nats at the final step, matching the dense kernel).
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)
        mask = _varlen_mask(iq, ik, block_q, block_k, qseg_ref, kseg_ref,
                            q_off=q_off)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp2(m_prev - m_new)
        # Mask again after the exp: on a fully-masked row m_new == NEG_INF
        # and exp2(s - m_new) would be exp2(0) = 1, not 0.
        p = jnp.where(mask, jnp.exp2(s - m_new[:, :1]), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape
        )
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        empty = l == 0.0  # padding rows → zero output
        l = jnp.where(empty, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # m/l are base-2; publish nats. Padding rows get NEG_INF so the
            # backward's lse guard zeroes their p exactly.
            lse = (m_scr[:, 0] + jnp.log2(jnp.maximum(l_scr[:, 0], 1e-30))) / LOG2E
            lse_ref[0, 0] = jnp.where(empty[:, 0], NEG_INF, lse)


def flash_attention_varlen(
    q: jax.Array,  # (Hq, T, D) — packed sequences, total length T
    k: jax.Array,  # (Hkv, T, D)
    v: jax.Array,  # (Hkv, T, D)
    cu_seqlens: jax.Array,  # (N+1,) int32 monotonically increasing offsets
    *,
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    return_lse: bool = False,
    q_offset: jax.Array | None = None,
    kv_offset: jax.Array | None = None,
):
    """Varlen (cu_seqlens) causal flash attention over packed sequences —
    the reference's ``sp_ag_attention_intra_node.py`` varlen path. Tokens
    attend causally within their own segment only; rows in padding segments
    (beyond cu_seqlens[-1]) get zero output. Masking is data (segment-id
    equality), so the program stays uniform across any SPMD callers.

    ``q_offset``/``kv_offset`` (traced int32 scalars) place this call's Q
    rows and KV columns in the GLOBAL packed stream — the ring-attention
    hook, mirroring ``flash_attention``: ``cu_seqlens`` stays global, each
    ring step passes its shard offsets, and full / diagonal / skipped steps
    all run the same program (the mask is data)."""
    hq, t, d = q.shape
    hkv = k.shape[0]
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = fit_block(t, block_q)
    block_k = fit_block(t, block_k)
    n_kv = t // block_k
    dynamic = q_offset is not None or kv_offset is not None

    # One segment-id source for fwd AND bwd: a sentinel/side drift between
    # them would silently break gradients (saved LSE vs recomputed p).
    seg_q, seg_k = _varlen_segments(cu_seqlens, t, q_offset, kv_offset)

    def kv_index(bh, iq_, ik_, *_):
        return bh // group, ik_, 0

    out_specs = [pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((hq, t, d), q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik, *_: (bh, 0, iq)))
        out_shape.append(jax.ShapeDtypeStruct((hq, 1, t), jnp.float32))

    kernel = functools.partial(
        _flash_varlen_kernel, scale=scale, block_q=block_q,
        block_k=block_k, n_kv=n_kv,
    )
    if dynamic:
        kernel_fn = (kernel if return_lse else
                     (lambda *refs: kernel(*refs[:7], None, *refs[7:])))
    else:
        kernel_fn = (
            (lambda *refs: kernel(None, *refs)) if return_lse else
            (lambda *refs: kernel(None, *refs[:6], None, *refs[6:])))
    operands = (q, k, v, seg_q, seg_k)
    if dynamic:
        offs = jnp.array(
            [0 if q_offset is None else q_offset,
             0 if kv_offset is None else kv_offset], jnp.int32)
        operands = (offs,) + operands
    res = pl.pallas_call(
        kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if dynamic else 0,
            grid=(hq, t // block_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_q), lambda bh, iq, ik, *_: (0, iq)),
                pl.BlockSpec((1, block_k), lambda bh, iq, ik, *_: (0, ik)),
            ],
            out_specs=out_specs if return_lse else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=out_shape if return_lse else out_shape[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
        name="flash_attention_varlen",
    )(*operands)
    if return_lse:
        o, lse = res
        return o, lse.reshape(hq, t)
    return res


def attention_reference(q, k, v, *, causal=True, scale=None):
    """Unfused reference (the torch-eager analog used by reference tests)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    group = hq // hkv
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kx.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------------------ backward


def _bwd_p_ds(qq, kk, do_tile, v_tile, lse2_col, delta_col, sc, mask=None):
    """Shared backward tile math (dense AND varlen, dq AND dk/dv kernels):
    p recomputed exactly from the saved LSE in the exp2 domain, then
    ds = p∘(dp − δ)·scale. ONE implementation on purpose — this is the
    precision-sensitive core, and a fix must never need to land four times.
    Masked positions give exp2(−inf) = 0; rows whose whole step was masked
    (lse ≈ −inf) are forced to 0 so zero cotangents never meet an inf."""
    s2 = jax.lax.dot_general(
        qq, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (sc * LOG2E)
    if mask is not None:
        s2 = jnp.where(mask, s2, NEG_INF)
    p = jnp.exp2(s2 - lse2_col)
    p = jnp.where(lse2_col > NEG_INF * 0.5, p, 0.0)
    dp = jax.lax.dot_general(
        do_tile, v_tile, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_col) * sc
    return p, ds


def _causal_mask(q_off, iq, ik, block_q, block_k):
    """Dense causal mask in global coordinates (q rows offset by q_off)."""
    q_ids = q_off + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_ids = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_ids >= k_ids


def _varlen_mask(iq, ik, block_q, block_k, qseg_ref, kseg_ref, q_off=0):
    """Packed-segment mask: causal within the stream AND same segment.
    ``q_off`` (static 0 or traced ring offset q_offset−kv_offset) places the
    q rows relative to the visiting KV columns in the GLOBAL packed stream —
    the segment ids are already global (computed at offset positions), so
    the pair mask covers full/diagonal/fully-skipped ring steps uniformly."""
    q_ids = q_off + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_ids = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.logical_and(
        q_ids >= k_ids,
        qseg_ref[0].reshape(block_q, 1) == kseg_ref[0].reshape(1, block_k),
    )


def _flash_bwd_dq_kernel(
    offs_ref,  # SMEM (2,) int32 [q_offset, kv_offset] or None (static)
    lse2_ref,  # (1, 1, bq) f32 — saved LSE × log2(e)
    delta_ref,  # (1, 1, bq) f32 — Σ_d do·o − dlse
    q_ref,  # (1, bq, d)
    k_ref,  # (1, bk, d)
    v_ref,  # (1, bk, d)
    do_ref,  # (1, bq, d)
    dq_ref,  # (1, bq, d) out
    dq_scr,  # VMEM (bq, d) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_kv: int,
    kv_len: int,
    sq: int,
):
    """dq pass: same sweep as the forward, p recomputed exactly from the
    saved LSE (exp2 domain, no re-max), dq accumulated over kv blocks.
    Dynamic offsets keep every ring rank's program uniform, like the
    forward; fully-masked rows (lse ≈ -inf from a skipped ring step) are
    guarded to p = 0 so their zero cotangents never meet an inf."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    q_off = offs_ref[0] - offs_ref[1] if offs_ref is not None else kv_len - sq

    @pl.when(ik == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute(masked):
        kk = k_ref[0]
        mask = _causal_mask(q_off, iq, ik, block_q, block_k) if masked else None
        _, ds = _bwd_p_ds(
            q_ref[0], kk, do_ref[0], v_ref[0], lse2_ref[0, 0][:, None],
            delta_ref[0, 0][:, None], scale, mask,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        first_q = q_off + iq * block_q
        crosses = ik * block_k + block_k - 1 > first_q

        @pl.when(ik * block_k <= first_q + block_q - 1)
        def _():
            @pl.when(crosses)
            def _():
                compute(masked=True)

            @pl.when(jnp.logical_not(crosses))
            def _():
                compute(masked=False)
    else:
        compute(masked=False)

    @pl.when(ik == n_kv - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,
    o: jax.Array,  # (B, Hq, Sq, D) saved forward output
    lse: jax.Array,  # (B, Hq, Sq) saved log-sum-exp (nats)
    do: jax.Array,  # (B, Hq, Sq, D) output cotangent
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    q_offset: jax.Array | None = None,
    kv_offset: jax.Array | None = None,
    dlse: jax.Array | None = None,  # (B, Hq, Sq) LSE cotangent (ring merges)
):
    """Pallas flash-attention backward: two kernels (dq; dk/dv), O(S) memory,
    p recomputed exactly from the saved LSE in the exp2 domain (4.1× the XLA
    SDPA grad on-chip); the kernels lift the block matmuls onto the MXU with
    f32 (bq, bk) intermediates never touching HBM.

    ``q_offset``/``kv_offset`` mirror the forward's dynamic global positions
    (uniform ring programs). ``dlse`` is the LSE output's cotangent: it folds
    into the δ correction (ds = p∘(dp − δ + dlse)), which is how ring-merge
    gradients flow back through each step's partial. Returns (dq, dk, dv)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    sc = scale if scale is not None else d ** -0.5
    if block_q is None or block_k is None:
        tq, tk = flash_bwd_config_for(q, k, v, causal)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = fit_block(sq, block_q)
    block_k = fit_block(sk, block_k)
    n_q = sq // block_q
    n_kv = sk // block_k
    dynamic = q_offset is not None or kv_offset is not None

    lse2 = (lse.astype(jnp.float32) * LOG2E).reshape(b * hq, 1, sq)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).reshape(delta.shape)
    delta = delta.reshape(b * hq, 1, sq)
    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)
    dor = do.reshape(b * hq, sq, d)

    def kv_index(bh, iq_, ik_, *_):
        return (bh // hq) * hkv + (bh % hq) // group, ik_, 0

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=sc, causal=causal, block_q=block_q,
        block_k=block_k, n_kv=n_kv, kv_len=sk, sq=sq,
    )
    if dynamic:
        dq_kernel_fn = dq_kernel
        offs = jnp.array(
            [
                0 if q_offset is None else q_offset,
                0 if kv_offset is None else kv_offset,
            ],
            jnp.int32,
        )
        dq_operands = (offs, lse2, delta, qr, kr, vr, dor)
    else:
        dq_kernel_fn = lambda *refs: dq_kernel(None, *refs)
        dq_operands = (lse2, delta, qr, kr, vr, dor)

    dq = pl.pallas_call(
        dq_kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if dynamic else 0,
            grid=(b * hq, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik, *_: (bh, 0, iq)),
                pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik, *_: (bh, 0, iq)),
                pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)
            ),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
    )(*dq_operands)

    # dk/dv: innermost grid dim jj = gi * n_q + qi walks the GQA group and
    # the q blocks; all q-side operands index through jj.
    def q_row(bh, ik_, jj, *_):
        return bh * group + jj // n_q, jj % n_q, 0

    def q_scalar(bh, ik_, jj, *_):
        return bh * group + jj // n_q, 0, jj % n_q

    def dkv_wrapped(*refs):
        if dynamic:
            offs_ref, *refs = refs
        else:
            offs_ref = None
        (lse2_ref, delta_ref, q_ref, k_ref, v_ref, do_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        ik = pl.program_id(1)
        jj = pl.program_id(2)
        iq = jax.lax.rem(jj, n_q)
        q_off = offs_ref[0] - offs_ref[1] if offs_ref is not None else sk - sq
        n_inner_total = group * n_q

        @pl.when(jj == 0)
        def _():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        def compute(masked):
            qq = q_ref[0]
            mask = _causal_mask(q_off, iq, ik, block_q, block_k) if masked else None
            p, ds = _bwd_p_ds(
                qq, k_ref[0], do_ref[0], v_ref[0], lse2_ref[0, 0][:, None],
                delta_ref[0, 0][:, None], sc, mask,
            )
            dv_scr[...] += jax.lax.dot_general(
                p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_scr[...] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), qq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        if causal:
            first_q = q_off + iq * block_q
            # Skip q blocks whose every row precedes this kv block.
            any_pair = ik * block_k <= first_q + block_q - 1
            crosses = ik * block_k + block_k - 1 > first_q

            @pl.when(any_pair)
            def _():
                @pl.when(crosses)
                def _():
                    compute(masked=True)

                @pl.when(jnp.logical_not(crosses))
                def _():
                    compute(masked=False)
        else:
            compute(masked=False)

        @pl.when(jj == n_inner_total - 1)
        def _():
            dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    dkv_operands = (
        (offs, lse2, delta, qr, kr, vr, dor)
        if dynamic
        else (lse2, delta, qr, kr, vr, dor)
    )
    dk, dv = pl.pallas_call(
        dkv_wrapped,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if dynamic else 0,
            grid=(b * hkv, n_kv, group * n_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), q_scalar),
                pl.BlockSpec((1, 1, block_q), q_scalar),
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
                pl.BlockSpec((1, block_q, d), q_row),
            ],
            out_specs=(
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, sk, d), v.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
    )(*dkv_operands)

    return (
        dq.reshape(b, hq, sq, d),
        dk.reshape(b, hkv, sk, d),
        dv.reshape(b, hkv, sk, d),
    )


# ------------------------------------------------------- varlen backward


def _varlen_segments(cu_seqlens: jax.Array, t: int,
                     q_offset: jax.Array | None = None,
                     kv_offset: jax.Array | None = None):
    """Per-position segment ids; Q padding −1, K padding −2 (never match).
    ``q_offset``/``kv_offset`` shift the positions into the global packed
    stream (ring shards); cu_seqlens itself is always global."""

    def seg_at(offset, sentinel):
        pos = jnp.arange(t, dtype=jnp.int32)
        if offset is not None:
            pos = pos + jnp.asarray(offset, jnp.int32)
        seg = jnp.searchsorted(cu_seqlens[1:], pos, side="right").astype(jnp.int32)
        valid = pos < cu_seqlens[-1]
        return jnp.where(valid, seg, sentinel).reshape(1, t)

    return seg_at(q_offset, -1), seg_at(kv_offset, -2)


def flash_attention_varlen_bwd(
    q: jax.Array,  # (Hq, T, D) packed
    k: jax.Array,  # (Hkv, T, D)
    v: jax.Array,
    o: jax.Array,  # (Hq, T, D) saved forward output
    lse: jax.Array,  # (Hq, T) saved log-sum-exp (nats; NEG_INF on padding)
    do: jax.Array,  # (Hq, T, D) output cotangent
    cu_seqlens: jax.Array,
    *,
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    q_offset: jax.Array | None = None,
    kv_offset: jax.Array | None = None,
    dlse: jax.Array | None = None,  # (Hq, T) LSE cotangent (ring merges)
):
    """Varlen backward: the dense two-kernel (dq; dk/dv) structure with the
    packed-segment mask — ``(q_id ≥ k_id) ∧ (seg_q == seg_k)`` — replacing
    the causal-offset mask, p recomputed exactly from the saved LSE in the
    exp2 domain. Padding rows carry lse = NEG_INF and o = 0, so their p and
    δ vanish and they contribute nothing. Returns (dq, dk, dv).

    ``q_offset``/``kv_offset``/``dlse`` mirror the dense backward: global
    ring positions (uniform per-rank programs) and the LSE cotangent folded
    into δ, so varlen RING training gradients flow per step.

    Reference scope note: the reference's varlen attention lives inside its
    SP prefill path and is inference-only; this backward extends the varlen
    kernel to training (packed-sequence SFT), same discipline as the dense
    ``flash_attention_bwd``."""
    hq, t, d = q.shape
    hkv = k.shape[0]
    group = hq // hkv
    sc = scale if scale is not None else d ** -0.5
    block_q = fit_block(t, block_q)
    block_k = fit_block(t, block_k)
    n_q = t // block_q
    n_kv = t // block_k
    dynamic = q_offset is not None or kv_offset is not None

    seg_q, seg_k = _varlen_segments(cu_seqlens, t, q_offset, kv_offset)
    lse2 = (lse.astype(jnp.float32) * LOG2E).reshape(hq, 1, t)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).reshape(delta.shape)
    delta = delta.reshape(hq, 1, t)
    offs = (jnp.array(
        [0 if q_offset is None else q_offset,
         0 if kv_offset is None else kv_offset], jnp.int32)
        if dynamic else None)

    def kv_index(bh, iq_, ik_, *_):
        return bh // group, ik_, 0

    def dq_kernel(offs_ref, lse2_ref, delta_ref, q_ref, k_ref, v_ref, do_ref,
                  qseg_ref, kseg_ref, dq_ref, dq_scr):
        iq = pl.program_id(1)
        ik = pl.program_id(2)
        q_off = offs_ref[0] - offs_ref[1] if offs_ref is not None else 0

        @pl.when(ik == 0)
        def _():
            dq_scr[...] = jnp.zeros_like(dq_scr)

        # Packed-causal skip: same-segment keys never lie ahead of the
        # (global) diagonal of the packed stream.
        @pl.when(ik * block_k <= q_off + iq * block_q + block_q - 1)
        def _():
            kk = k_ref[0]
            _, ds = _bwd_p_ds(
                q_ref[0], kk, do_ref[0], v_ref[0], lse2_ref[0, 0][:, None],
                delta_ref[0, 0][:, None], sc,
                _varlen_mask(iq, ik, block_q, block_k, qseg_ref, kseg_ref,
                             q_off=q_off),
            )
            dq_scr[...] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), kk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(ik == n_kv - 1)
        def _():
            dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    dq_kernel_fn = dq_kernel if dynamic else (lambda *refs: dq_kernel(None, *refs))
    dq_operands = (lse2, delta, q, k, v, do, seg_q, seg_k)
    if dynamic:
        dq_operands = (offs,) + dq_operands
    dq = pl.pallas_call(
        dq_kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if dynamic else 0,
            grid=(hq, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik, *_: (bh, 0, iq)),
                pl.BlockSpec((1, 1, block_q), lambda bh, iq, ik, *_: (bh, 0, iq)),
                pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
                pl.BlockSpec((1, block_q), lambda bh, iq, ik, *_: (0, iq)),
                pl.BlockSpec((1, block_k), lambda bh, iq, ik, *_: (0, ik)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda bh, iq, ik, *_: (bh, iq, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((hq, t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
    )(*dq_operands)

    n_inner = group * n_q

    def q_row(bh, ik_, jj, *_):
        return bh * group + jj // n_q, jj % n_q, 0

    def q_scalar(bh, ik_, jj, *_):
        return bh * group + jj // n_q, 0, jj % n_q

    def qseg_row(bh, ik_, jj, *_):
        return 0, jj % n_q

    def dkv_kernel(offs_ref, lse2_ref, delta_ref, q_ref, k_ref, v_ref, do_ref,
                   qseg_ref, kseg_ref, dk_ref, dv_ref, dk_scr, dv_scr):
        ik = pl.program_id(1)
        jj = pl.program_id(2)
        iq = jax.lax.rem(jj, n_q)
        q_off = offs_ref[0] - offs_ref[1] if offs_ref is not None else 0

        @pl.when(jj == 0)
        def _():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        @pl.when(ik * block_k <= q_off + iq * block_q + block_q - 1)
        def _():
            qq = q_ref[0]
            p, ds = _bwd_p_ds(
                qq, k_ref[0], do_ref[0], v_ref[0], lse2_ref[0, 0][:, None],
                delta_ref[0, 0][:, None], sc,
                _varlen_mask(iq, ik, block_q, block_k, qseg_ref, kseg_ref,
                             q_off=q_off),
            )
            dv_scr[...] += jax.lax.dot_general(
                p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_scr[...] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), qq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(jj == n_inner - 1)
        def _():
            dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    dkv_kernel_fn = dkv_kernel if dynamic else (lambda *refs: dkv_kernel(None, *refs))
    dkv_operands = (lse2, delta, q, k, v, do, seg_q, seg_k)
    if dynamic:
        dkv_operands = (offs,) + dkv_operands
    dk, dv = pl.pallas_call(
        dkv_kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 if dynamic else 0,
            grid=(hkv, n_kv, n_inner),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), q_scalar),
                pl.BlockSpec((1, 1, block_q), q_scalar),
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
                pl.BlockSpec((1, block_q, d), q_row),
                pl.BlockSpec((1, block_q), qseg_row),
                pl.BlockSpec((1, block_k), lambda bh, ik_, jj, *_: (0, ik_)),
            ],
            out_specs=(
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, ik_, jj, *_: (bh, ik_, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((hkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((hkv, t, d), v.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
    )(*dkv_operands)
    return dq, dk, dv
