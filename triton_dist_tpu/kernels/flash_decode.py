"""Flash decode (GQA, KV-cache) + distributed sequence-sharded decode.

Reference: ``python/triton_dist/kernels/nvidia/flash_decode.py`` (1132 LoC) —
split-KV partial attention, intra-rank combine, **inter-rank combine over
ranks** for KV sharded by sequence (:130,:308,:393,:482), scaling 1→32 GPUs
(``README.md:209-211``). TPU redesign:

* Intra-chip: GPU split-KV parallelises partial softmax across SMs; a TPU
  core walks the grid sequentially, so the kernel is simply online-softmax
  over KV blocks (no intra-rank combine needed). GQA is computed as one
  ``(group, d) @ (d, block_k)`` MXU product per kv head — query heads of a
  group ride the sublane dimension.
* Cache-length masking comes from an SMEM lengths array (static shapes,
  dynamic validity — the TPU answer to varlen).
* Inter-rank: each rank decodes over its KV sequence shard returning
  ``(o, lse)``; the combine is a numerically-stable weighted sum after an
  all-gather of the per-rank ``(o, lse)`` pair (tiny tensors → XLA collective
  over ICI is the right transport; reference kernel :482-566).
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
DEFAULT_BLOCK_K = 256


def flash_decode_op_name() -> str:
    """Tune-cache op key (single source for the kernel lookup and the
    offline ``tools.tune_gemm --flash-decode`` sweep)."""
    return "flash_decode"


def flash_decode_config_for(q_sds, k_sds, v_sds) -> int:
    """Trace-time tuned block_k lookup for the decode sweep (offline
    ``tools.tune_gemm --flash-decode`` fills the cache). The key is the
    FULL (q, k_cache, v_cache) signature — exactly the arg list
    ``autotune`` times and persists under, same convention as
    ``flash_attn.flash_config_for`` (a reader keying on fewer args than
    the writer would silently never hit). Falls back to the 256 default —
    ``fit_block`` shrinks it for short caches.

    ``TDT_FLASH_BLOCK_K`` (int > 0) overrides both the cache and the
    default: the online-softmax accumulation order follows the swept block
    partition, so two lowerings of the same attention are bitwise-identical
    only at the SAME block_k. Pinning it (typically to the paged KV block
    size) makes the contiguous path byte-comparable with the paged
    table-walk — the megakernel parity contract (docs/megakernel.md)."""
    import os

    pinned = int(os.environ.get("TDT_FLASH_BLOCK_K", "0") or "0")
    if pinned > 0:
        return pinned
    from triton_dist_tpu.tools.tune import lookup

    hit = lookup(flash_decode_op_name(), [q_sds, k_sds, v_sds])
    if hit:
        return int(hit["block_k"])
    return DEFAULT_BLOCK_K


def _decode_kernel(
    lengths_ref,  # SMEM (B,)
    q_ref,  # (1, group, d)
    k_ref,  # (1, bk, d)
    v_ref,  # (1, bk, d)
    o_ref,  # (1, group, d)
    lse_ref,  # (1, 1, group)
    acc_scr,  # VMEM (group, d) f32
    m_scr,  # VMEM (group, LANES) f32
    l_scr,  # VMEM (group, LANES) f32
    *,
    scale: float,
    block_k: int,
    n_kv: int,
    hkv: int,
):
    bh = pl.program_id(0)
    ik = pl.program_id(1)
    length = lengths_ref[bh // hkv]

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(ik * block_k < length)  # skip blocks entirely past the cache end
    def _():
        q = q_ref[0]  # (group, d)
        k = k_ref[0]  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group, bk)
        k_ids = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_ids < length, s, NEG_INF)

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
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(
            l_scr[:, 0] == 0.0,
            NEG_INF,
            m_scr[:, 0] + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30)),
        )
        lse_ref[0, 0] = lse.astype(lse_ref.dtype)


def flash_decode(
    q: jax.Array,  # (B, Hq, D) — single decode step
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, D)
    lengths: jax.Array,  # (B,) int32 — valid cache length per sequence
    *,
    scale: float | None = None,
    block_k: int | None = None,
    return_lse: bool = False,
):
    """One-token GQA decode against a padded KV cache. Returns ``o``
    (B, Hq, D) (+ ``lse`` (B, Hq) fp32 if requested). ``block_k=None``
    reads the tune cache (offline ``--flash-decode`` sweep) so every
    caller — engine backends, the fused attention back-leg — lands on the
    same swept block."""
    b, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    from triton_dist_tpu.kernels.gemm import fit_block

    if block_k is None:
        block_k = flash_decode_config_for(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        )
    block_k = fit_block(s, block_k)
    n_kv = s // block_k

    qr = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kr = k_cache.reshape(b * hkv, s, d)
    vr = v_cache.reshape(b * hkv, s, d)

    o, lse = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, block_k=block_k, n_kv=n_kv, hkv=hkv
        ),
        grid=(b * hkv, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, group, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, group, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, 1, group), lambda bh, ik: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, 1, group), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
        name="flash_decode",
    )(lengths.astype(jnp.int32), qr, kr, vr)

    o = o.reshape(b, hq, d)
    if return_lse:
        return o, lse.reshape(b, hq)
    return o


def _paged_decode_kernel(
    tables_ref,  # scalar-prefetch (B, max_blocks) int32
    lengths_ref,  # SMEM (B,)
    q_ref,  # (1, group, d)
    k_ref,  # (1, 1, bs, d) — one physical pool block
    v_ref,  # (1, 1, bs, d)
    o_ref,  # (1, group, d)
    lse_ref,  # (1, 1, group)
    acc_scr,  # VMEM (group, d) f32
    m_scr,  # VMEM (group, LANES) f32
    l_scr,  # VMEM (group, LANES) f32
    *,
    scale: float,
    block_size: int,
    n_kv: int,
    hkv: int,
):
    """Online-softmax decode walking a block TABLE instead of a contiguous
    row. Identical math to ``_decode_kernel`` with ``block_k=block_size`` —
    the BlockSpec index_map does the page walk (physical block id prefetched
    from ``tables_ref``), so the compute body never changes and bitwise
    parity with the contiguous kernel at the same block partition holds by
    construction."""
    bh = pl.program_id(0)
    ik = pl.program_id(1)
    length = lengths_ref[bh // hkv]

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(ik * block_size < length)  # logical blocks past the cache end skip
    def _():
        q = q_ref[0]  # (group, d)
        k = k_ref[0, 0]  # (bs, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group, bs)
        k_ids = ik * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_ids < length, s, NEG_INF)

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
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(
            l_scr[:, 0] == 0.0,
            NEG_INF,
            m_scr[:, 0] + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30)),
        )
        lse_ref[0, 0] = lse.astype(lse_ref.dtype)


def _paged_decode_quant_kernel(
    tables_ref,  # scalar-prefetch (B, max_blocks) int32
    lengths_ref,  # SMEM (B,)
    q_ref,  # (1, group, d)
    k_ref,  # (1, 1, bs, d) — one physical pool block, wire dtype
    v_ref,  # (1, 1, bs, d)
    ks_ref,  # (1, 1, bs, 1) f32 — the block's per-row scales
    vs_ref,  # (1, 1, bs, 1) f32
    o_ref,  # (1, group, d)
    lse_ref,  # (1, 1, group)
    acc_scr,  # VMEM (group, d) f32
    m_scr,  # VMEM (group, LANES) f32
    l_scr,  # VMEM (group, LANES) f32
    *,
    scale: float,
    block_size: int,
    n_kv: int,
    hkv: int,
):
    """``_paged_decode_kernel`` over a QUANTIZED pool: the scale pool walks
    the same table through the same index map (a whole (bs, 1) block read —
    legal where a sublane-slice of a lane-padded memref is not, see
    ``models/quant.py``), each block dequantizes to f32 in VMEM right after
    the walk, and everything downstream is the identical online-softmax.
    Dequantization ``q·scale`` is exact in f32 (power-of-two scales), so
    this path is bitwise-comparable to the gather→dequant→contiguous oracle
    at the same block partition."""
    bh = pl.program_id(0)
    ik = pl.program_id(1)
    length = lengths_ref[bh // hkv]

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(ik * block_size < length)  # logical blocks past the cache end skip
    def _():
        q = q_ref[0]  # (group, d)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]  # (bs, d) f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group, bs)
        k_ids = ik * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_ids < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape
        )
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]  # (bs, d) f32
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(
            l_scr[:, 0] == 0.0,
            NEG_INF,
            m_scr[:, 0] + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30)),
        )
        lse_ref[0, 0] = lse.astype(lse_ref.dtype)


def gather_paged_kv(k_pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Materialize a contiguous (B, Hkv, max_blocks*bs, D) cache view from a
    (num_blocks, Hkv, bs, D) pool and a (B, max_blocks) int32 block table.
    Pure gather — unmapped table entries point at the null block (zeros) and
    sit past ``lengths``, so the view feeds the contiguous kernel unchanged.
    This is the interpret-mode parity ORACLE for the paged kernel and the
    engine's gather-based decode fallback."""
    b, mb = tables.shape
    _, hkv, bs, d = k_pool.shape
    gathered = jnp.take(k_pool, tables.reshape(-1), axis=0)  # (B*MB, Hkv, bs, D)
    gathered = gathered.reshape(b, mb, hkv, bs, d).transpose(0, 2, 1, 3, 4)
    return gathered.reshape(b, hkv, mb * bs, d)


def paged_flash_decode(
    q: jax.Array,  # (B, Hq, D) — single decode step
    k_pool: jax.Array,  # (num_blocks, Hkv, bs, D) — global block pool
    v_pool: jax.Array,
    tables: jax.Array,  # (B, max_blocks) int32 physical block ids
    lengths: jax.Array,  # (B,) int32 valid cache length per sequence
    *,
    scale: float | None = None,
    impl: str = "pallas",
    return_lse: bool = False,
    k_scale: jax.Array | None = None,  # (num_blocks, Hkv, bs, 1) f32
    v_scale: jax.Array | None = None,
):
    """One-token GQA decode against a PAGED cache.

    ``impl="pallas"`` walks the block table inside the kernel grid: the
    physical block id for grid step ``(bh, ik)`` is scalar-prefetched from
    ``tables`` and becomes the BlockSpec index — logical position is grid
    position, physical position is table data, shapes stay fixed.
    ``impl="gather"`` is the oracle: gather the pool into a contiguous view
    and run the proven contiguous kernel at ``block_k=block_size`` (the
    same KV partition → bitwise-identical accumulation order).

    With ``k_scale``/``v_scale`` (or ``QuantPool`` operands) the pool is
    quantized (``models/quant.py``): the kernel walks the parallel scale
    pool through the same table and dequantizes each block to f32 right
    after the VMEM read — no gather bounce, no fp32 pool ever materializes.
    The gather oracle dequantizes host-side and feeds the contiguous kernel
    f32 KV, which is the bitwise-identical computation (power-of-two scales
    make dequantization exact in f32)."""
    from triton_dist_tpu.models.quant import QuantPool, dequantize_kv

    if isinstance(k_pool, QuantPool):
        k_pool, k_scale = k_pool.q, k_pool.scale
    if isinstance(v_pool, QuantPool):
        v_pool, v_scale = v_pool.q, v_pool.scale
    quant = k_scale is not None
    assert (k_scale is None) == (v_scale is None)

    b, hq, d = q.shape
    nb, hkv, bs, _ = k_pool.shape
    assert hq % hkv == 0
    group = hq // hkv
    mb = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5

    if impl == "gather":
        kc = gather_paged_kv(k_pool, tables)
        vc = gather_paged_kv(v_pool, tables)
        if quant:
            kc = dequantize_kv(kc, gather_paged_kv(k_scale, tables))
            vc = dequantize_kv(vc, gather_paged_kv(v_scale, tables))
        return flash_decode(
            q, kc, vc, lengths, scale=scale, block_k=bs, return_lse=return_lse
        )
    if impl != "pallas":
        raise ValueError(f"unknown paged decode impl {impl!r}")

    qr = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)

    def walk(width):
        # Payload and scale pools walk the SAME table entry — one physical
        # block id resolves both the bytes and their per-row scales.
        return pl.BlockSpec(
            (1, 1, bs, width),
            lambda bh, ik, tab: (tab[bh // hkv, ik], bh % hkv, 0, 0),
        )

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, group, d), lambda bh, ik, tab: (bh, 0, 0)),
        walk(d),
        walk(d),
    ]
    operands = [lengths.astype(jnp.int32), qr, k_pool, v_pool]
    if quant:
        in_specs += [walk(1), walk(1)]
        operands += [k_scale, v_scale]
        kernel = _paged_decode_quant_kernel
    else:
        kernel = _paged_decode_kernel

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # tables ride ahead of the grid for index maps
        grid=(b * hkv, mb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, group, d), lambda bh, ik, tab: (bh, 0, 0)),
            pl.BlockSpec((1, 1, group), lambda bh, ik, tab: (bh, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(
            kernel, scale=scale, block_size=bs, n_kv=mb, hkv=hkv
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, 1, group), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret_mode_default(),
        name="paged_flash_decode_quant" if quant else "paged_flash_decode",
    )(
        tables.astype(jnp.int32).reshape(b, mb),
        *operands,
    )

    o = o.reshape(b, hq, d)
    if return_lse:
        return o, lse.reshape(b, hq)
    return o


def combine_partials(o_parts: jax.Array, lse_parts: jax.Array) -> jax.Array:
    """Numerically-stable combine of per-shard attention partials.

    ``o_parts`` (world, B, Hq, D) normalised partial outputs, ``lse_parts``
    (world, B, Hq) their log-sum-exps. Reference inter-rank combine kernel
    (``flash_decode.py:482-566``)."""
    m = jnp.max(lse_parts, axis=0, keepdims=True)  # (1, B, Hq)
    w = jnp.exp(lse_parts - m)  # (world, B, Hq)
    denom = jnp.sum(w, axis=0)  # (B, Hq)
    num = jnp.sum(w[..., None] * o_parts.astype(jnp.float32), axis=0)
    return (num / jnp.maximum(denom, 1e-30)[..., None]).astype(o_parts.dtype)


def dist_flash_decode_shard(
    q: jax.Array,  # (B, Hq, D) — replicated across the sp axis
    k_shard: jax.Array,  # (B, Hkv, S_shard, D) — this rank's sequence shard
    v_shard: jax.Array,
    global_lengths: jax.Array,  # (B,) int32 — total valid cache length
    *,
    axis: str = "sp",
    scale: float | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Sequence-sharded distributed decode, usable inside shard_map.

    Each rank attends over its own KV shard; partials are combined across the
    ``axis`` ranks via all-gather + stable weighted sum (the reference's
    cross-rank GQA decode, ``flash_decode.py:763-1131`` host wrappers)."""
    s_shard = k_shard.shape[2]
    me = jax.lax.axis_index(axis)
    # Valid length within my shard: clamp(global_len - me*s_shard, 0, s_shard)
    local_len = jnp.clip(global_lengths - me * s_shard, 0, s_shard)
    o, lse = flash_decode(
        q, k_shard, v_shard, local_len, scale=scale, block_k=block_k, return_lse=True
    )
    o_all = jax.lax.all_gather(o, axis)  # (world, B, Hq, D)
    lse_all = jax.lax.all_gather(lse, axis)  # (world, B, Hq)
    return combine_partials(o_all, lse_all)
