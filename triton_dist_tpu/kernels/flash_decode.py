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


def _softmax_tile(q, k, v, k0, length, m_prev, l_prev, acc_prev, scale):
    """One KV tile of the online softmax for one kv head: ``q`` (group, d)
    against ``k``/``v`` (tile, d) whose first row is position ``k0``; rows at
    or past ``length`` are masked. ``m``/``l`` are (group, LANES) lane-
    broadcast running max and sum, ``acc`` (group, d) f32. The contiguous and
    the paged kernel both call this, which is what makes them agree bit for
    bit at one tile size. Returns the updated (m, l, acc)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (group, tile)
    k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_ids < length, s, NEG_INF)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_new = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), m_prev.shape
    )
    acc_new = acc_prev * alpha[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


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
        m_scr[...], l_scr[...], acc_scr[...] = _softmax_tile(
            q_ref[0], k_ref[0], v_ref[0], ik * block_k, length,
            m_scr[...], l_scr[...], acc_scr[...], scale,
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
    layer_ref,  # scalar-prefetch (1,) int32 — the layer of the stacked pool
    tables_ref,  # scalar-prefetch (B, max_blocks) int32
    lengths_ref,  # scalar-prefetch (B,) int32
    q_ref,  # VMEM (1, hkv, group, d)
    *refs,  # pools in HBM, outputs, tile buffers, semaphores, accumulators
    scale: float,
    block_size: int,
    pages: int,
    hkv: int,
    quant: bool,
):
    """Online-softmax decode walking a block TABLE. One grid step is one
    slot: the pools stay in HBM and the slot's live tiles of ``pages``
    pages are DMA'd by the table into a double-buffered VMEM tile, all kv
    heads of a page in one copy; tiles past the slot's length are neither
    fetched nor computed. A tile's math is ``_decode_kernel``'s at
    ``block_k = pages * block_size`` — the same dots, masks and update
    order per kv head — so bitwise parity with the contiguous kernel at
    that block partition holds by construction.

    A QUANTIZED pool walks its scale pool through the same table entries
    and dequantizes each tile to f32 right after the VMEM read
    (``q·scale`` is exact in f32, power-of-two scales), which keeps it
    bitwise-comparable to the gather→dequant→contiguous oracle."""
    n_pools = 4 if quant else 2
    pools = refs[:n_pools]  # (L, num_blocks, hkv, bs, d | 1) in HBM
    o_ref, lse_ref = refs[n_pools:n_pools + 2]
    bufs = refs[n_pools + 2:2 * n_pools + 2]  # (2, hkv, tile, d | 1)
    sems, acc_scr, m_scr, l_scr = refs[2 * n_pools + 2:]

    b = pl.program_id(0)
    li = layer_ref[0]
    length = lengths_ref[b]
    tile = pages * block_size
    n_tiles = (length + tile - 1) // tile

    def copies(t, slot):
        # One descriptor a page and pool; payload and scales resolve the
        # same table entry. A page past the length is still a mapped (or
        # the NULL) block: finite bytes that the mask zeroes.
        out = []
        for p in range(pages):
            phys = tables_ref[b, t * pages + p]
            for i in range(n_pools):
                out.append(pltpu.make_async_copy(
                    pools[i].at[li, phys],
                    bufs[i].at[slot, :, pl.ds(p * block_size, block_size), :],
                    sems.at[slot, i],
                ))
        return out

    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(n_tiles > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def tile_step(t, carry):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            for c in copies(t + 1, 1 - slot):
                c.start()

        for c in copies(t, slot):
            c.wait()

        for h in range(hkv):
            k = bufs[0][slot, h]  # (tile, d)
            v = bufs[1][slot, h]
            if quant:
                k = k.astype(jnp.float32) * bufs[2][slot, h]
                v = v.astype(jnp.float32) * bufs[3][slot, h]
            m_scr[h], l_scr[h], acc_scr[h] = _softmax_tile(
                q_ref[0, h], k, v, t * tile, length,
                m_scr[h], l_scr[h], acc_scr[h], scale,
            )
        return carry

    jax.lax.fori_loop(0, n_tiles, tile_step, 0)

    for h in range(hkv):
        l = l_scr[h][:, :1]  # (group, 1)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, h] = (acc_scr[h] / l_safe).astype(o_ref.dtype)
        lse_ref[0, h] = jnp.where(
            l == 0.0, NEG_INF, m_scr[h][:, :1] + jnp.log(jnp.maximum(l, 1e-30))
        )


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


def paged_kv_append(pk, pv, layer, k_new, v_new, tables, lengths, active):
    """Write one decode step's new K/V rows (B, Hkv, D) of layer ``layer``
    through the block table into the stacked pools (L, num_blocks, Hkv, bs,
    D), in place when the pools are a donated loop carry: slot ``b``'s row
    lands at ``pool[layer, tables[b, lengths[b] // bs], :, lengths[b] % bs]``.
    An inactive slot's row redirects to the reserved NULL block 0 — a freed
    slot's old blocks may already belong to another tenant. ``QuantPool``
    pairs quantize the row ONCE, here (payload and per-row scale land
    together); no stored row is ever re-quantized. Returns ``(pk', pv')``."""
    from triton_dist_tpu.models.quant import QuantPool, quantize_kv_rows

    quant = isinstance(pk, QuantPool)
    bs = (pk.q if quant else pk).shape[3]
    blk = jnp.take_along_axis(tables, (lengths // bs)[:, None], axis=1)[:, 0]
    phys = jnp.where(active, blk, 0)
    sub = lengths % bs

    def put(pool, rows):
        # One dynamic_update_slice a slot, not one scatter: XLA gives a
        # scatter's operand a layout of its own choosing, and between that
        # and the layout the kernel reads lie two copies of the whole pool
        # a layer. A slice update takes the pool as it lies.
        for i in range(rows.shape[0]):
            pool = jax.lax.dynamic_update_slice(
                pool, rows[i][None, None, :, None, :].astype(pool.dtype),
                (layer, phys[i], 0, sub[i], 0),
            )
        return pool

    if not quant:
        return put(pk, k_new), put(pv, v_new)
    kq, ks = quantize_kv_rows(k_new, pk.wire)  # (B, Hkv, D), (B, Hkv, 1)
    vq, vs = quantize_kv_rows(v_new, pv.wire)
    return (
        QuantPool(put(pk.q, kq), put(pk.scale, ks), pk.wire),
        QuantPool(put(pv.q, vq), put(pv.scale, vs), pv.wire),
    )


def paged_flash_decode(
    q: jax.Array,  # (B, Hq, D) — single decode step
    k_pool: jax.Array,  # (num_blocks, Hkv, bs, D) block pool, or stacked
    v_pool: jax.Array,  # (L, num_blocks, Hkv, bs, D) with ``layer``
    tables: jax.Array,  # (B, max_blocks) int32 physical block ids
    lengths: jax.Array,  # (B,) int32 valid cache length per sequence
    *,
    layer=None,  # int or int32 scalar: the stacked pools' layer to read
    scale: float | None = None,
    block_k: int | None = None,
    impl: str = "pallas",
    return_lse: bool = False,
    k_scale: jax.Array | None = None,  # the pools' shape with D → 1, f32
    v_scale: jax.Array | None = None,
):
    """One-token GQA decode against a PAGED cache.

    ``impl="pallas"`` walks the block table inside the kernel: the pools
    stay in HBM, and for each slot the kernel DMAs the live tiles of
    ``block_k // bs`` pages by their table entries into VMEM — logical
    position is the table's column, physical position is table data,
    shapes stay fixed. With ``layer`` the pools are the STACKED serving
    pools and the layer's index is one more scalar operand, so no
    ``pool[layer]`` slice is ever materialized for the custom call.
    ``impl="gather"`` is the oracle: gather the pool into a contiguous view
    and run the proven contiguous kernel at the same tile (the same KV
    partition → bitwise-identical accumulation order).

    ``block_k=None`` resolves like ``flash_decode``'s (pin, tune cache, 256)
    over the equivalent contiguous cache; the tile is the largest whole
    number of pages that divides the table and is no longer than that.

    With ``k_scale``/``v_scale`` (or ``QuantPool`` operands) the pool is
    quantized (``models/quant.py``): the kernel walks the parallel scale
    pool through the same table and dequantizes each tile to f32 right
    after the VMEM read — no gather bounce, no fp32 pool ever materializes.
    The gather oracle dequantizes host-side and feeds the contiguous kernel
    f32 KV, which is the bitwise-identical computation (power-of-two scales
    make dequantization exact in f32)."""
    from triton_dist_tpu.kernels.gemm import fit_block
    from triton_dist_tpu.models.quant import QuantPool, dequantize_kv

    if isinstance(k_pool, QuantPool):
        k_pool, k_scale = k_pool.q, k_pool.scale
    if isinstance(v_pool, QuantPool):
        v_pool, v_scale = v_pool.q, v_pool.scale
    quant = k_scale is not None
    assert (k_scale is None) == (v_scale is None)
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quant else [])
    if layer is None:
        assert k_pool.ndim == 4, k_pool.shape
        pools, layer = [x[None] for x in pools], 0  # a bitcast, not a slice

    b, hq, d = q.shape
    _, _, hkv, bs, _ = pools[0].shape
    assert hq % hkv == 0
    group = hq // hkv
    mb = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if block_k is None:
        cache_sds = jax.ShapeDtypeStruct((b, hkv, mb * bs, d), pools[0].dtype)
        block_k = flash_decode_config_for(
            jax.ShapeDtypeStruct(q.shape, q.dtype), cache_sds, cache_sds
        )
    pages = fit_block(mb, max(block_k // bs, 1))
    tile = pages * bs
    interpret = interpret_mode_default()
    if quant and impl == "pallas" and not interpret:
        # Mosaic pads the scale pool's last dimension of 1 to the 128 lanes
        # of a tile and refuses a DMA of the one real lane, so on the chip a
        # quantized pool is read through the gather until its scales lie
        # lane-dense (PERF.md section 7); the interpreter walks them.
        impl = "gather"

    if impl == "gather":
        kc, vc, *scales = (gather_paged_kv(x[layer], tables) for x in pools)
        if quant:
            kc = dequantize_kv(kc, scales[0])
            vc = dequantize_kv(vc, scales[1])
        return flash_decode(
            q, kc, vc, lengths, scale=scale, block_k=tile, return_lse=return_lse
        )
    if impl != "pallas":
        raise ValueError(f"unknown paged decode impl {impl!r}")

    head_spec = lambda last: pl.BlockSpec(
        (1, hkv, group, last), lambda bi, *_: (bi, 0, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, tables, lengths: DMA addresses and bounds
        grid=(b,),
        in_specs=[head_spec(d)] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=[head_spec(d), head_spec(1)],
        scratch_shapes=(
            [pltpu.VMEM((2, hkv, tile, x.shape[-1]), x.dtype) for x in pools]
            + [
                pltpu.SemaphoreType.DMA((2, len(pools))),
                pltpu.VMEM((hkv, group, d), jnp.float32),
                pltpu.VMEM((hkv, group, LANES), jnp.float32),
                pltpu.VMEM((hkv, group, LANES), jnp.float32),
            ]
        ),
    )
    o, lse = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, block_size=bs, pages=pages,
            hkv=hkv, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_flash_decode_quant" if quant else "paged_flash_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q.reshape(b, hkv, group, d),
        *pools,
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
