"""Decode attention over whole K/V rows of a block pool, read where they lie.

``models/hybrid_ssm.py`` keeps one layer's K and V in the paged pool, a row a
position with all heads side by side (``(1, blocks, 1, bs, W)``), and eight
layers read it every decode step. This kernel is that read: for each slot it
walks the slot's row of the block table over its LIVE length and gives, a
query row, ``softmax(q K^T) V`` over the whole rows, float32. The heads'
structure is the caller's (a block-diagonal query,
``layers/hybrid_ssm.py:rows_query``), so a tile's work is two dense products,
``(G, W) . (W, tile)`` and ``(G, tile) . (tile, W)``.

It is ``flash_decode._paged_decode_kernel``'s walk (tables and lengths as
scalar prefetch, the pools left in HBM, a page one contiguous copy into a
double-buffered VMEM tile, ``_softmax_tile`` for the online softmax) at one
"kv head" of width ``W``, with one difference that 32 slots a call pay for:
a slot's last tile is computed under the NEXT slot's first fetch, so that
the walk does not start from an empty pipe once a slot (a tenth of the
call's time on the chip: PERF.md section 6, PR 33). That carries a buffer's
turn and a fetch in flight from one grid step to the next: the grid is
sequential. A tile's page copies are waited for once a pool (a DMA semaphore
counts what landed), which reads shorter and times the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.flash_decode import LANES, NEG_INF, _softmax_tile
from triton_dist_tpu.kernels.gemm import fit_block
from triton_dist_tpu.runtime.platform import interpret_mode_default

#: A tile's K and V rows together are at most this (from shapes, not a knob):
#: 14 pages of 16 rows of 1280 bfloat16, 224 positions, where the chip reads
#: fastest (PERF.md section 6, PR 33: 12 to 42 pages lie within a tenth).
TILE_BYTES = 1280 << 10
#: What the kernel's buffers may take of a core's VMEM (under Mosaic's
#: default scope of 16 MiB).
VMEM_BYTES = 12 << 20


def tile_pages(pool_shape: tuple, max_blocks: int, itemsize: int) -> int:
    """Pages a tile: the most that divide the table's row and whose K and V
    rows are within ``TILE_BYTES``."""
    _, _, _, bs, width = pool_shape
    return fit_block(max_blocks, max(TILE_BYTES // (2 * bs * width * itemsize), 1))


def takes(rows: int, pool_shape: tuple, max_blocks: int, itemsize: int) -> bool:
    """Whether the kernel takes ``rows`` query rows a slot over this pool: one
    layer and one "head" of whole rows, the row in whole lanes, a page in
    whole sublane tiles of its type, and the two double-buffered tiles, the
    query's and the result's blocks and the accumulator within ``VMEM_BYTES``."""
    layers, _, heads, bs, width = pool_shape
    tile = tile_pages(pool_shape, max_blocks, itemsize) * bs
    vmem = (2 * 2 * tile * width * itemsize + 2 * rows * width * (itemsize + 4)
            + rows * (width + 2 * LANES) * 4)
    return (layers == 1 and heads == 1 and width % LANES == 0
            and bs % (32 // itemsize) == 0 and vmem <= VMEM_BYTES)


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, turn, acc_scr, m_scr, l_scr, *,
            scale: float, block_size: int, pages: int, slots: int):
    b = pl.program_id(0)
    tile = pages * block_size
    tiles_of = lambda row: (lengths_ref[row] + tile - 1) // tile
    n_tiles = tiles_of(b)
    after = jnp.minimum(b + 1, slots - 1)
    follows = (b + 1 < slots) & (tiles_of(after) > 0)

    def fetch(row, t, buf):
        # A page past the length is still a mapped (or the NULL) block:
        # finite bytes that the mask zeroes.
        for p in range(pages):
            phys = tables_ref[row, t * pages + p]
            for i, (pool, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                pltpu.make_async_copy(
                    pool.at[0, phys, 0],
                    dst.at[buf, pl.ds(p * block_size, block_size), :],
                    sems.at[buf, i],
                ).start()

    @pl.when(b == 0)
    def _():
        turn[0] = 0

        @pl.when(n_tiles > 0)
        def _():
            fetch(0, 0, 0)

    first = turn[0]  # the buffer this slot's tile 0 was fetched into
    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)

    def tile_step(t, carry):
        buf = jax.lax.rem(first + t, 2)
        more = t + 1 < n_tiles

        @pl.when(more | follows)
        def _():  # this slot's next tile, or after its last the next slot's first
            fetch(jnp.where(more, b, after), jnp.where(more, t + 1, 0), 1 - buf)

        for i, dst in enumerate((k_buf, v_buf)):  # every page's bytes, in one wait
            pltpu.make_async_copy(dst.at[buf], dst.at[buf], sems.at[buf, i]).wait()
        m_scr[...], l_scr[...], acc_scr[...] = _softmax_tile(
            q_ref[0], k_buf[buf], v_buf[buf], t * tile, lengths_ref[b],
            m_scr[...], l_scr[...], acc_scr[...], scale,
        )
        return carry

    jax.lax.fori_loop(0, n_tiles, tile_step, 0)

    @pl.when((n_tiles == 0) & follows)
    def _():  # a slot nobody reads for hands the turn on
        fetch(after, 0, first)

    turn[0] = jax.lax.rem(first + n_tiles, 2)
    l = l_scr[:, :1]
    o_ref[0] = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)


def shared_kv_decode(q_rows, k_pool, v_pool, tables, lengths, *, scale: float):
    """``q_rows`` (B, G, W): G query rows a slot, each over whole rows;
    ``k_pool``, ``v_pool`` (1, blocks, 1, bs, W); ``tables`` (B, max_blocks)
    int32 block numbers; ``lengths`` (B,) int32, the positions a slot's rows
    may see (0: none, the result zeros). Returns (B, G, W) float32:
    ``softmax(scale * q K^T) V`` a query row over positions ``[0, length)``.
    Whole tiles of ``tile_pages`` pages are fetched up to the length, none
    past it."""
    pages = tile_pages(k_pool.shape, tables.shape[1], k_pool.dtype.itemsize)
    return _call(q_rows, k_pool, v_pool, tables.astype(jnp.int32), lengths.astype(jnp.int32),
                 scale=scale, pages=pages, interpret=interpret_mode_default())


# A program calls this once a reading layer at the same shapes: under its own
# jit the kernel is traced and lowered once a program, not once a layer
# (0.16 s each in every process that builds the decode chunk).
@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def _call(q_rows, k_pool, v_pool, tables, lengths, *, scale: float, pages: int, interpret):
    b, g, w = q_rows.shape
    bs = k_pool.shape[3]
    tile = pages * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables, lengths: DMA addresses and bounds
        grid=(b,),
        in_specs=[pl.BlockSpec((1, g, w), lambda bi, *_: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, g, w), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, w), k_pool.dtype),
            pltpu.VMEM((2, tile, w), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((g, w), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_size=bs, pages=pages, slots=b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="shared_kv_decode",
    )(tables, lengths, q_rows, k_pool, v_pool)
