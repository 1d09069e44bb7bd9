"""Latent attention's two kernels: a prefill chunk's attention in the
expanded form (under a selection's mask, :func:`dsa_flash_prefill`, or under
causality alone, :func:`latent_flash_prefill`), and a decode step's in the
absorbed form over the latent pool where it lies
(:func:`latent_flash_decode`).

**Prefill.**

``layers/latent_sparse.py:attend_expanded`` attends a chunk of ``C`` query
rows over a prompt's latent buffer ``rows (P, rank + R)`` under a mask
``allowed (C, P)`` that holds the selection and causality together. In plain
XLA the ``(heads, C, keys)`` scores, their exp and ``p`` each pass through
HBM in float32. Here they stay in VMEM:

* grid ``(head group, key tile)``, the key tile sequential. A step makes K
  ``[k_nope | k_rope]`` and V of its key tile for the group's heads from the
  tile's latent rows, in VMEM, once; then every query tile of the chunk that
  may see the key tile attends to it, a head at a time, with an online
  softmax whose running max, sum and ``(heads, C, V)`` accumulator live in
  VMEM scratch across the key tiles. So K and V are made once a (head,
  key), as in the XLA body, and never written to HBM.
* **one table decides what is computed**: ``any(allowed)`` over each (query
  tile, key tile) (:func:`tile_table`), handed over as scalar prefetch. A
  false entry's tile is not computed; a key tile no query tile sees is not
  fetched either (its block index is the last fetched one's) and its K and V
  are not made. That covers the keys past the chunk's diagonal, the upper
  half of the diagonal block, and any tile the selection left empty.

The mathematics and the precision are the XLA body's: operands in the
queries' type, float32 accumulation, float32 online softmax, ``p`` cast to
the operands' type for P.V, K and V rounded to the operands' type after
they are made. K's two parts come from ONE product, ``[c_kv | k_r] @ [[W_uk,
0], [0, I]]``: the zeros add nothing and the identity copies ``k_r``
exactly, so the values are those of the two separate products, and the
score is one contraction over ``N + R`` where the XLA body adds two. A head
of ``N + R`` values that is not whole lanes (128 + 64) is padded with zeros
to whole lanes, in the queries and in ``[[W_uk, 0], [0, I]]`` alike: on a
128-wide matrix unit a contraction over 192 costs what one over 256 does,
and zeros add nothing.

Where the layer has no indexer the mask says nothing the chunk's offset does
not: :func:`latent_flash_prefill` is the same body handed ``off`` instead of
``allowed``. Its table is the causal one (:func:`causal_table`) and a tile's
mask comes from the positions, so no ``(C, P)`` array is made, padded or
fetched. A key tile wholly at or before a query tile's first position
(:func:`interior_table`, a scalar test in the kernel) hides nothing from the
tile's rows and is attended with no mask at all; only the tile across a
query tile's diagonal makes one. The scale rides in K (in float32, before
K's cast: once a key and head), so no score is scaled either. Under a
selection's mask every visited tile is masked and the scores are scaled, as
they always were.

**Decode.** One query a slot, every earlier position visible. The query is
taken into the latent space outside (``[q_nope W_uk | q_rope]``, 576 wide at
the published sizes), so a position's key is its whole cache row and its
value the row's first ``rank`` values: the heads share both, and a tile of
rows is read once for all of them. :func:`latent_flash_decode` is
``kernels/shared_kv_decode.py``'s walk over ONE pool: the block table and the
lengths as scalar prefetch, the pool left in HBM, a page one copy into a
double-buffered VMEM tile, ``flash_decode._softmax_tile`` for the online
softmax in float32, a slot's last tile computed under the next slot's first
fetch; tiles past a slot's length are neither fetched nor computed. The
weighted sum stays in the latent space (``rank`` wide, float32) and goes
back through ``W_uv`` outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.flash_decode import NEG_INF, _softmax_tile
from triton_dist_tpu.kernels.gemm import fit_block
from triton_dist_tpu.runtime.platform import interpret_mode_default

F32 = jnp.float32
NEG = -jnp.inf
LANES = 128
#: Query rows and keys a tile, and heads a grid step (the chip's timings
#: that chose them: PERF.md section 6, PR 31).
QUERY_TILE = 512
KEY_TILE = 1024
HEAD_GROUP = 4
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
#: The decode kernel's tile of latent rows is at most this (24 pages of 16
#: rows of 576 bfloat16 at the published sizes), and its buffers this much
#: of a core's VMEM (under Mosaic's default scope of 16 MiB).
DECODE_TILE_BYTES = 512 << 10
DECODE_VMEM_BYTES = 12 << 20


def _lanes(n: int) -> int:
    return -(-n // LANES) * LANES


def tile_sizes(C: int, P: int) -> tuple[int, int]:
    """(query rows, keys) a tile for a chunk of ``C`` rows over ``P`` keys."""
    return min(QUERY_TILE, C), min(KEY_TILE, -(-P // LANES) * LANES)


def vmem_bytes(C: int, g: int, rank: int, qk_dim: int, v_dim: int, itemsize: int) -> int:
    """What a grid step of :func:`dsa_flash_prefill` holds in VMEM at the
    largest key tile: the blocks (each twice, for the pipeline), the scratch,
    and a few (query tile, key tile) float32 temporaries of the softmax."""
    tq, tk = min(QUERY_TILE, C), KEY_TILE
    qk_dim = _lanes(qk_dim)
    width = rank + qk_dim  # [c_kv | k_r] padded to whole lanes, at most
    blocks = (g * C * qk_dim * itemsize + tk * width * itemsize + C * tk
              + g * width * qk_dim * itemsize + g * rank * v_dim * itemsize
              + C * g * v_dim * itemsize)
    scratch = (g * tk * (qk_dim + v_dim) * itemsize + tq * tk * 4
               + g * C * (v_dim + 2 * LANES) * 4)
    return 2 * blocks + scratch + 4 * tq * tk * 4


def takes(C: int, H: int, rank: int, qk_dim: int, v_dim: int, itemsize: int) -> bool:
    """Whether the kernel takes these shapes: whole query tiles of whole
    sublane groups (the mask is int8: 32 rows), the value head and the latent
    rank in whole lanes (the query/key head is padded to them), and a chunk
    whose accumulator, queries and mask fit
    the VMEM asked for (they grow with ``C``: 2048 rows of bfloat16 count 58
    MiB here, 8192 would not fit). ``P`` is padded to whole key tiles, so any
    will do."""
    tq, g = min(QUERY_TILE, C), min(HEAD_GROUP, H)
    return (C % tq == 0 and tq % 32 == 0 and H % g == 0
            and rank % LANES == 0 and v_dim % LANES == 0
            and vmem_bytes(C, g, rank, qk_dim, v_dim, itemsize) <= VMEM_LIMIT_BYTES)


def tile_table(allowed, tq: int, tk: int):
    """``any(allowed)`` over each (query tile, key tile): (ceil(C / tq),
    ceil(P / tk)) bool."""
    C, P = allowed.shape
    a = jnp.pad(allowed, ((0, -C % tq), (0, -P % tk)))
    return a.reshape(a.shape[0] // tq, tq, a.shape[1] // tk, tk).any(axis=(1, 3))


def causal_table(C: int, P: int, off, tq: int, tk: int):
    """The table of a chunk whose mask is causality alone: key tile ``j`` is
    seen by query tile ``i`` iff its first key is at or before the tile's
    last query, ``off + (i + 1) tq - 1``. (C / tq, ceil(P / tk)) bool."""
    last = off + (jnp.arange(C // tq, dtype=jnp.int32) + 1) * tq - 1
    return jnp.arange(-(-P // tk), dtype=jnp.int32)[None, :] * tk <= last[:, None]


def interior_table(C: int, P: int, off, tq: int, tk: int):
    """The tiles of :func:`causal_table` that the kernel attends with no
    mask: key tile ``j``'s last key is at or before query tile ``i``'s first
    position, ``(j + 1) tk - 1 <= off + i tq``. (C / tq, ceil(P / tk)) bool."""
    first = off + jnp.arange(C // tq, dtype=jnp.int32) * tq
    return (jnp.arange(-(-P // tk), dtype=jnp.int32)[None, :] + 1) * tk - 1 <= first[:, None]


def _kernel(*refs, causal: bool, scale: float, rank: int, tq: int, nq: int, nk: int,
            g: int, v_dim: int):
    if causal:  # the chunk's first position in the mask's place
        (tab_ref, kmap_ref, off_ref, q_ref, rows_ref, wk_ref, wv_ref, o_ref,
         k_scr, v_scr, bias_scr, acc_scr, m_scr, l_scr) = refs
    else:
        (tab_ref, kmap_ref, q_ref, rows_ref, mask_ref, wk_ref, wv_ref, o_ref,
         k_scr, v_scr, bias_scr, acc_scr, m_scr, l_scr) = refs
    del kmap_ref  # the index maps' alone
    j = pl.program_id(1)
    dt = k_scr.dtype
    tk = k_scr.shape[1]

    @pl.when(j == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)

    seen = tab_ref[j]
    for i in range(1, nq):
        seen = seen | tab_ref[i * nk + j]

    @pl.when(seen != 0)
    def _():
        def expand(h, carry):
            k = jnp.dot(rows_ref[...], wk_ref[h], preferred_element_type=F32)
            k_scr[h] = (k * scale if causal else k).astype(dt)  # causal: K carries the scale
            v_scr[h] = jnp.dot(rows_ref[:, :rank], wv_ref[h],
                               preferred_element_type=F32).astype(dt)
            return carry

        jax.lax.fori_loop(0, g, expand, 0)

        def tile(r, masked: bool):
            """The online softmax of query rows ``r`` over the key tile, a
            head at a time; ``masked``: under ``bias_scr``, a row that sees
            nothing yet kept finite."""
            def head(h, carry):
                s = jax.lax.dot_general(
                    q_ref[h, r, :], k_scr[h], (((1,), (1,)), ((), ())),
                    preferred_element_type=F32)  # (tq, tk)
                if not causal:
                    s = s * scale
                if masked:
                    s = s + bias_scr[...]
                m_prev = m_scr[h, r, :]  # (tq, LANES), a row's value in every lane
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                m_safe = jnp.where(m_new == NEG, 0.0, m_new) if masked else m_new
                alpha = jnp.exp(m_prev - m_safe)
                p = jnp.exp(s - m_safe[:, :1])
                l_scr[h, r, :] = l_scr[h, r, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
                m_scr[h, r, :] = m_new
                acc_scr[h, r, :] = acc_scr[h, r, :] * alpha[:, :1] + jnp.dot(
                    p.astype(dt), v_scr[h], preferred_element_type=F32)
                return carry

            jax.lax.fori_loop(0, g, head, 0)

        def query_tile(i, carry):
            visit = tab_ref[i * nk + j] != 0
            if causal:  # a key tile wholly at or before the query tile's first
                # position hides nothing from any of its rows
                interior = (j + 1) * tk - 1 <= off_ref[0] + i * tq
                visit = visit & jnp.logical_not(interior)

            @pl.when(visit)
            def _():
                r = pl.ds(pl.multiple_of(i * tq, tq), tq)
                if causal:
                    q_pos = off_ref[0] + i * tq + jax.lax.broadcasted_iota(
                        jnp.int32, (tq, tk), 0)
                    ok = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1) <= q_pos
                else:
                    ok = mask_ref[r, :].astype(jnp.int32) != 0
                bias_scr[...] = jnp.where(ok, 0.0, NEG)
                tile(r, masked=True)

            if causal:
                @pl.when(interior)
                def _():
                    tile(pl.ds(pl.multiple_of(i * tq, tq), tq), masked=False)

            return carry

        jax.lax.fori_loop(0, nq, query_tile, 0)

    @pl.when(j == nk - 1)
    def _():
        for h in range(g):
            l = l_scr[h][:, :1]
            o_ref[:, h * v_dim:(h + 1) * v_dim] = (
                acc_scr[h] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _flash_prefill(q_nope, q_rope, rows, allowed, off, w_uk, w_uv, scale: float, table,
                   name: str):
    """The two prefill kernels' one body: under ``allowed`` (C, P), or where
    it is None under causality from the chunk's first position ``off``."""
    C, H, N = q_nope.shape
    P, width = rows.shape  # rank + R, or wider: what lies past them meets zeros
    rank, _, V = w_uv.shape
    R = q_rope.shape[-1]
    dt = q_nope.dtype
    causal = allowed is None
    tq, tk = tile_sizes(C, P)
    g = min(HEAD_GROUP, H)
    assert C % tq == 0 and H % g == 0, (C, tq, H, g)
    if table is None:
        table = causal_table(C, P, off, tq, tk) if causal else tile_table(allowed, tq, tk)
    nq, nk = table.shape
    assert nq == C // tq and nk == -(-P // tk), (table.shape, C, P, tq, tk)
    p_pad, w_pad, qk = nk * tk - P, -width % LANES, _lanes(N + R)
    # Heads lead, so that a head's tile is a block of whole rows.
    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(1, 0, 2)  # (H, C, N + R)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, qk - N - R)))
    rows = jnp.pad(rows, ((0, p_pad), (0, w_pad)))
    # [c_kv | k_r | 0] @ wk = [c_kv @ W_uk | k_r | 0]
    wk = jnp.zeros((H, width + w_pad, qk), dt)
    wk = wk.at[:, :rank, :N].set(w_uk.transpose(1, 0, 2))
    wk = wk.at[:, rank:rank + R, N:N + R].set(jnp.eye(R, dtype=dt))
    wv = w_uv.transpose(1, 0, 2)
    seen = table.any(axis=0)
    # A key tile nobody sees names the block fetched last, which is no fetch.
    kmap = jax.lax.cummax(jnp.where(seen, jnp.arange(nk, dtype=jnp.int32), 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda G, j, *_: (G,) + (0,) * (len(shape) - 1))
    q_spec = whole(g, C, qk)
    rows_spec = pl.BlockSpec((tk, width + w_pad), lambda G, j, tab, km, *_: (km[j], 0))
    w_specs = [whole(g, width + w_pad, qk), whole(g, rank, V)]
    if causal:
        prefetch = (table.reshape(-1).astype(jnp.int32), kmap,
                    jnp.asarray(off, jnp.int32).reshape(1))
        in_specs, operands = [q_spec, rows_spec, *w_specs], (q, rows, wk, wv)
    else:
        prefetch = (table.reshape(-1).astype(jnp.int32), kmap)
        mask = jnp.pad(allowed, ((0, 0), (0, p_pad))).astype(jnp.int8)
        mask_spec = pl.BlockSpec((C, tk), lambda G, j, tab, km: (0, km[j]))
        in_specs, operands = [q_spec, rows_spec, mask_spec, *w_specs], (q, rows, mask, wk, wv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(H // g, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((C, g * V), lambda G, j, *_: (0, G)),
        scratch_shapes=[
            pltpu.VMEM((g, tk, qk), dt),      # K of the key tile
            pltpu.VMEM((g, tk, V), dt),       # V of the key tile
            pltpu.VMEM((tq, tk), F32),        # 0 where allowed, -inf where not
            pltpu.VMEM((g, C, V), F32),       # the accumulator
            pltpu.VMEM((g, C, LANES), F32),   # running max
            pltpu.VMEM((g, C, LANES), F32),   # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale, rank=rank, tq=tq, nq=nq, nk=nk,
                          g=g, v_dim=V),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * V), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret_mode_default(),
        name=name,
    )(*prefetch, *operands)


def dsa_flash_prefill(q_nope, q_rope, rows, allowed, w_uk, w_uv, scale: float, *, table=None):
    """q_nope (C, H, N), q_rope (C, H, R); ``rows`` (P, rank + R or wider) the
    prompt's latent buffer; ``allowed`` (C, P) bool; w_uk (rank, H, N), w_uv (rank, H,
    V); ``table`` :func:`tile_table` of ``allowed`` at :func:`tile_sizes`,
    made here unless given. -> (C, H * V) in q's type."""
    return _flash_prefill(q_nope, q_rope, rows, allowed, None, w_uk, w_uv, scale, table,
                          "dsa_flash_prefill")


def latent_flash_prefill(q_nope, q_rope, rows, off, w_uk, w_uv, scale: float, *, table=None):
    """The same attention where every earlier position is visible: ``off``
    the chunk's first position in ``allowed``'s place, ``table``
    :func:`causal_table`'s at :func:`tile_sizes`. -> (C, H * V) in q's type."""
    return _flash_prefill(q_nope, q_rope, rows, None, off, w_uk, w_uv, scale, table,
                          "latent_flash_prefill")


# --------------------------------------------------------------------- decode


def decode_tile_pages(pool_shape: tuple, max_blocks: int, itemsize: int) -> int:
    """Pages a tile of the decode kernel: the most that divide the table's
    row and whose rows are within ``DECODE_TILE_BYTES``."""
    _, _, _, bs, width = pool_shape
    return fit_block(max_blocks, max(DECODE_TILE_BYTES // (bs * width * itemsize), 1))


def decode_takes(heads: int, rank: int, pool_shape: tuple, max_blocks: int,
                 itemsize: int) -> bool:
    """Whether :func:`latent_flash_decode` takes ``heads`` absorbed queries a
    slot over this pool: one row for all heads, the row and the latent rank
    in whole lanes (a page is copied whole, and the value is a lane-aligned
    slice of the row: ``models/latent_sparse.py`` pads such a model's row),
    a page in whole sublane tiles of its type, and the double-buffered tile,
    the query's and the result's blocks and the accumulator within
    ``DECODE_VMEM_BYTES``."""
    _, _, row_heads, bs, width = pool_shape
    tile = decode_tile_pages(pool_shape, max_blocks, itemsize) * bs
    vmem = (2 * tile * width * itemsize + 2 * heads * width * itemsize
            + 3 * heads * (rank + 2 * LANES) * 4 + 2 * heads * tile * 4)
    return (row_heads == 1 and width % LANES == 0 and rank % LANES == 0 and rank <= width
            and bs % (32 // itemsize) == 0 and heads % 8 == 0 and vmem <= DECODE_VMEM_BYTES)


def _decode_kernel(tables_ref, lengths_ref, q_ref, pool_hbm, o_ref,
                   buf, sems, turn, acc_scr, m_scr, l_scr, *,
                   scale: float, layer: int, rank: int, block_size: int, pages: int,
                   slots: int):
    b = pl.program_id(0)
    tile = pages * block_size
    tiles_of = lambda row: (lengths_ref[row] + tile - 1) // tile
    n_tiles = tiles_of(b)
    after = jnp.minimum(b + 1, slots - 1)
    follows = (b + 1 < slots) & (tiles_of(after) > 0)

    def fetch(row, t, which):
        # A page past the length is still a mapped (or the NULL) block:
        # finite bytes that the mask zeroes.
        for p in range(pages):
            pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[row, t * pages + p], 0],
                buf.at[which, pl.ds(p * block_size, block_size), :],
                sems.at[which],
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
        which = jax.lax.rem(first + t, 2)
        more = t + 1 < n_tiles

        @pl.when(more | follows)
        def _():  # this slot's next tile, or after its last the next slot's first
            fetch(jnp.where(more, b, after), jnp.where(more, t + 1, 0), 1 - which)

        # every page's bytes, in one wait
        pltpu.make_async_copy(buf.at[which], buf.at[which], sems.at[which]).wait()
        rows = buf[which]
        m_scr[...], l_scr[...], acc_scr[...] = _softmax_tile(
            q_ref[0], rows, rows[:, :rank], t * tile, lengths_ref[b],
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


def latent_flash_decode(q_abs, pool, layer: int, tables, lengths, *, rank: int, scale: float):
    """``q_abs`` (B, H, W): a slot's absorbed queries ``[q_nope W_uk | q_rope
    | 0]``; ``pool`` (L, blocks, 1, bs, W) the latent pool, rows ``[c_kv |
    k_r | 0]`` in whole lanes, of which layer ``layer`` is read; ``tables`` (B, max_blocks) int32 block
    numbers; ``lengths`` (B,) int32, the positions a slot's query may see (0:
    none, the result zeros). Returns (B, H, rank) float32: ``softmax(scale *
    q rows^T) rows[:, :rank]`` a head over positions ``[0, length)``. Whole
    tiles of :func:`decode_tile_pages` pages are fetched up to the length,
    none past it."""
    pages = decode_tile_pages(pool.shape, tables.shape[1], pool.dtype.itemsize)
    return _decode_call(q_abs, pool, tables.astype(jnp.int32), lengths.astype(jnp.int32),
                        layer=layer, rank=rank, scale=scale, pages=pages,
                        interpret=interpret_mode_default())


# A step program calls this once a layer at the same shapes: under its own jit
# (``kernels/shared_kv_decode.py``'s lesson) a call is a few lines to trace.
@functools.partial(jax.jit, static_argnames=("layer", "rank", "scale", "pages", "interpret"))
def _decode_call(q_abs, pool, tables, lengths, *, layer: int, rank: int, scale: float,
                 pages: int, interpret):
    b, h, w = q_abs.shape
    bs = pool.shape[3]
    tile = pages * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables, lengths: DMA addresses and bounds
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda bi, *_: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, rank), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, rank), F32),
            pltpu.VMEM((h, LANES), F32),
            pltpu.VMEM((h, LANES), F32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, layer=layer, rank=rank, block_size=bs,
                          pages=pages, slots=b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_flash_decode",
    )(tables, lengths, q_abs, pool)
