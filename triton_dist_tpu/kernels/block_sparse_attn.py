"""Attention over the blocks a query group selected, and nothing else.

A K/V head's query group (``G`` query heads) takes a list of blocks of ``B``
positions a query (``layers/sparse_linear.py`` makes it, from pooled keys
alone) and attends the positions at or before its own in those blocks.

:func:`bsa_group_scores` is the part of the selection that is matrix work,
for a prefill chunk: each query head's softmax over the pooled keys it can
wholly see, summed over the group, a tile of queries at a time with the
heads' ``(tq, NP)`` scores in VMEM only. Its ``pallas_call`` is named
``bsa_select``, which is how a device trace finds the selection's time.

:func:`bsa_prefill` serves a prefill chunk: a flash kernel over the chunk's
query tiles against the prompt's K and V rows as they lie in the running
buffers (``(P, Hkv * D)``, a head a block of columns). Grid (K/V head, query
tile, key tile), the key tiles sequential. **One table decides what is
fetched and computed**: whether some query of the tile selected some block
of the key tile (:func:`tile_table`; that covers the key tiles past the
diagonal too), handed over as scalar prefetch; a key tile nobody selected
names the block fetched last, which is no fetch. Inside a visited tile the
selection is applied a block at a time: the tile's ``(queries, blocks)``
selection times a 0/1 ``(blocks, keys)`` matrix on the MXU gives each
(query, key) its block's flag, then causality; the group's heads share that
bias and each keeps its own online softmax (running max, sum and ``(G, tq,
D)`` accumulator in VMEM scratch across the key tiles). Scores, their exp
and ``p`` never reach HBM.

:func:`bsa_decode` serves a decode step: ``flash_decode``'s table walk, but
over the ``topk`` pages a (slot, K/V head) selected instead of a slot's
whole table. The block pool's page is the selection's block, so the list of
blocks through the slot's table row IS the list of pages: the pools stay in
HBM, a page is one copy (its head's ``D`` columns of ``B`` rows) into a
double-buffered VMEM tile of a few pages, the group's ``G`` query rows meet
a tile in two MXU products. Never a gather of the table's whole extent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.kernels.gemm import fit_block
from triton_dist_tpu.runtime.platform import interpret_mode_default

F32 = jnp.float32
NEG = -jnp.inf
LANES = 128
#: Query rows and keys a tile of the prefill kernel.
QUERY_TILE = 256
KEY_TILE = 512
#: Keys a tile of the decode kernel (whole pages).
DECODE_TILE = 512
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def takes(group: int, head_dim: int, block: int, itemsize: int) -> bool:
    """Whether the kernels take these shapes: the head in whole lanes, a
    block in whole sublane tiles that divides a lane tile's worth of keys,
    the group's rows a whole sublane tile of the operands' type."""
    sub = 32 // itemsize
    return (head_dim % LANES == 0 and block % sub == 0 and LANES % block == 0
            and group % sub == 0)


# ---------------------------------------------------------------- selection


def _scores_kernel(off_ref, q_ref, c_ref, r_ref, *, scale: float, kernel: int, stride: int,
                   tq: int, group: int):
    i = pl.program_id(1)
    n = c_ref.shape[0]
    seen_at = stride * jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) + kernel - 1
    q_pos = off_ref[0] + i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    ok = seen_at <= q_pos
    r_ref[0] = jnp.zeros((tq, n), F32)

    def head(h, carry):
        s = jax.lax.dot_general(q_ref[0, h], c_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        s = jnp.where(ok, s, NEG)
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.where(ok, jnp.exp(s - jnp.where(m == NEG, 0.0, m)), 0.0)
        l = jnp.sum(e, axis=1, keepdims=True)
        r_ref[0] += e / jnp.where(l > 0, l, 1.0)
        return carry

    jax.lax.fori_loop(0, group, head, 0)


def bsa_group_scores(q, pooled, off, *, kernel: int, stride: int):
    """The selection's scores for a prefill chunk, as the kernel named
    ``bsa_select``. q (C, Hkv, G, D) at positions ``off ...``; ``pooled``
    (NP, Hkv * D), key ``j`` the mean of K rows ``stride j ... stride j +
    kernel - 1``. Returns ``r`` (Hkv, C, NP) float32: over the group's
    heads, the sum of each head's softmax (scale ``1 / sqrt(D)``) over the
    pooled keys wholly visible from the query; 0 at a key that is not. The
    ``(heads, C, NP)`` scores, their exp and the softmax never reach HBM."""
    C, hkv, G, D = q.shape
    NP = pooled.shape[0]
    tq = min(QUERY_TILE, -(-C // 16) * 16)
    c_pad, n_pad = -C % tq, -NP % LANES
    q = jnp.pad(q.transpose(1, 2, 0, 3), ((0, 0), (0, 0), (0, c_pad), (0, 0)))
    pooled = jnp.pad(pooled, ((0, n_pad), (0, 0)))
    n = NP + n_pad
    r = pl.pallas_call(
        functools.partial(_scores_kernel, scale=D ** -0.5, kernel=kernel, stride=stride,
                          tq=tq, group=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hkv, (C + c_pad) // tq),
            in_specs=[pl.BlockSpec((1, G, tq, D), lambda g, i, *_: (g, 0, i, 0)),
                      pl.BlockSpec((n, D), lambda g, i, *_: (0, g))],
            out_specs=pl.BlockSpec((1, tq, n), lambda g, i, *_: (g, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((hkv, C + c_pad, n), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret_mode_default(),
        name="bsa_select",
    )(jnp.reshape(off, (1,)).astype(jnp.int32), q, pooled)
    return r[:, :C, :NP]


# ------------------------------------------------------------------ prefill


def prefill_tiles(C: int, P: int, block: int) -> tuple[int, int]:
    """(query rows, keys) a tile for a chunk of ``C`` rows over ``P`` keys."""
    tq = min(QUERY_TILE, -(-C // 16) * 16)
    tk = min(KEY_TILE, -(-P // LANES) * LANES)
    assert tk % block == 0, (tk, block)
    return tq, tk


def tile_table(sel, tq: int, pages: int):
    """``sel`` (Hkv, C, NB) bool, the blocks a query selected (none past its
    own) -> (Hkv, ceil(C / tq), ceil(NB / pages)) bool: whether any query of
    the tile selected any block of the key tile."""
    hkv, C, NB = sel.shape
    a = jnp.pad(sel, ((0, 0), (0, -C % tq), (0, -NB % pages)))
    return a.reshape(hkv, a.shape[1] // tq, tq, a.shape[2] // pages, pages).any(axis=(2, 4))


def _prefill_kernel(tab_ref, kmap_ref, off_ref, q_ref, k_ref, v_ref, sel_ref, o_ref,
                    bias_scr, acc_scr, m_scr, l_scr, *,
                    scale: float, block: int, tq: int, tk: int, nq: int, nk: int, group: int):
    del kmap_ref  # the index maps' alone
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    dt = q_ref.dtype

    @pl.when(j == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(tab_ref[(g * nq + i) * nk + j] != 0)
    def _():
        nb = sel_ref.shape[-1]
        key_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        first = block * jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
        in_block = ((key_pos >= first) & (key_pos < first + block)).astype(dt)  # (nb, tk)
        flag = jnp.dot(sel_ref[0], in_block, preferred_element_type=F32)  # (tq, tk)
        q_pos = off_ref[0] + i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        bias_scr[...] = jnp.where((flag > 0.5) & (key_pos <= q_pos), 0.0, NEG)

        def head(h, carry):
            s = jax.lax.dot_general(q_ref[0, h], k_ref[...], (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
            s = s * scale + bias_scr[...]
            m_prev = m_scr[h]  # (tq, LANES), a row's value in every lane
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(m_new == NEG, 0.0, m_new)
            alpha = jnp.exp(m_prev - m_safe)
            p = jnp.exp(s - m_safe[:, :1])
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * alpha[:, :1] + jnp.dot(
                p.astype(dt), v_ref[...], preferred_element_type=F32)
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[...][:, :, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def bsa_prefill(q, k_rows, v_rows, sel, off, *, block: int, scale: float, table=None):
    """q (C, Hkv, G, D): a chunk's queries at positions ``off ...``;
    ``k_rows``, ``v_rows`` (P, Hkv * D) the prompt's running buffers; ``sel``
    (Hkv, C, NB) bool with ``NB = ceil(P / block)``: the blocks each query of
    a group takes, none past its own; ``table`` :func:`tile_table` of ``sel``
    at :func:`prefill_tiles`, made here unless given. -> (C, Hkv, G, D) in
    q's type: softmax over the positions ``<= off + row`` of the row's
    blocks (zeros for a row with none)."""
    C, hkv, G, D = q.shape
    P = k_rows.shape[0]
    dt = q.dtype
    tq, tk = prefill_tiles(C, P, block)
    pages = tk // block
    if table is None:
        table = tile_table(sel, tq, pages)
    _, nq, nk = table.shape
    assert nq == -(-C // tq) and nk == -(-P // tk), (table.shape, C, P, tq, tk)
    c_pad, p_pad = nq * tq - C, nk * tk - P
    nb = -(-(nk * pages) // LANES) * LANES
    q = jnp.pad(q.transpose(1, 2, 0, 3), ((0, 0), (0, 0), (0, c_pad), (0, 0)))  # (Hkv, G, C, D)
    if p_pad:
        k_rows, v_rows = (jnp.pad(z, ((0, p_pad), (0, 0))) for z in (k_rows, v_rows))
    sel = jnp.pad(sel, ((0, 0), (0, c_pad), (0, nb - sel.shape[2]))).astype(dt)
    # A key tile nobody selected names the tile fetched last, which is no fetch.
    kmap = jax.lax.cummax(
        jnp.where(table, jnp.arange(nk, dtype=jnp.int32), 0), axis=2)
    flat = lambda t: t.reshape(-1).astype(jnp.int32)
    at = lambda g, i, j, tab, km: km[(g * nq + i) * nk + j]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, G, tq, D), lambda g, i, j, *_: (g, 0, i, 0)),
            pl.BlockSpec((tk, D), lambda g, i, j, tab, km, off: (at(g, i, j, tab, km), g)),
            pl.BlockSpec((tk, D), lambda g, i, j, tab, km, off: (at(g, i, j, tab, km), g)),
            pl.BlockSpec((1, tq, nb), lambda g, i, j, *_: (g, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, tq, D), lambda g, i, j, *_: (g, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, tk), F32),        # 0 where allowed, -inf where not
            pltpu.VMEM((G, tq, D), F32),      # the accumulator
            pltpu.VMEM((G, tq, LANES), F32),  # running max
            pltpu.VMEM((G, tq, LANES), F32),  # running sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block=block, tq=tq, tk=tk,
                          nq=nq, nk=nk, group=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, G, nq * tq, D), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret_mode_default(),
        name="bsa_prefill",
    )(flat(table), flat(kmap), jnp.reshape(off, (1,)).astype(jnp.int32), q, k_rows, v_rows, sel)
    return out[:, :, :C].transpose(2, 0, 1, 3)


# ------------------------------------------------------------------- decode


def decode_pages(topk: int, block: int) -> int:
    """Pages a tile of the decode kernel: the most that divide ``topk`` and
    keep a tile within ``DECODE_TILE`` keys."""
    return fit_block(topk, max(DECODE_TILE // block, 1))


def _decode_kernel(phys_ref, logical_ref, count_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, acc_scr, m_scr, l_scr, *,
                   scale: float, layer: int, block: int, pages: int, topk: int, hkv: int,
                   head_dim: int):
    b = pl.program_id(0)
    tile = pages * block
    length = lengths_ref[b]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    for g in range(hkv):  # static: a head's columns of a page are a static slice
        base = (b * hkv + g) * topk
        n_tiles = (count_ref[b * hkv + g] + pages - 1) // pages

        def fetch(t, buf, g=g, base=base):
            # An entry past the count names the NULL block: finite bytes
            # that the mask zeroes.
            for p in range(pages):
                phys = phys_ref[base + t * pages + p]
                for i, (pool, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                    pltpu.make_async_copy(
                        pool.at[layer, phys, 0, :, pl.ds(g * head_dim, head_dim)],
                        dst.at[buf, pl.ds(p * block, block), :],
                        sems.at[buf, i],
                    ).start()

        @pl.when(n_tiles > 0)
        def _():
            fetch(0, 0)

        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)

        def tile_step(t, carry, g=g, base=base, fetch=fetch, n_tiles=n_tiles):
            buf = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n_tiles)
            def _():
                fetch(t + 1, 1 - buf)

            for i, dst in enumerate((k_buf, v_buf)):  # every page's bytes, in one wait
                pltpu.make_async_copy(dst.at[buf], dst.at[buf], sems.at[buf, i]).wait()
            # a column's limit: how many of its page's positions the row may see
            limit = jnp.zeros((1, tile), jnp.int32)
            for p in range(pages):
                entry = t * pages + p
                seen = jnp.clip(length - logical_ref[base + entry] * block, 0, block)
                seen = jnp.where(entry < count_ref[b * hkv + g], seen, 0)
                limit = jnp.where((col >= p * block) & (col < (p + 1) * block),
                                  p * block + seen, limit)
            s = jax.lax.dot_general(q_ref[0, g], k_buf[buf], (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            s = jnp.where(col < limit, s, NEG)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(m_new == NEG, 0.0, m_new)
            alpha = jnp.exp(m_prev - m_safe)
            p_ = jnp.exp(s - m_safe[:, :1])
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p_, axis=1, keepdims=True)
            m_scr[...] = m_new
            acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
                p_.astype(v_buf.dtype), v_buf[buf], preferred_element_type=F32)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile_step, 0)
        l = l_scr[:, :1]
        o_ref[0, g] = acc_scr[...] / jnp.where(l > 0, l, 1.0)


def bsa_decode(q, k_pool, v_pool, layer: int, tables, blocks, counts, lengths, *, scale: float):
    """q (B, Hkv, G, D): a slot's query rows; ``k_pool``, ``v_pool`` (L,
    pages, 1, block, Hkv * D); ``layer`` which of the pools' layers;
    ``tables`` (B, max_blocks) int32; ``blocks`` (B, Hkv, topk) int32: the
    blocks a (slot, K/V head) selected, the first ``counts`` (B, Hkv) of them
    real; ``lengths`` (B,) int32 the positions a slot's row may see. Returns
    (B, Hkv, G, D) float32: softmax over the positions ``< length`` of the
    selected blocks (zeros where ``counts`` is 0). Whole tiles of
    :func:`decode_pages` pages are fetched up to the count, none past it."""
    B, hkv, G, D = q.shape
    block = k_pool.shape[3]
    topk = blocks.shape[2]
    pages = decode_pages(topk, block)
    real = jnp.arange(topk, dtype=jnp.int32)[None, None, :] < counts[:, :, None]
    logical = jnp.where(real, blocks, 0).astype(jnp.int32)
    phys = jnp.where(real, jnp.take_along_axis(
        tables.astype(jnp.int32)[:, None, :], logical, axis=2), 0)
    return _decode_call(phys.reshape(-1), logical.reshape(-1),
                        counts.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
                        q, k_pool, v_pool, scale=scale, layer=layer, pages=pages, topk=topk,
                        interpret=interpret_mode_default())


@functools.partial(jax.jit, static_argnames=("scale", "layer", "pages", "topk", "interpret"))
def _decode_call(phys, logical, counts, lengths, q, k_pool, v_pool, *, scale: float, layer: int,
                 pages: int, topk: int, interpret):
    B, hkv, G, D = q.shape
    block = k_pool.shape[3]
    tile = pages * block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # pages, blocks, counts, lengths: DMA addresses and bounds
        grid=(B,),
        in_specs=[pl.BlockSpec((1, hkv, G, D), lambda b, *_: (b, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hkv, G, D), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, D), k_pool.dtype),
            pltpu.VMEM((2, tile, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((G, D), F32),
            pltpu.VMEM((G, LANES), F32),
            pltpu.VMEM((G, LANES), F32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, layer=layer, block=block, pages=pages,
                          topk=topk, hkv=hkv, head_dim=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, G, D), F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bsa_decode",
    )(phys, logical, counts, lengths, q, k_pool, v_pool)
