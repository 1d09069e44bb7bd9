"""A prefill chunk's masked latent attention as one flash kernel.

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
score is one contraction over ``N + R`` where the XLA body adds two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def tile_sizes(C: int, P: int) -> tuple[int, int]:
    """(query rows, keys) a tile for a chunk of ``C`` rows over ``P`` keys."""
    return min(QUERY_TILE, C), min(KEY_TILE, -(-P // LANES) * LANES)


def vmem_bytes(C: int, g: int, rank: int, qk_dim: int, v_dim: int, itemsize: int) -> int:
    """What a grid step of :func:`dsa_flash_prefill` holds in VMEM at the
    largest key tile: the blocks (each twice, for the pipeline), the scratch,
    and a few (query tile, key tile) float32 temporaries of the softmax."""
    tq, tk = min(QUERY_TILE, C), KEY_TILE
    width = rank + qk_dim  # [c_kv | k_r] padded to whole lanes, at most
    blocks = (g * C * qk_dim * itemsize + tk * width * itemsize + C * tk
              + g * width * qk_dim * itemsize + g * rank * v_dim * itemsize
              + C * g * v_dim * itemsize)
    scratch = (g * tk * (qk_dim + v_dim) * itemsize + tq * tk * 4
               + g * C * (v_dim + 2 * LANES) * 4)
    return 2 * blocks + scratch + 4 * tq * tk * 4


def takes(C: int, H: int, rank: int, qk_dim: int, v_dim: int, itemsize: int) -> bool:
    """Whether the kernel takes these shapes: whole query tiles of whole
    sublane groups (the mask is int8: 32 rows), head dims and the latent
    rank in whole lanes, and a chunk whose accumulator, queries and mask fit
    the VMEM asked for (they grow with ``C``: 2048 rows of bfloat16 count 58
    MiB here, 8192 would not fit). ``P`` is padded to whole key tiles, so any
    will do."""
    tq, g = min(QUERY_TILE, C), min(HEAD_GROUP, H)
    return (C % tq == 0 and tq % 32 == 0 and H % g == 0
            and rank % LANES == 0 and qk_dim % LANES == 0 and v_dim % LANES == 0
            and vmem_bytes(C, g, rank, qk_dim, v_dim, itemsize) <= VMEM_LIMIT_BYTES)


def tile_table(allowed, tq: int, tk: int):
    """``any(allowed)`` over each (query tile, key tile): (ceil(C / tq),
    ceil(P / tk)) bool."""
    C, P = allowed.shape
    a = jnp.pad(allowed, ((0, -C % tq), (0, -P % tk)))
    return a.reshape(a.shape[0] // tq, tq, a.shape[1] // tk, tk).any(axis=(1, 3))


def _kernel(tab_ref, kmap_ref, q_ref, rows_ref, mask_ref, wk_ref, wv_ref, o_ref,
            k_scr, v_scr, bias_scr, acc_scr, m_scr, l_scr, *,
            scale: float, rank: int, tq: int, nq: int, nk: int, g: int, v_dim: int):
    del kmap_ref  # the index maps' alone
    j = pl.program_id(1)
    dt = k_scr.dtype

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
            k_scr[h] = jnp.dot(rows_ref[...], wk_ref[h], preferred_element_type=F32).astype(dt)
            v_scr[h] = jnp.dot(rows_ref[:, :rank], wv_ref[h],
                               preferred_element_type=F32).astype(dt)
            return carry

        jax.lax.fori_loop(0, g, expand, 0)

        def query_tile(i, carry):
            @pl.when(tab_ref[i * nk + j] != 0)
            def _():
                r = pl.ds(pl.multiple_of(i * tq, tq), tq)
                ok = mask_ref[r, :].astype(jnp.int32) != 0
                bias_scr[...] = jnp.where(ok, 0.0, NEG)

                def head(h, carry):
                    s = jax.lax.dot_general(
                        q_ref[h, r, :], k_scr[h], (((1,), (1,)), ((), ())),
                        preferred_element_type=F32)  # (tq, tk)
                    s = s * scale + bias_scr[...]
                    m_prev = m_scr[h, r, :]  # (tq, LANES), a row's value in every lane
                    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                    m_safe = jnp.where(m_new == NEG, 0.0, m_new)
                    alpha = jnp.exp(m_prev - m_safe)
                    p = jnp.exp(s - m_safe[:, :1])
                    l_scr[h, r, :] = l_scr[h, r, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
                    m_scr[h, r, :] = m_new
                    acc_scr[h, r, :] = acc_scr[h, r, :] * alpha[:, :1] + jnp.dot(
                        p.astype(dt), v_scr[h], preferred_element_type=F32)
                    return carry

                jax.lax.fori_loop(0, g, head, 0)

            return carry

        jax.lax.fori_loop(0, nq, query_tile, 0)

    @pl.when(j == nk - 1)
    def _():
        for h in range(g):
            l = l_scr[h][:, :1]
            o_ref[:, h * v_dim:(h + 1) * v_dim] = (
                acc_scr[h] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def dsa_flash_prefill(q_nope, q_rope, rows, allowed, w_uk, w_uv, scale: float, *, table=None):
    """q_nope (C, H, N), q_rope (C, H, R); ``rows`` (P, rank + R) the prompt's
    latent buffer; ``allowed`` (C, P) bool; w_uk (rank, H, N), w_uv (rank, H,
    V); ``table`` :func:`tile_table` of ``allowed`` at :func:`tile_sizes`,
    made here unless given. -> (C, H * V) in q's type."""
    C, H, N = q_nope.shape
    P, width = rows.shape
    rank, _, V = w_uv.shape
    R = width - rank
    dt = q_nope.dtype
    tq, tk = tile_sizes(C, P)
    g = min(HEAD_GROUP, H)
    assert C % tq == 0 and H % g == 0, (C, tq, H, g)
    if table is None:
        table = tile_table(allowed, tq, tk)
    nq, nk = table.shape
    assert nq == C // tq and nk == -(-P // tk), (table.shape, C, P, tq, tk)
    p_pad, w_pad = nk * tk - P, -width % LANES
    # Heads lead, so that a head's tile is a block of whole rows.
    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(1, 0, 2)  # (H, C, N + R)
    rows = jnp.pad(rows, ((0, p_pad), (0, w_pad)))
    mask = jnp.pad(allowed, ((0, 0), (0, p_pad))).astype(jnp.int8)
    # [c_kv | k_r | 0] @ wk = [c_kv @ W_uk | k_r]
    wk = jnp.zeros((H, width + w_pad, N + R), dt)
    wk = wk.at[:, :rank, :N].set(w_uk.transpose(1, 0, 2))
    wk = wk.at[:, rank:width, N:].set(jnp.eye(R, dtype=dt))
    wv = w_uv.transpose(1, 0, 2)
    seen = table.any(axis=0)
    # A key tile nobody sees names the block fetched last, which is no fetch.
    kmap = jax.lax.cummax(jnp.where(seen, jnp.arange(nk, dtype=jnp.int32), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // g, nk),
        in_specs=[
            pl.BlockSpec((g, C, N + R), lambda G, j, tab, km: (G, 0, 0)),
            pl.BlockSpec((tk, width + w_pad), lambda G, j, tab, km: (km[j], 0)),
            pl.BlockSpec((C, tk), lambda G, j, tab, km: (0, km[j])),
            pl.BlockSpec((g, width + w_pad, N + R), lambda G, j, tab, km: (G, 0, 0)),
            pl.BlockSpec((g, rank, V), lambda G, j, tab, km: (G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((C, g * V), lambda G, j, tab, km: (0, G)),
        scratch_shapes=[
            pltpu.VMEM((g, tk, N + R), dt),   # K of the key tile
            pltpu.VMEM((g, tk, V), dt),       # V of the key tile
            pltpu.VMEM((tq, tk), F32),        # 0 where allowed, -inf where not
            pltpu.VMEM((g, C, V), F32),       # the accumulator
            pltpu.VMEM((g, C, LANES), F32),   # running max
            pltpu.VMEM((g, C, LANES), F32),   # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, tq=tq, nq=nq, nk=nk, g=g, v_dim=V),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * V), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret_mode_default(),
        name="dsa_flash_prefill",
    )(table.reshape(-1).astype(jnp.int32), kmap, q, rows, mask, wk, wv)
