"""The shard-local math of ``models/sparse_linear.py``: a lightning
(decayed linear attention) mixer and a block-sparse attention mixer whose
selection reads pooled keys alone. Plain XLA but for the lightning chunk
(``kernels/lightning_attn.py``), a chunk's selection scores and the two
attends (``kernels/block_sparse_attn.py``), which run where the shapes let them
(heads in whole lanes) and have a ``jax.numpy`` form here for the rest.

* **Pooled keys**: ``c_j = mean(k[s j : s j + K])``, a K/V head's, kept a
  slot beside the pool. :func:`pool_chunk` makes the ones a prefill chunk
  completes from the prompt's running K rows; :func:`pool_step` the one a
  decode step may complete, from the pool's last ``K`` rows of the slot.
* **Selection** (under ``jax.named_scope("bsa_select")`` in the model):
  :func:`group_scores` is ``r_g(j)``, the group's sum of each head's softmax
  over the pooled keys wholly visible; :func:`block_scores` the largest
  ``r_g(j)`` over the pooled keys whose span meets a block;
  :func:`select_blocks` the first ``init_blocks``, the window's blocks and
  the best of the rest up to ``topk``, ties to the lower index, by rank (a
  block is taken if fewer than ``topk`` blocks beat it): no sort, exact.
  :func:`selected_lists` turns a selection into a list of blocks, which
  through a slot's table row is a list of pages.
* **Attend**: :func:`attend_chunk` (a chunk's queries over the prompt's
  buffers) and :func:`attend_step` (a slot's query over its pages), each the
  kernel or the ``jax.numpy`` form by shape.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels import block_sparse_attn as bsa
from triton_dist_tpu.kernels import lightning_attn as la
from triton_dist_tpu.layers.hybrid_ssm import swiglu  # noqa: F401
from triton_dist_tpu.layers.latent_sparse import mm  # noqa: F401

F32 = jnp.float32
NEG = -jnp.inf


def rms(x, weight, eps):
    """RMSNorm over the last axis, float32 inside, in ``weight``'s type."""
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(weight.dtype) * weight


def rope_half(x, pos, theta: float):
    """Rotate-half rotary embedding over the whole last axis; ``pos``
    broadcasts against ``x.shape[:-2]`` (x is (..., heads, D))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.asarray(pos, F32)[..., None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# --------------------------------------------------------------- lightning


def lightning_chunk(q, k, v, s0, n_real):
    """q, k, v (C, H, D); ``s0`` (H, D, D) float32 -> (o (C, H, D) float32
    unscaled, the state after the chunk's last real row)."""
    C, H, D = q.shape
    slope = la.slopes(H)
    if la.takes(D):
        flat = lambda z: z.reshape(C, H * D)
        o, S = la.lightning_chunk(flat(q), flat(k), flat(v), s0, slope, n_real)
        return o.reshape(C, H, D), S
    return la.lightning_chunk_xla(q, k, v, s0, slope, n_real)


def lightning_step(q, k, v, S, active):
    """q, k, v (B, H, D); ``S`` (B, H, D, D) float32 -> (o (B, H, D) float32
    unscaled, S')."""
    return la.lightning_step(q, k, v, S, la.slopes(q.shape[1]), active)


# ------------------------------------------------------------- pooled keys


def pool_chunk(k_rows, off, C: int, kernel: int, stride: int):
    """The pooled keys a chunk of ``C`` rows at ``off`` (a multiple of
    ``stride``) completes. ``k_rows`` (P, W) the prompt's K rows with the
    chunk's written. Returns (j (n,) int32: the pooled key's index, past
    every extent where it is no key (one that begins before position 0, or
    is not whole within the prompt), values (n, W) in ``k_rows``' type)."""
    P, W = k_rows.shape
    n = -(-C // stride)
    lead = kernel - stride  # rows before the chunk that its first keys span
    rows = off - lead + jnp.arange(n * stride + lead, dtype=jnp.int32)
    seg = k_rows[jnp.clip(rows, 0, P - 1)].astype(F32)
    parts = seg.reshape(n + lead // stride, stride, W).sum(axis=1)
    win = sum(parts[t:t + n] for t in range(kernel // stride)) / kernel
    j = off // stride - lead // stride + jnp.arange(n, dtype=jnp.int32)
    last = jnp.minimum(off + C, P) - 1
    whole = (j >= 0) & (stride * j + kernel - 1 <= last)
    return jnp.where(whole, j, jnp.iinfo(jnp.int32).max), win.astype(k_rows.dtype)


def pool_step(pool, tables, pos, active, kernel: int, stride: int):
    """The pooled key a decode step at ``pos`` (B,) completes, from the last
    ``kernel`` K rows of each slot in ``pool`` (pages, 1, bs, W) (the step's
    own row written). Returns (j (B,), past every extent where the step
    completes none, values (B, W))."""
    bs = pool.shape[2]
    rows = jnp.maximum(pos[:, None] - kernel + 1 + jnp.arange(kernel, dtype=jnp.int32), 0)
    blk = jnp.take_along_axis(tables, rows // bs, axis=1)
    win = jnp.mean(pool[blk, 0, rows % bs].astype(F32), axis=1)
    first = pos - kernel + 1
    done = active & (first >= 0) & (first % stride == 0)
    return jnp.where(done, first // stride, jnp.iinfo(jnp.int32).max), win.astype(pool.dtype)


# --------------------------------------------------------------- selection


def group_scores(q, pooled, q_pos, kernel: int, stride: int, off=None):
    """q (T, Hkv, G, D); ``pooled`` (NP, Hkv, D); ``q_pos`` (T,). Returns
    ``r`` (Hkv, T, NP) float32: over the group's heads, the sum of each
    head's softmax (scale ``1 / sqrt(D)``) over the pooled keys wholly
    visible from the query (``stride j + kernel - 1 <= q_pos``); 0 at a key
    that is not, and everywhere for a query that sees none. A chunk's
    queries (``off`` given: ``q_pos`` is ``off + arange(T)``) go through the
    kernel where the shapes let them."""
    D = q.shape[-1]
    if off is not None and in_kernel(q):
        return bsa.bsa_group_scores(q, pooled.reshape(pooled.shape[0], -1), off,
                                    kernel=kernel, stride=stride)
    sc = jnp.einsum("tgrd,jgd->gtrj", q, pooled, preferred_element_type=F32) / math.sqrt(D)
    seen_at = stride * jnp.arange(pooled.shape[0], dtype=jnp.int32) + kernel - 1
    ok = (seen_at[None, :] <= q_pos[:, None])[None, :, None, :]
    sc = jnp.where(ok, sc, NEG)
    m = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(sc - jnp.where(m == NEG, 0.0, m)), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.sum(e / jnp.where(l > 0, l, 1.0), axis=2)


def block_scores(r, nb: int, kernel: int, stride: int, block: int):
    """``r`` (..., NP) -> (..., nb): a block's score is the largest ``r``
    over the pooled keys whose span meets it (-1 where none does)."""
    ratio, extra = block // stride, (kernel - 1) // stride
    want = ratio * nb + extra  # r_pad[ratio b + t] is key ratio b + t - extra
    r = r[..., :want - extra]
    pad = [(0, 0)] * (r.ndim - 1) + [(extra, want - extra - r.shape[-1])]
    r = jnp.pad(r, pad, constant_values=-1.0)
    score = r[..., 0:ratio * nb:ratio]
    for t in range(1, ratio + extra):
        score = jnp.maximum(score, r[..., t:t + ratio * nb:ratio])
    return score


def select_blocks(score, own, topk: int, init_blocks: int, window_blocks: int):
    """``score`` (..., T, nb); ``own`` (T,) the block of each query's own
    position. Returns (sel (..., T, nb) bool, forced (T, nb) bool): the blocks
    a query takes, and those among them it takes whatever the scores (the
    first ``init_blocks`` and the ``window_blocks`` ending at its own)."""
    nb = score.shape[-1]
    blocks = jnp.arange(nb, dtype=jnp.int32)
    visible = blocks[None, :] <= own[:, None]
    forced = visible & ((blocks[None, :] < init_blocks)
                        | (blocks[None, :] > own[:, None] - window_blocks))
    key = jnp.where(forced, jnp.inf, jnp.where(visible, score, NEG))
    mine, other = key[..., :, None], key[..., None, :]
    ahead = (other > mine) | ((other == mine) & (blocks[None, :] < blocks[:, None]))
    rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
    return (rank < topk) & visible, forced


def selected_lists(sel, topk: int):
    """``sel`` (..., nb) bool -> (blocks (..., topk) int32 ascending, the
    first ``counts`` (...,) of them real)."""
    nb = sel.shape[-1]
    order = jnp.argsort(~sel, axis=-1, stable=True).astype(jnp.int32)
    if nb < topk:
        order = jnp.pad(order, [(0, 0)] * (sel.ndim - 1) + [(0, topk - nb)])
    return order[..., :topk], jnp.sum(sel, axis=-1, dtype=jnp.int32)


# ------------------------------------------------------------------ attend


def attend_xla(q, k, v, sel, q_pos, block: int):
    """q (T, Hkv, G, D); k, v (S, Hkv, D); ``sel`` (Hkv, T, nb) -> (T, Hkv,
    G, D) float32: softmax over the positions ``<= q_pos`` of the selected
    blocks, zeros for a query with none."""
    D = q.shape[-1]
    key = jnp.arange(k.shape[0], dtype=jnp.int32)
    allowed = sel[:, :, key // block] & (key[None, :] <= q_pos[:, None])[None]
    s = jnp.einsum("tgrd,sgd->gtrs", q, k, preferred_element_type=F32) / math.sqrt(D)
    s = jnp.where(allowed[:, :, None, :], s, NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(allowed[:, :, None, :], jnp.exp(s - jnp.where(m == NEG, 0.0, m)), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = (e / jnp.where(l > 0, l, 1.0)).astype(v.dtype)
    return jnp.einsum("gtrs,sgd->tgrd", p, v, preferred_element_type=F32)


def in_kernel(q, block: int = bsa.LANES) -> bool:
    """Whether these queries (..., G, D) go through the kernels: over blocks
    of ``block`` positions for the attends, whatever the block for the
    selection's scores."""
    return bsa.takes(q.shape[-2], q.shape[-1], block, q.dtype.itemsize)


def attend_chunk(q, k_rows, v_rows, sel, off, block: int):
    """A prefill chunk's attend. q (C, Hkv, G, D) at positions ``off ...``;
    ``k_rows``, ``v_rows`` (P, Hkv * D). Returns ((C, Hkv, G, D) in q's type,
    the pages it fetched)."""
    C, hkv, G, D = q.shape
    P = k_rows.shape[0]
    if in_kernel(q, block):
        tq, tk = bsa.prefill_tiles(C, P, block)
        table = bsa.tile_table(sel, tq, tk // block)
        out = bsa.bsa_prefill(q, k_rows, v_rows, sel, off, block=block,
                              scale=1.0 / math.sqrt(D), table=table)
        return out, (tk // block) * jnp.sum(table, dtype=jnp.int32)
    heads = lambda z: z.reshape(P, hkv, D)
    q_pos = off + jnp.arange(C, dtype=jnp.int32)
    out = attend_xla(q, heads(k_rows), heads(v_rows), sel, q_pos, block)
    return out.astype(q.dtype), jnp.int32(hkv * -(-P // block))


def attend_step(q, k_pool, v_pool, layer: int, tables, sel, seen, topk: int):
    """A decode step's attend. q (B, Hkv, G, D); the pools (L, pages, 1, bs,
    Hkv * D); ``sel`` (B, Hkv, nb) with ``nb`` the table's extent in blocks;
    ``seen`` (B,) the positions a slot's row may see (0: none). Returns
    ((B, Hkv, G, D) float32, the pages it fetched)."""
    B, hkv, G, D = q.shape
    bs = k_pool.shape[3]
    if in_kernel(q, bs):
        blocks, counts = selected_lists(sel, topk)
        counts = jnp.where(seen[:, None] > 0, counts, 0)
        out = bsa.bsa_decode(q, k_pool, v_pool, layer, tables, blocks, counts, seen,
                             scale=1.0 / math.sqrt(D))
        pages = bsa.decode_pages(topk, bs)
        return out, jnp.sum(-(-counts // pages) * pages, dtype=jnp.int32)
    # the pool gathered through the table at its whole extent
    through = lambda pool: jnp.take(pool[layer, :, 0], tables, axis=0, mode="clip").reshape(
        B, tables.shape[1] * bs, hkv, D)
    one = lambda q1, k1, v1, s1, n1: attend_xla(
        q1[None], k1, v1, s1[:, None], (n1 - 1)[None], bs)[0]
    out = jax.vmap(one)(q, through(k_pool), through(v_pool), sel, seen)
    return out, hkv * tables.shape[1] * jnp.sum(seen > 0, dtype=jnp.int32)
