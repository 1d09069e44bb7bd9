"""Latent attention, a learned sparse selection, and a sigmoid-routed expert
layer that is told which experts it holds: the shard-local math of
``models/latent_sparse.py``, in plain XLA but for the selection's k-th
value (``kernels/kth_value.py``) and a prefill chunk's attention under the
selection (``kernels/latent_flash.py``); the cell that runs these shows
which part a later change should replace next.

* **Latent attention.** A token's cache row is ``[c_kv | k_r]``: the
  normalised KV latent and one roped key part shared by all heads. Prefill
  attends in the *expanded* form (per-head K and V made from the rows, a
  block of keys at a time, online softmax: at serving shapes one flash
  kernel, ``kernels/latent_flash.py``), decode in the *absorbed* form
  (scores and the weighted sum taken in the latent space: over gathered rows
  where a selection names them, :func:`attend_absorbed`, and over the whole
  visible extent where the layer has no indexer, read in the pool where it
  lies, :func:`attend_absorbed_paged`). The two give the same numbers
  (``tests/test_latent_sparse.py``, ``tests/test_latent_dense.py``). The
  softmax scale and the rotary's frequency table are the configuration's
  (``softmax_scale``, :func:`rope_freqs`): a plain table, or YaRN's blended
  one with its ``mscale ** 2`` on the scale (:class:`Yarn`).
* **Sparse selection.** Index scores ``I[t, s] = sum_h w[t, h] relu(q[t, h]
  . k[s])`` over the visible cache, and exactly the ``k`` largest a query,
  ties to the lower position: ``lax.top_k``'s set. Prefill finds a row's
  k-th largest score by bisection over its bits, without a sort, and
  carries the selection as a mask over the prompt's buffer; decode asks
  ``lax.top_k`` for the positions.
* **Experts.** Sigmoid scores over every published expert, the top ``k`` by
  score plus bias, gates from the scores alone; of the chosen, only those
  held here are computed, sorted by expert and taken a tile of rows at a
  time. A tile belongs to one expert and the loop runs over the tiles that
  exist, so no capacity binds and no row is ever dropped; an expert no row
  chose is not read.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.kernels import latent_flash
from triton_dist_tpu.kernels.kth_value import kth_value
from triton_dist_tpu.layers.tp import RMSNorm

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
NEG = -jnp.inf


def mm(a, b, out_dtype=None):
    """a @ b accumulated in float32, in ``a``'s type unless told otherwise."""
    out = jnp.dot(a, b, preferred_element_type=F32)
    return out.astype(a.dtype if out_dtype is None else out_dtype)


def rms_norm(x, weight, eps):
    return RMSNorm(weight=weight, eps=eps)(x)


def layer_norm(x, weight, bias, eps):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight + bias


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's scaling of a rotary table, as the DeepSeek-V3 family's
    ``rope_scaling`` states it: positions stretched ``factor`` times past
    ``original_max`` on the slow pairs, left alone on the fast ones, a ramp
    between the pairs that turn ``beta_fast`` and ``beta_slow`` times over
    the original context."""

    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _m(self, t: float) -> float:
        return 0.1 * t * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0

    def ramp_ends(self, dim: int, theta: float) -> tuple[int, int]:
        """(low, high): the pairs between which the table blends."""
        corr = lambda n: dim * math.log(self.original_max / (n * 2 * math.pi)) / (
            2 * math.log(theta))
        return (max(math.floor(corr(self.beta_fast)), 0),
                min(math.ceil(corr(self.beta_slow)), dim - 1))

    def inv_freq(self, dim: int, theta: float):
        """(dim // 2,) float64: ``f / factor`` where the ramp is 1, ``f``
        where it is 0."""
        extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        low, high = self.ramp_ends(dim, theta)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        return extra / self.factor * ramp + extra * (1.0 - ramp)

    @property
    def rope_mscale(self) -> float:
        """What cos and sin are multiplied by."""
        return self._m(self.mscale) / self._m(self.mscale_all_dim)

    @property
    def softmax_mscale(self) -> float:
        """What the softmax scale is multiplied by."""
        return self._m(self.mscale_all_dim) ** 2 if self.mscale_all_dim else 1.0


def rope_freqs(dim: int, c):
    """(dim // 2,) float32 frequencies of a rotary over ``dim`` values under
    configuration ``c`` (``rope_theta``, ``rope_scaling``), and what cos and
    sin are multiplied by: the one place the table is made."""
    half = dim // 2
    if c.rope_scaling is None:
        return c.rope_theta ** (-jnp.arange(half, dtype=F32) / half), 1.0
    y = c.rope_scaling
    return jnp.asarray(y.inv_freq(dim, c.rope_theta), F32), y.rope_mscale


def rope_interleaved(x, pos, table):
    """Rotary embedding over interleaved pairs ``(2i, 2i+1)`` of the last
    axis, in place (no de-interleave: q and k turn alike, so their dot
    products are the published ones). ``pos`` broadcasts against
    ``x.shape[:-1]``; ``table`` is :func:`rope_freqs`' of the axis."""
    freqs, mscale = table
    half = x.shape[-1] // 2
    ang = jnp.asarray(pos, F32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(F32).reshape(x.shape[:-1] + (half, 2))
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------ projections


def latent_project(lp, h, pos, c):
    """h (T, d) normed rows at positions ``pos`` (T,) -> (c_q (T, q_rank),
    q_nope (T, H, N), q_rope (T, H, R) roped, row (T, kv_rank + R): the
    token's cache row ``[rms(c_kv) | rope(k_r)]``)."""
    t = h.shape[0]
    c_q = rms_norm(mm(h, lp["w_dq"]), lp["q_norm"], c.rms_eps)
    q = mm(c_q, lp["w_uq"]).reshape(t, c.num_heads, c.qk_nope_head_dim + c.qk_rope_head_dim)
    q_nope, q_rope = q[..., : c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
    table = rope_freqs(c.qk_rope_head_dim, c)
    q_rope = rope_interleaved(q_rope, pos[:, None], table)
    ckv = mm(h, lp["w_dkv"])
    c_kv = rms_norm(ckv[:, : c.kv_lora_rank], lp["kv_norm"], c.rms_eps)
    k_r = rope_interleaved(ckv[:, c.kv_lora_rank:], pos, table)
    return c_q, q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def index_project(lp, h, c_q, pos, c):
    """The indexer's query heads (T, Hi, Di), its one key (T, Di): the
    index-key cache row, and the head weights (T, Hi) float32, scale folded
    in. RoPE turns the first ``index_rope_dim`` values of q and k."""
    t = h.shape[0]
    r = c.index_rope_dim
    table = rope_freqs(r, c)
    q = mm(c_q, lp["w_iq"]).reshape(t, c.index_n_heads, c.index_head_dim)
    q = jnp.concatenate(
        [rope_interleaved(q[..., :r], pos[:, None], table), q[..., r:]], axis=-1)
    k = layer_norm(mm(h, lp["w_ik"]), lp["ik_norm_w"], lp["ik_norm_b"], c.index_norm_eps)
    k = jnp.concatenate(
        [rope_interleaved(k[..., :r], pos, table), k[..., r:]], axis=-1)
    w = mm(h, lp["w_iw"], F32) * (c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
    return q, k, w


# -------------------------------------------------------------- selection


def index_scores(q, w, keys, block: int = 256):
    """I[t, s] (T, S) float32 of queries q (T, Hi, Di) with head weights w
    (T, Hi) over keys (S, Di), ``block`` queries at a time: the (block, Hi,
    S) scores before the sum over heads are the largest thing alive."""

    def one(args):
        qb, wb = args
        s = jnp.einsum("thd,sd->ths", qb, keys, preferred_element_type=F32)
        return jnp.einsum("ths,th->ts", jax.nn.relu(s), wb, precision=HIGHEST)

    t = q.shape[0]
    if t <= block:
        return one((q, w))
    pad = (-t) % block
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    n = (t + pad) // block
    out = jax.lax.map(one, (q.reshape(n, block, *q.shape[1:]), w.reshape(n, block, -1)))
    return out.reshape(n * block, -1)[:t]


def index_scores_batched(q, w, keys):
    """Decode: one query a slot. q (B, Hi, Di), w (B, Hi), keys (B, S, Di)
    -> (B, S) float32."""
    s = jnp.einsum("bhd,bsd->bhs", q, keys, preferred_element_type=F32)
    return jnp.einsum("bhs,bh->bs", jax.nn.relu(s), w, precision=HIGHEST)


def select_mask(scores, visible, k: int):
    """The exact top-``k`` of ``scores`` (T, S) among ``visible`` (T, S), as
    a mask: everything visible where at most ``k`` positions are. Equal
    scores at the boundary go to the lower position, as ``lax.top_k``
    orders them, so this is the set :func:`select_positions` returns.

    The k-th largest score of a row comes from ``kernels/kth_value.py``
    (no sort), and the mask from float comparisons with it. Where the
    scores equal to it are more than the places left, the lower positions
    take them, which needs a running count along the row: that walk is
    made only if some row of the call needs it. -> (mask (T, S), the rows
    that needed the walk (T,) bool)."""
    if scores.shape[-1] <= k:
        return visible, jnp.zeros(scores.shape[:1], bool)
    s = jnp.where(visible, scores, NEG)
    kth = kth_value(s, k)
    above = s > kth
    tied = (s == kth) & visible
    room = k - above.sum(axis=-1, keepdims=True)
    overflows = tied.sum(axis=-1, keepdims=True) > room
    mask = jax.lax.cond(
        overflows.any(),
        lambda: above | (tied & (jnp.cumsum(tied, axis=-1) <= room)),
        lambda: above | tied)
    return mask, overflows[:, 0]


def select_positions(scores, visible, k: int):
    """The same selection as positions (T, min(k, S)) and which of them are
    real (fewer than ``k`` are visible early in a sequence)."""
    s = jnp.where(visible, scores, NEG)
    vals, idx = jax.lax.top_k(s, min(k, s.shape[-1]))
    return idx.astype(jnp.int32), vals > NEG


# -------------------------------------------------------------- attention


def attend_tiles(allowed, off):
    """What :func:`attend_expanded` computes of a chunk's attention, in tiles
    of ``kernels/latent_flash.py``'s sizes: ``table`` (query tiles, key
    tiles) bool, the tiles where ``allowed`` (C, P) allows anything, and
    ``counts`` (3,) int32: how many those are, how many tiles hold a key
    at or before the chunk's last position ``off + C - 1`` (what a loop over
    the key blocks under the chunk's diagonal computes), and how many are
    attended with no mask (none under a selection's mask)."""
    C, P = allowed.shape
    tq, tk = latent_flash.tile_sizes(C, P)
    table = latent_flash.tile_table(allowed, tq, tk)
    return table, _counts(table, C, off, tk, jnp.int32(0))


def causal_tiles(C: int, P: int, off):
    """:func:`attend_tiles` of a chunk whose mask is causality alone, from
    its first position: no (C, P) array is made; the tiles wholly at or
    before a query tile's first position are the unmasked ones
    (``latent_flash.interior_table``)."""
    tq, tk = latent_flash.tile_sizes(C, P)
    table = latent_flash.causal_table(C, P, off, tq, tk)
    unmasked = latent_flash.interior_table(C, P, off, tq, tk).sum(dtype=jnp.int32)
    return table, _counts(table, C, off, tk, unmasked)


def _counts(table, C: int, off, tk: int, unmasked):
    nq, nk = table.shape
    under = nq * jnp.clip((off + C + tk - 1) // tk, 1, nk)
    return jnp.stack([table.sum(dtype=jnp.int32), under.astype(jnp.int32), unmasked])


def tile_rows_read(table, sent, P: int):
    """Keys the attention fetches for the rows somebody sent, in whole key
    tiles: a query row reads every key tile its query tile visits. ``table``
    (query tiles, key tiles) bool, ``sent`` (C,) bool. -> () int32."""
    nq, _ = table.shape
    _, tk = latent_flash.tile_sizes(sent.shape[0], P)
    per_tile = sent.reshape(nq, -1).sum(axis=1, dtype=jnp.int32)
    return (per_tile * table.sum(axis=1, dtype=jnp.int32)).sum() * tk


def attend_expanded(q_nope, q_rope, rows, allowed, off, w_uk, w_uv, c, *, table=None):
    """Expanded-form attention of a prefill chunk. q_nope (C, H, N), q_rope
    (C, H, R); ``rows`` (P, kv_rank + R or wider) the prompt's latent buffer;
    ``allowed`` (C, P) bool, selection and causality together, or None where
    every earlier position is allowed (a layer with no indexer); ``off`` the
    chunk's first position; ``table`` :func:`attend_tiles`' of ``allowed``
    (:func:`causal_tiles`' where it is None) where the caller has it. Where
    the shapes tile, one flash kernel (``kernels/latent_flash.py``: K and V
    made from the rows in VMEM, scores and ``p`` never in HBM, tiles the
    table leaves empty skipped; without ``allowed`` the one that makes its
    mask from the positions); else :func:`attend_expanded_xla`, the same
    mathematics in plain XLA. -> (C, H * V) in q's type."""
    C, H, N = q_nope.shape
    if latent_flash.takes(C, H, c.kv_lora_rank, N + q_rope.shape[-1], w_uv.shape[-1],
                          q_nope.dtype.itemsize):
        if allowed is None:
            return latent_flash.latent_flash_prefill(
                q_nope, q_rope, rows, off, w_uk, w_uv, c.softmax_scale, table=table)
        return latent_flash.dsa_flash_prefill(
            q_nope, q_rope, rows, allowed, w_uk, w_uv, c.softmax_scale, table=table)
    if allowed is None:
        pos = off + jnp.arange(C, dtype=jnp.int32)
        allowed = jnp.arange(rows.shape[0], dtype=jnp.int32)[None, :] <= pos[:, None]
    return attend_expanded_xla(q_nope, q_rope, rows, allowed, off, w_uk, w_uv, c)


def attend_expanded_xla(q_nope, q_rope, rows, allowed, off, w_uk, w_uv, c, *,
                        head_group: int = 16, key_block: int = 2048):
    """:func:`attend_expanded` in plain XLA: the path of shapes the kernel
    does not take, and its oracle. K and V of a block of keys are made from
    the rows a group of heads at a time and folded into an online softmax;
    the loop stops at the last block a row of this chunk can see, so a chunk
    costs what lies under its diagonal. -> (C, H * V) in q's type."""
    C, H, _ = q_nope.shape
    P = rows.shape[0]
    V = w_uv.shape[-1]
    dt = q_nope.dtype
    kb = min(key_block, P)
    n_blocks = jnp.clip((off + C + kb - 1) // kb, 1, -(-P // kb))
    g = min(head_group, H)
    assert H % g == 0, (H, g)
    scale = c.softmax_scale
    rank, R = c.kv_lora_rank, q_rope.shape[-1]

    def group(args):
        qn, qr, wuk, wuv = args  # (C, g, N), (C, g, R), (rank, g, N), (rank, g, V)

        def body(j, carry):
            m, l, acc = carry
            # A last block that would run past P starts early instead (what
            # dynamic_slice does anyway); its overlap is masked out below.
            start = jnp.minimum(j * kb, P - kb)
            blk = jax.lax.dynamic_slice(rows, (start, 0), (kb, rows.shape[1]))
            ckv, kr = blk[:, :rank], blk[:, rank:rank + R]
            kn = jnp.einsum("sc,chn->shn", ckv, wuk, preferred_element_type=F32).astype(dt)
            v = jnp.einsum("sc,chv->shv", ckv, wuv, preferred_element_type=F32).astype(dt)
            s = jnp.einsum("thn,shn->hts", qn, kn, preferred_element_type=F32)
            s = (s + jnp.einsum("thr,sr->hts", qr, kr, preferred_element_type=F32)) * scale
            ok = jax.lax.dynamic_slice(allowed, (0, start), (C, kb))
            ok = ok & (start + jnp.arange(kb) >= j * kb)[None, :]
            s = jnp.where(ok[None], s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(m - m_safe)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("hts,shv->htv", p.astype(dt), v, preferred_element_type=F32)
            return m_new, l, acc * corr[..., None] + pv

        init = (jnp.full((g, C), NEG, F32), jnp.zeros((g, C), F32), jnp.zeros((g, C, V), F32))
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
        return (acc / jnp.where(l > 0, l, 1.0)[..., None]).astype(dt)  # (g, C, V)

    def split(x, axis):  # heads -> (groups, g) with the groups leading
        shape = x.shape[:axis] + (H // g, g) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    out = jax.lax.map(group, (split(q_nope, 1), split(q_rope, 1), split(w_uk, 1), split(w_uv, 1)))
    return out.reshape(H, C, V).transpose(1, 0, 2).reshape(C, H * V)


def attend_absorbed(q_nope, q_rope, rows, real, w_uk, w_uv, c):
    """Absorbed-form attention of one decode step over gathered rows (the
    selected ones, or a whole extent: the form of shapes
    :func:`attend_absorbed_paged`'s kernel does not take, and its oracle).
    q_nope (B, H, N), q_rope (B, H, R); ``rows`` (B, K, kv_rank + R or wider)
    the gathered latent rows and ``real`` (B, K) which of them count. The
    query is taken into the latent space, scores and the weighted sum stay
    there, and the result comes back through W_uv. -> (B, H * V)."""
    dt = q_nope.dtype
    rank = c.kv_lora_rank
    scale = c.softmax_scale
    ckv, kr = rows[..., :rank], rows[..., rank:rank + q_rope.shape[-1]]
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_uk, preferred_element_type=F32).astype(dt)
    s = jnp.einsum("bhc,bkc->bhk", q_lat, ckv, preferred_element_type=F32)
    s = (s + jnp.einsum("bhr,bkr->bhk", q_rope, kr, preferred_element_type=F32)) * scale
    s = jnp.where(real[:, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhk,bkc->bhc", p.astype(dt), ckv, preferred_element_type=F32).astype(dt)
    o = jnp.einsum("bhc,chv->bhv", ctx, w_uv, preferred_element_type=F32).astype(dt)
    return o.reshape(o.shape[0], -1)


def attend_absorbed_paged(q_nope, q_rope, pool, layer: int, tables, lengths, w_uk, w_uv, c):
    """:func:`attend_absorbed` over everything a slot's query may see, read
    in the pool where it lies (``kernels/latent_flash.py:latent_flash_decode``:
    the block table walked, a slot's live tiles alone fetched, once for all
    heads). ``pool`` (L, blocks, 1, bs, W) whose rows are ``[c_kv | k_r | 0]``
    in whole lanes; ``lengths`` (B,) the positions visible (0: none, zeros).
    -> (B, H * V)."""
    dt = q_nope.dtype
    B, H, _ = q_nope.shape
    rank, width = c.kv_lora_rank, pool.shape[-1]
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_uk, preferred_element_type=F32).astype(dt)
    pad = jnp.zeros((B, H, width - rank - q_rope.shape[-1]), dt)
    ctx = latent_flash.latent_flash_decode(
        jnp.concatenate([q_lat, q_rope, pad], axis=-1), pool, layer, tables, lengths,
        rank=rank, scale=c.softmax_scale)
    o = jnp.einsum("bhc,chv->bhv", ctx.astype(dt), w_uv, preferred_element_type=F32).astype(dt)
    return o.reshape(B, -1)


# ---------------------------------------------------------------- experts


def swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route_sigmoid(x, w_router, bias, k: int, scaling: float, normalise: bool = True):
    """Sigmoid scores in float32 over every expert the router knows; the
    ``k`` with the largest score plus bias (the score alone where ``bias``
    is None: a router that has none), ties to the lower index; gates from
    the scores alone, normalised over all ``k`` chosen (held here or not)
    and scaled. -> (idx (T, k) int32, gates (T, k) float32)."""
    s = jax.nn.sigmoid(
        jnp.dot(x.astype(F32), w_router.astype(F32), precision=HIGHEST))
    _, idx = jax.lax.top_k(s if bias is None else s + bias.astype(F32), k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if normalise:
        g = g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), g * scaling


def held_experts(x, idx, gates, w_gate, w_up, w_down, first: int, *, rows=None,
                 tile: int = 256):
    """``sum over chosen and held of gate * ffn_e(x)`` for rows x (T, d):
    ``idx``/``gates`` (T, k) are the router's choice over all experts, and
    experts ``first .. first + E`` are the ones whose weights (E, ...) are
    here. The held picks are sorted by expert and cut into tiles of
    ``tile`` rows, each of one expert; the loop runs over the tiles there
    are. What the absent experts would add is left out, and so is a row
    that ``rows`` (T,) bool does not mark (padding, an inactive slot): its
    picks make no tile. -> (T, d) float32."""
    T, k = idx.shape
    E = w_gate.shape[0]
    tile = min(tile, -(-T // 8) * 8)
    local = idx - first
    held = (local >= 0) & (local < E)
    if rows is not None:
        held &= rows[:, None]
    flat = jnp.where(held, local, E).reshape(-1)  # (T*k,), absent picks last
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    tiles = -(-counts // tile)
    tile_end = jnp.cumsum(tiles)
    row_start = jnp.cumsum(counts) - counts
    flat_gates = gates.reshape(-1)

    def body(i, acc):
        e = jnp.sum(i >= tile_end).astype(jnp.int32)  # this tile's expert
        first_row = row_start[e] + (i - (tile_end[e] - tiles[e])) * tile
        r = first_row + jnp.arange(tile, dtype=jnp.int32)
        real = r < row_start[e] + counts[e]
        pick = order[jnp.clip(r, 0, T * k - 1)]
        tok = jnp.where(real, pick // k, T)  # past the end: dropped below
        xt = x[jnp.clip(tok, 0, T - 1)]
        y = swiglu(xt, w_gate[e], w_up[e], w_down[e]).astype(F32)
        y = y * jnp.where(real, flat_gates[pick], 0.0)[:, None]
        return acc.at[tok].add(y, mode="drop")

    out = jax.lax.fori_loop(0, tile_end[-1], body, jnp.zeros(x.shape, F32))
    return out


def expert_counts(idx, num_experts: int, rows=None):
    """Rows each expert was chosen by, (num_experts,) int32; with ``rows``
    (T,) bool, of the rows it marks (a chunk's padding and a decode batch's
    inactive slots are not rows anyone sent)."""
    n = jnp.ones(idx.shape[:1], jnp.int32) if rows is None else rows.astype(jnp.int32)
    return jnp.zeros((num_experts,), jnp.int32).at[idx].add(n[:, None])
