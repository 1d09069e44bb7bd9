"""The shard-local math of ``models/hybrid_ssm.py``: a Mamba-1 mixer,
differential attention, a gated memory unit, the fused SwiGLU. Plain XLA but
for the selective scan (``kernels/ssm_scan.py``) and the pool's read in
place (``kernels/shared_kv_decode.py``).

* **Mamba**, in three forms over the same weights. :func:`mamba_chunk`: a
  chunk of rows with the conv's tail and the state carried in and out, and
  a count of the rows that are real (the rest leave both as they were);
  :func:`mamba_sequence`: a whole sequence, which is a chunk that starts
  from nothing; :func:`mamba_step`: one row a slot. All give ``(Mix's input
  to W_out before the gate, ...)``: the last Mamba layer hands its scan's
  output ``m`` on to the gated memory units above it.
* **Differential attention** (:func:`diff_attend`): the pair ``p`` of query
  heads ``(2p, 2p+1)`` reads the pair ``g = p // n_rep`` of key heads, each
  head its own softmax, the second taken from the first ``lambda`` times,
  over the values of both heads side by side; a norm over those ``2 x
  head`` values, scaled by ``1 - lambda_init``. The caller brings the keys,
  the values and the mask: a window's ring, a prompt's buffer, the pool
  through the table, another layer's. :func:`diff_attend_rows` is the same
  for one query a slot over keys and values kept as whole rows (all heads
  side by side, as the rings and the pool keep them): the heads' structure
  goes into a block-diagonal query, so both products are dense matrix
  products over the rows as they lie, and no row is laid out again;
  :func:`diff_attend_pool` is that over a block pool's rows read in place
  (``kernels/shared_kv_decode.py``).
* **Gated memory unit** (:func:`gmu`): ``W_out (m * silu(W_in u))``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.shared_kv_decode import shared_kv_decode
from triton_dist_tpu.kernels.ssm_scan import ssm_scan, ssm_scan_xla
from triton_dist_tpu.layers.latent_sparse import layer_norm, mm  # noqa: F401

F32 = jnp.float32
NEG = -jnp.inf


def swiglu(h, w1, w2):
    """``W2 (silu(g) * u)``, ``[g | u] = W1 h``."""
    gu = mm(h, w1)
    ff = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :ff]) * gu[..., ff:], w2)


# ------------------------------------------------------------------- Mamba


def _ssm_inputs(lp, xc):
    """From the convolved rows ``xc`` (T, Din): (dt (T, Din), B, C (T, N)),
    float32."""
    n = lp["a_log"].shape[0]
    r = lp["w_dt"].shape[0]
    dbc = mm(xc, lp["w_x"], F32)
    dt = jax.nn.softplus(mm(dbc[:, :r].astype(xc.dtype), lp["w_dt"], F32)
                         + lp["b_dt"].astype(F32))
    return dt, dbc[:, r:r + n], dbc[:, r + n:]


def _scan(x, dt, a_t, b, c, d, s0):
    fn = ssm_scan if x.shape[-1] % 128 == 0 else ssm_scan_xla
    return fn(x, dt, a_t, b, c, d, s0)


def mamba_chunk(lp, u, tail, s, n_real):
    """u (T, d) normed rows, of which the first ``n_real`` are somebody's;
    ``tail`` (K-1, Din) the conv's last inputs before the chunk, ``s`` (N,
    Din) float32 the state. Returns (Mix (T, d), m (T, Din) the scan's
    output before the gate, tail', s')."""
    t = u.shape[0]
    din = lp["w_out"].shape[0]
    k = lp["conv_w"].shape[0]
    xz = mm(u, lp["w_in"])
    x, z = xz[:, :din], xz[:, din:]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=0)  # (K-1+T, Din)
    conv = sum(xp[i:i + t].astype(F32) * lp["conv_w"][i].astype(F32) for i in range(k))
    xc = jax.nn.silu(conv + lp["conv_b"].astype(F32)).astype(x.dtype)
    dt, b, c = _ssm_inputs(lp, xc)
    dt = jnp.where((jnp.arange(t) < n_real)[:, None], dt, 0.0)
    a_t = -jnp.exp(lp["a_log"].astype(F32))  # (N, Din)
    y, s = _scan(xc.astype(F32), dt, a_t, b, c, lp["d"].astype(F32), s)
    m = y.astype(u.dtype)
    tail = jax.lax.dynamic_slice_in_dim(xp, jnp.clip(n_real, 0, t), k - 1, axis=0)
    return mm(m * jax.nn.silu(z), lp["w_out"]), m, tail.astype(u.dtype), s


def mamba_sequence(lp, u):
    """A whole sequence u (T, d): (Mix, m)."""
    din, k, n = lp["w_out"].shape[0], lp["conv_w"].shape[0], lp["a_log"].shape[0]
    out, m, _, _ = mamba_chunk(lp, u, jnp.zeros((k - 1, din), u.dtype),
                               jnp.zeros((n, din), F32), u.shape[0])
    return out, m


def mamba_step(lp, u, tail, s, active):
    """One row a slot: u (B, d), tail (B, K-1, Din), s (B, N, Din) float32,
    ``active`` (B,) bool: an inactive slot's tail and state stay as they
    were. Returns (Mix (B, d), m (B, Din), tail', s')."""
    din = lp["w_out"].shape[0]
    xz = mm(u, lp["w_in"])
    x, z = xz[:, :din], xz[:, din:]
    xp = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)  # (B, K, Din)
    conv = jnp.sum(xp.astype(F32) * lp["conv_w"].astype(F32)[None], axis=1)
    xc = jax.nn.silu(conv + lp["conv_b"].astype(F32)).astype(x.dtype)
    dt, b, c = _ssm_inputs(lp, xc)
    a_t = -jnp.exp(lp["a_log"].astype(F32))
    xf = xc.astype(F32)
    s1 = jnp.exp(dt[:, None, :] * a_t[None]) * s + (dt * xf)[:, None, :] * b[:, :, None]
    y = jnp.sum(s1 * c[:, :, None], axis=1) + lp["d"].astype(F32) * xf
    m = y.astype(u.dtype)
    keep = active[:, None, None]
    return (mm(m * jax.nn.silu(z), lp["w_out"]), m,
            jnp.where(keep, xp[:, 1:], tail).astype(tail.dtype), jnp.where(keep, s1, s))


# --------------------------------------------------------------- attention


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_lambda(lp, layer: int):
    """The layer's ``lambda``: a float32 scalar."""
    f = lambda n: lp[n].astype(F32)
    return (jnp.exp(jnp.sum(f("lam_q1") * f("lam_k1")))
            - jnp.exp(jnp.sum(f("lam_q2") * f("lam_k2"))) + lambda_init(layer))


def diff_attend(q, k, v, mask, lam, layer: int, subln, eps: float):
    """q (T, Hq, D); k, v (S, Hkv, D); mask (T, S) bool, each row allowing
    something; ``lam`` the layer's lambda. Returns (T, Hq * D)."""
    t, hq, dh = q.shape
    s_len, hkv, _ = k.shape
    groups, rep = hkv // 2, hq // hkv
    q5 = q.reshape(t, groups, rep, 2, dh)
    k4 = k.reshape(s_len, groups, 2, dh)
    sc = jnp.einsum("tgprd,sgrd->gprts", q5, k4, preferred_element_type=F32)
    sc = jnp.where(mask[None, None, None], sc / math.sqrt(dh), NEG)
    pr = jax.nn.softmax(sc, axis=-1)
    a = (pr[:, :, 0] - lam * pr[:, :, 1]).astype(v.dtype)  # (G, P, T, S)
    o = jnp.einsum("gpts,sgv->tgpv", a, v.reshape(s_len, groups, 2 * dh),
                   preferred_element_type=F32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * subln.astype(F32) * (1.0 - lambda_init(layer))
    return o.reshape(t, hq * dh).astype(q.dtype)


def rows_query(q, hkv: int):
    """q (B, Hq, D) as block-diagonal rows (B, Hq, Hkv * D): query head ``h``'s
    values in the columns of the key head it reads, zeros elsewhere, so that
    its score over a whole K row is one dense product."""
    hq = q.shape[1]
    heads = jnp.arange(hq)
    key_head = 2 * (heads // (2 * (hq // hkv))) + heads % 2
    reads = (key_head[:, None] == jnp.arange(hkv)[None, :]).astype(q.dtype)  # (Hq, Hkv)
    return jnp.einsum("bhd,hj->bhjd", q, reads).reshape(q.shape[0], hq, -1)


def diff_rows_finish(o, dtype, rep: int, dh: int, layer: int, subln, eps: float):
    """o (B, pairs, Hkv * D) float32: what a pair's difference of softmaxes
    gives over whole V rows. Keeps a pair's own group of ``2 D`` values,
    norms and scales them. Returns (B, Hq * D)."""
    b, pairs, width = o.shape
    groups = width // (2 * dh)
    o = o.reshape(b, pairs, groups, 2 * dh)
    own = (jnp.arange(pairs)[:, None] // rep == jnp.arange(groups)[None, :])
    o = jnp.sum(jnp.where(own[None, :, :, None], o, 0.0), axis=2)  # a pair's own group
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * subln.astype(F32) * (1.0 - lambda_init(layer))
    return o.reshape(b, 2 * pairs * dh).astype(dtype)


def diff_attend_rows(q, k_rows, v_rows, mask, lam, layer: int, subln, eps: float):
    """One query a slot: q (B, Hq, D); ``k_rows``, ``v_rows`` (B, S, Hkv * D)
    the keys and values of S positions, a row a position; mask (B, S) bool.
    Returns (B, Hq * D): :func:`diff_attend`'s numbers."""
    b, hq, dh = q.shape
    hkv = k_rows.shape[-1] // dh
    sc = jnp.einsum("bsk,bhk->bhs", k_rows, rows_query(q, hkv), preferred_element_type=F32)
    sc = jnp.where(mask[:, None, :], sc / math.sqrt(dh), NEG)
    pr = jax.nn.softmax(sc, axis=-1).reshape(b, hq // 2, 2, -1)
    a = (pr[:, :, 0] - lam * pr[:, :, 1]).astype(v_rows.dtype)  # (B, pairs, S)
    o = jnp.einsum("bps,bsk->bpk", a, v_rows, preferred_element_type=F32)
    return diff_rows_finish(o, q.dtype, hq // hkv, dh, layer, subln, eps)


def diff_attend_pool(q, k_pool, v_pool, tables, lengths, lam, layer: int, subln, eps: float):
    """:func:`diff_attend_rows` over the rows of a block pool (1, blocks, 1,
    bs, Hkv * D) through a slot's table row, positions ``[0, length)``, read
    in place by ``kernels/shared_kv_decode.py``: a head's softmax over the
    whole V rows comes back float32, and the pair's difference is taken of
    those, not of the probabilities."""
    b, hq, dh = q.shape
    hkv = k_pool.shape[-1] // dh
    o = shared_kv_decode(rows_query(q, hkv), k_pool, v_pool, tables, lengths,
                         scale=1.0 / math.sqrt(dh)).reshape(b, hq // 2, 2, -1)
    return diff_rows_finish(o[:, :, 0] - lam * o[:, :, 1], q.dtype, hq // hkv, dh,
                            layer, subln, eps)


def gmu(lp, u, m):
    """``W_out (m * silu(W_in u))``: ``m`` the last Mamba layer's scan
    output for the same rows."""
    return mm(m * jax.nn.silu(mm(u, lp["w_in"])), lp["w_out"])
