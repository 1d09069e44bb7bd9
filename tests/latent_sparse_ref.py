"""Plain reference for the latent-attention / sparse-selection / held-experts
decoder (``triton_dist_tpu/models/latent_sparse.py``): the forward pass of
ONE sequence in ``jax.numpy``, float32, every product at ``highest``; no
cache, no kernels, no batching, no blocks. It follows the published
equations (DeepSeek-V3.2's attention and indexer, which the GLM-5.x family's
``glm_moe_dsa`` is described by):

* latent attention in the EXPANDED form only, dense softmax under a mask;
* the indexer's scores over the causal half, ``lax.top_k`` for the exact
  ``index_topk`` largest a query (equal scores: the lower position), a
  ``shared`` layer taking the mask of the nearest ``full`` layer below;
* sigmoid router over every published expert, top-k by score plus bias,
  gates from the scores normalised over all chosen and scaled; of the
  chosen only the experts in ``held`` are computed, plus the shared expert.

Departures from the published model, each also in the program: the
multi-token-prediction layer is not part of the logits and is not here; the
index path's Hadamard rotation (it leaves dot products as they are) and its
fp8 storage are left out; RoPE turns the FIRST ``index_rope_dim`` values of
the index q and k over interleaved pairs, the index key's norm is a
LayerNorm (weight 1, bias 0, eps 1e-6), ``shared`` layers hold no indexer
weights (assumed from the DeepSeek-V3.2 reference implementation).

``c`` is anything with the program's ``LatentSparseConfig`` attributes;
``params`` the program's parameter pytree (any float type; taken to float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, pos, theta):
    """x (T, ..., D): pairs (2i, 2i+1) turn by pos * theta^(-i / (D/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _ffn(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def selection(c, lp, h, c_q, pos, causal):
    """The (T, T) mask of the positions each query attends to."""
    T = h.shape[0]
    r = c.index_rope_dim
    q = _mm(c_q, lp["w_iq"]).reshape(T, c.index_n_heads, c.index_head_dim)
    q = jnp.concatenate([_rope(q[..., :r], pos, c.rope_theta), q[..., r:]], axis=-1)
    k = _layer_norm(_mm(h, lp["w_ik"]), lp["ik_norm_w"], lp["ik_norm_b"], c.index_norm_eps)
    k = jnp.concatenate([_rope(k[..., :r], pos, c.rope_theta), k[..., r:]], axis=-1)
    w = _mm(h, lp["w_iw"]) * (c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
    s = jnp.einsum("thd,sd->ths", q, k, precision=HI)
    scores = jnp.einsum("ths,th->ts", jax.nn.relu(s), w, precision=HI)
    if T <= c.index_topk:
        return causal
    scores = jnp.where(causal, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, c.index_topk)
    picked = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(vals > -jnp.inf)
    return picked & causal


def attention(c, lp, h, pos, allowed):
    """(T, d): expanded latent attention over ``allowed``; also c_q."""
    T = h.shape[0]
    H, N, R, V = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    c_q = _rms(_mm(h, lp["w_dq"]), lp["q_norm"], c.rms_eps)
    q = _mm(c_q, lp["w_uq"]).reshape(T, H, N + R)
    q_nope, q_rope = q[..., :N], _rope(q[..., N:], pos, c.rope_theta)
    ckv = _mm(h, lp["w_dkv"])
    c_kv = _rms(ckv[:, : c.kv_lora_rank], lp["kv_norm"], c.rms_eps)
    k_r = _rope(ckv[:, c.kv_lora_rank:], pos, c.rope_theta)  # one for all heads
    k_nope = jnp.einsum("sc,chn->shn", c_kv, lp["w_uk"], precision=HI)
    v = jnp.einsum("sc,chv->shv", c_kv, lp["w_uv"], precision=HI)
    s = jnp.einsum("thn,shn->hts", q_nope, k_nope, precision=HI)
    s = (s + jnp.einsum("thr,sr->hts", q_rope, k_r, precision=HI)) / jnp.sqrt(float(N + R))
    p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shv->thv", p, v, precision=HI).reshape(T, H * V)
    return _mm(o, lp["w_o"]), c_q


def route(c, lp, x):
    """(idx (T, k), gates (T, k)) over every published expert."""
    s = jax.nn.sigmoid(_mm(x, lp["router"]))
    _, idx = jax.lax.top_k(s + lp["router_bias"], c.experts_per_token)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if c.norm_topk_prob:
        g = g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, g * c.routed_scaling_factor


def routed(c, lp, x, held=None):
    """``sum over chosen and held of gate * ffn_e(x)``; ``held`` (first,
    count) defaults to the experts the weights are of. Dense over the held
    experts: every one computes every row and the gate zeroes the rest."""
    first, count = c.experts_held if held is None else held
    assert lp["e_gate"].shape[0] == count  # the weights are the held experts'
    idx, g = route(c, lp, x)
    y = jnp.zeros_like(x)
    for e in range(count):
        gate = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        y = y + gate[:, None] * _ffn(x, lp["e_gate"][e], lp["e_up"][e], lp["e_down"][e])
    return y


def forward(c, params, tokens):
    """Logits (T, V) float32 of one sequence ``tokens`` (T,)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    T = len(tokens)
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    x = p["embed"][jnp.asarray(tokens)]
    allowed = causal
    for layer, lp in enumerate(p["layers"]):
        h = _rms(x, lp["ln1"], c.rms_eps)
        if c.index_kinds[layer] == "full":
            c_q = _rms(_mm(h, lp["w_dq"]), lp["q_norm"], c.rms_eps)
            allowed = selection(c, lp, h, c_q, pos, causal)
        a, _ = attention(c, lp, h, pos, allowed)
        x = x + a
        h = _rms(x, lp["ln2"], c.rms_eps)
        if c.mlp_kinds[layer] == "dense":
            x = x + _ffn(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            x = x + routed(c, lp, h) + _ffn(h, lp["s_gate"], lp["s_up"], lp["s_down"])
    return _mm(_rms(x, p["final_norm"], c.rms_eps), p["lm_head"])
