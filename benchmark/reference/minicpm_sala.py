"""Plain reference for ``"architecture": "minicpm_sala"``: the benchmark's
own copy, which imports nothing of the program and takes nothing it made.

MiniCPM-SALA's decoder as its ``config.json`` gives it, with what that file
does not carry taken from the descriptions the configuration's ``assumed``
names, in ``jax.numpy``, float32 math at ``highest`` over the weights in the
type the configuration states. d = ``hidden_size``, L0 = the PUBLISHED
``num_hidden_layers`` (32, whatever depth is held), eps = ``rms_norm_eps``;
``RMS`` is RMSNorm (its weight 1).

All layers, MiniCPM's muP form: ``h0 = scale_emb * E[token]``; ``h <- h +
(scale_depth / sqrt(L0)) * Mix(RMS(h))``; ``h <- h + (scale_depth /
sqrt(L0)) * W2 (silu(g) * u)``, ``[g | u] = W1 RMS(h)``; ``logits = W_head
RMS(h) / (d / dim_model_base)``. No biases, the head untied. ``Mix`` by the
layer's entry of ``mixer_types``:

* **``lightning-attn``**: ``[q | k | v | g] = W_in u``, q, k, v as
  ``lightning_nh`` heads of ``lightning_head_dim`` D; q and k RMS-normed
  over the head's D (``qk_norm``), then rotated (rotate-half, ``rope_theta``,
  over the whole head: ``lightning_use_rope``). A head ``h`` of H decays by
  ``lam_h = exp(-s_h)``, ``s_h = 2^(-8 (h + 1) / H)``: ``S_t = lam_h S_{t-1}
  + k_t^T v_t``, ``o_t = q_t S_t / sqrt(D)`` (``lightning_scale``), ``S``
  float32 ``[D, D]``, zero before position 0 (summed a block of positions
  at a time: :func:`_lightning`). Then ``y = RMS(o)`` over each head's D (``use_output_norm``),
  ``y <- y * sigmoid(g)`` (``use_output_gate``), ``Mix = W_o y``.
* **``minicpm4``** (InfLLM-v2 block-sparse attention): ``[q | k | v | g] =
  W_in u``, q as ``num_attention_heads`` heads of ``head_dim`` D, k and v as
  ``num_key_value_heads`` (a group is the query heads of one K/V head); q,
  k RMS-normed a head; no rotation (``attn_use_rope`` false). With
  ``sparse_config``'s ``kernel_size`` K, ``kernel_stride`` s, ``block_size``
  B, ``topk``, ``init_blocks``, ``window_size``: pooled key ``c_j =
  mean(k[s j : s j + K])``. For the query at ``i``, head ``h`` of group
  ``g``: ``p_h = softmax_j(q_h . c_j / sqrt(D))`` over the pooled keys
  wholly visible (``s j + K - 1 <= i``); ``r_g(j) = sum_h p_h(j)`` over the
  group; block ``b``'s score is the largest ``r_g(j)`` over the pooled keys
  whose span meets positions ``B b ... B b + B - 1``. The first
  ``init_blocks`` blocks and the ``window_size / B`` blocks ending at the
  query's own are always taken; the rest of the ``topk`` are the visible
  blocks of highest score, ties to the lower index. Every head of the group
  attends, softmax at ``1 / sqrt(D)``, the positions ``<= i`` of the
  group's blocks. ``Mix = W_o (o * sigmoid(g))`` (``attn_use_output_gate``).

Assumed and left out: the configuration file's ``assumed`` and ``left_out``
give each with its ground (the slopes, the norm's extent and order, the
sparse sizes; no ``dense_len`` switch, the exact softmax in the selection).

What is the benchmark's and not the model's: the weights are drawn here from
the seed (tensor ``i`` of layer ``l`` from ``fold_in(fold_in(key, l), i)`` in
the order ``w_in``, ``w_o``, ``w1``, ``w2``, normal x fan_in^-1/2; the
embedding x 0.02 and the head are tensors 0 and 1 of "layer" L); every
sequence runs alone, at its own length, layer by layer, the attention a
block of queries at a time and the MLP a block of rows at a time, so that
16.6k positions fit beside the weights. ``precision`` lowers every linear
layer for the control, as ``reference/qwen3_dense.py`` does.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3_dense import HI, NEXT_LOWER, PRECISIONS, _linear  # noqa: F401

F32 = jnp.float32
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the published config's key names."""
    sp = cfg["assumed"]["sparse_config"]
    assert not cfg["attn_use_rope"] and cfg["lightning_use_rope"] and cfg["qk_norm"]
    assert cfg["use_output_gate"] and cfg["use_output_norm"] and cfg["attn_use_output_gate"]
    assert not cfg["tie_word_embeddings"] and not cfg["attention_bias"]
    assert cfg["lightning_nkv"] == cfg["lightning_nh"] and cfg["hidden_act"] == "silu"
    mixers = [KINDS[m] for m in cfg["mixer_types"]]
    assert len(mixers) == int(cfg["num_hidden_layers"])
    return {
        "L": len(mixers), "mixers": mixers, "L0": int(cfg["published"]["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]), "ff": int(cfg["intermediate_size"]),
        "hq": int(cfg["num_attention_heads"]), "hkv": int(cfg["num_key_value_heads"]),
        "D": int(cfg["head_dim"]), "lh": int(cfg["lightning_nh"]),
        "ld": int(cfg["lightning_head_dim"]), "V": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "scale_emb": float(cfg["scale_emb"]), "scale_depth": float(cfg["scale_depth"]),
        "base": int(cfg["dim_model_base"]), "dtype": str(cfg["torch_dtype"]),
        "K": int(sp["kernel_size"]), "s": int(sp["kernel_stride"]), "B": int(sp["block_size"]),
        "topk": int(sp["topk"]), "init": int(sp["init_blocks"]), "win": int(sp["window_size"]),
    }


# ------------------------------------------------------------- the weights


def layer_tensors(s: dict, layer: int) -> list:
    """(name, shape) in draw order: the recipe of ``assumed.weights``."""
    d, ff = s["d"], s["ff"]
    if s["mixers"][layer] == "sparse":
        qw, kvw = s["hq"] * s["D"], s["hkv"] * s["D"]
        mix = [("w_in", (d, 2 * qw + 2 * kvw)), ("w_o", (qw, d))]
    else:
        w = s["lh"] * s["ld"]
        mix = [("w_in", (d, 4 * w)), ("w_o", (w, d))]
    return mix + [("w1", (d, 2 * ff)), ("w2", (ff, d))]


def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, F32)
    if dtype == F32:
        x = jax.lax.optimization_barrier(x)  # float32 toys: round as the op-by-op form does
    return (x * (1.0 / math.sqrt(shape[0]) if scale is None else scale)).astype(dtype)


def make_weights(cfg: dict, key, devices) -> dict:
    """The configuration's weights from ``key`` (a legacy uint32[2] key) on
    the first device, one fused draw a tensor."""
    s = sizes(cfg)
    dt = jnp.dtype(s["dtype"])
    draw = jax.jit(_draw, static_argnums=(1, 2, 3))
    with jax.default_device(list(devices)[0]):
        key = jnp.asarray(key)
        top = jax.random.fold_in(key, s["L"])
        out = {"embed": draw(jax.random.fold_in(top, 0), (s["V"], s["d"]), 0.02, dt),
               "head": draw(jax.random.fold_in(top, 1), (s["d"], s["V"]), None, dt),
               "layers": []}
        for layer in range(s["L"]):
            lk = jax.random.fold_in(key, layer)
            out["layers"].append({
                name: draw(jax.random.fold_in(lk, i), shape, None, dt)
                for i, (name, shape) in enumerate(layer_tensors(s, layer))})
    return out


# ------------------------------------------------------------ the equations


def _rms(x, eps):  # the weight is 1
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (T, H, D) at positions 0 ... T - 1: rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


#: positions a block of the lightning recurrence
L_BLOCK = 128


def _lightning(s, prec, lp, u):
    """u (T, d) -> Mix (T, d). The recurrence ``S_t = lam S_{t-1} + k_t^T
    v_t``, ``o_t = q_t S_t / sqrt(D)`` a block of ``L_BLOCK`` positions at a
    time, which is the same sum written out: within a block at index ``t``,
    ``o_t = lam^(t+1) q_t S_0 + sum_{j <= t} lam^(t-j) (q_t . k_j) v_j`` and
    ``S' = lam^C S_0 + sum_j lam^(C-1-j) k_j^T v_j`` (16.6k single steps a
    layer take a minute a check on the chip; the CPU tests hold this form to
    the step-by-step one)."""
    T = u.shape[0]
    H, D, C = s["lh"], s["ld"], min(L_BLOCK, u.shape[0])
    z = _linear(u, lp["w_in"], prec).reshape(T, 4, H, D)
    q = _rope(_rms(z[:, 0], s["eps"]), s["theta"])
    k = _rope(_rms(z[:, 1], s["eps"]), s["theta"])
    v, g = z[:, 2], z[:, 3]
    slope = 2.0 ** (-8.0 * (jnp.arange(H, dtype=F32) + 1.0) / H)  # lam = exp(-slope)
    t = jnp.arange(C, dtype=F32)
    since = t[:, None] - t[None, :]
    decay = jnp.where(since >= 0, jnp.exp(-slope[:, None, None] * jnp.maximum(since, 0.0)), 0.0)
    pad = (-T) % C  # zero rows after the last position: nobody reads past it
    cut = lambda a: jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(-1, C, H, D)

    def block(S, rows):
        qb, kb, vb = rows  # (C, H, D)
        within = jnp.einsum("thd,jhd->htj", qb, kb, precision=HI) * decay
        o = jnp.einsum("htj,jhd->thd", within, vb, precision=HI)
        o = o + jnp.exp(-slope[None, :, None] * (t[:, None, None] + 1.0)) * jnp.einsum(
            "thd,hde->the", qb, S, precision=HI)
        left = jnp.exp(-slope[None, :, None] * (C - 1.0 - t[:, None, None]))
        S = jnp.exp(-slope * C)[:, None, None] * S + jnp.einsum(
            "jhd,jhe->hde", kb * left, vb, precision=HI)
        return S, o / math.sqrt(D)

    _, o = jax.lax.scan(block, jnp.zeros((H, D, D), F32), (cut(q), cut(k), cut(v)))
    y = _rms(o.reshape(T + pad, H, D)[:T], s["eps"]) * jax.nn.sigmoid(g)
    return _linear(y.reshape(T, H * D), lp["w_o"], prec)


def select_blocks(s, r, pos, T):
    """r (hkv, tq, NP) the group sums of the pooled softmax, ``pos`` (tq,)
    the queries' positions -> (hkv, tq, NB) bool: the blocks each takes."""
    K, st, B = s["K"], s["s"], s["B"]
    NP, NB = r.shape[-1], -(-T // B)
    j, b = np.arange(NP)[:, None], np.arange(NB)[None, :]
    meets = jnp.asarray((st * j <= B * b + B - 1) & (st * j + K - 1 >= B * b))  # (NP, NB)
    score = jnp.max(jnp.where(meets, r[..., None], -1.0), axis=-2) if NP else (
        jnp.full(r.shape[:-1] + (NB,), -1.0, F32))
    own = (pos // B)[:, None]
    blocks = jnp.arange(NB)[None, :]
    visible = blocks <= own
    forced = visible & ((blocks < s["init"]) | (blocks > own - s["win"] // B))
    key = jnp.where(forced, jnp.inf, jnp.where(visible, score, -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)  # ties to the lower index
    rank = jnp.argsort(order, axis=-1)
    return (rank < s["topk"]) & visible


def _sparse(s, prec, q_block, lp, u):
    """u (T, d) -> Mix (T, d): a block of queries at a time."""
    T = u.shape[0]
    hq, hkv, D = s["hq"], s["hkv"], s["D"]
    G = hq // hkv
    K, st, B = s["K"], s["s"], s["B"]
    qw, kvw = hq * D, hkv * D
    z = _linear(u, lp["w_in"], prec)
    q = _rms(z[:, :qw].reshape(T, hkv, G, D), s["eps"])
    k = _rms(z[:, qw:qw + kvw].reshape(T, hkv, D), s["eps"])
    v = z[:, qw + kvw:qw + 2 * kvw].reshape(T, hkv, D)
    g = z[:, qw + 2 * kvw:]
    NP = max((T - K) // st + 1, 0)
    span = st * jnp.arange(NP)[:, None] + jnp.arange(K)[None, :]
    pooled = jnp.mean(k[span], axis=1)  # (NP, hkv, D)
    seen_at = st * jnp.arange(NP) + K - 1  # the position that completes pooled key j
    token = jnp.arange(T)

    def block(qb, pb):
        sc = jnp.einsum("tgrd,jgd->grtj", qb, pooled, precision=HI) / math.sqrt(D)
        ok = seen_at[None, :] <= pb[:, None]
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        r = jnp.sum(jnp.where(ok, p, 0.0), axis=1)  # over the group's heads; no key: 0
        sel = select_blocks(s, r, pb, T)  # (hkv, tq, NB)
        allowed = sel[:, :, token // B] & (token[None, :] <= pb[:, None])
        att = jnp.einsum("tgrd,sgd->grts", qb, k, precision=HI) / math.sqrt(D)
        att = jax.nn.softmax(jnp.where(allowed[:, None], att, -jnp.inf), axis=-1)
        return jnp.einsum("grts,sgd->tgrd", att, v, precision=HI).reshape(-1, qw)

    q_block = min(q_block, T)
    pad = (-T) % q_block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (T + pad) // q_block, q_block, *a.shape[1:])
    o = jax.lax.map(lambda xs: block(*xs), (cut(q), cut(token)))
    o = o.reshape(T + pad, qw)[:T]
    return _linear(o * jax.nn.sigmoid(g), lp["w_o"], prec)


def _mlp(s, prec, lp, x, rows: int):
    """``W2 (silu(g) * u)`` of RMS(x), ``rows`` rows at a time."""
    T = x.shape[0]
    rows = min(rows, T)
    pad = (-T) % rows

    def part(xb):
        gu = _linear(_rms(xb, s["eps"]), lp["w1"], prec)
        return _linear(jax.nn.silu(gu[:, :s["ff"]]) * gu[:, s["ff"]:], lp["w2"], prec)

    out = jax.lax.map(part, jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, rows, x.shape[1]))
    return out.reshape(T + pad, -1)[:T]


#: queries a block of the sparse attention, rows a block of the MLP
Q_BLOCK = 128
MLP_ROWS = 2048


def _layer(s, kind, prec, q_block, lp, x):
    """A layer of ``kind`` over x (T, d) float32."""
    scale = s["scale_depth"] / math.sqrt(s["L0"])
    u = _rms(x, s["eps"])
    mix = _lightning(s, prec, lp, u) if kind == "lightning" else _sparse(s, prec, q_block, lp, u)
    x = x + scale * mix
    return x + scale * _mlp(s, prec, lp, x, MLP_ROWS)


def _head(s, prec, x, rows, head):
    return _linear(_rms(x[rows], s["eps"]), head, prec) / (s["d"] / s["base"])


def logits_at(cfg: dict, weights: dict, tokens, rows, precision: str = "stated",
              block=None):
    """Float32 logits (S, R, V) of sequences ``tokens`` (S, T) int32 at
    positions ``rows`` (S, R) int32. Each sequence runs alone and is cut
    after the last position asked for (rounded up to 512: a few lengths, so
    a few compiled shapes); what lies beyond is in every asked row's
    future."""
    s = sizes(cfg)
    q_block = Q_BLOCK if block is None else block
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    # Layers of one kind share a program; x is donated, so a layer's result
    # takes its place.
    jitted = {k: jax.jit(partial(_layer, s, k, precision, q_block), donate_argnums=(1,))
              for k in set(s["mixers"])}
    head = jax.jit(partial(_head, s, precision))
    out = []
    for seq, at in zip(tokens, rows):
        T = min(len(seq), -(-(int(at.max()) + 1) // 512) * 512)
        x = s["scale_emb"] * weights["embed"][jnp.asarray(seq[:T])].astype(F32)
        for kind, lp in zip(s["mixers"], weights["layers"]):
            x = jitted[kind](lp, x)
            jax.block_until_ready(x)  # one layer on the device at a time
        out.append(head(x, jnp.asarray(at), weights["head"]))
    return jnp.stack(out)
