"""Plain reference for ``"architecture": "granite_hybrid"``: the benchmark's
own copy, which imports nothing of the program and takes nothing it made.

Granite-4.0-H's dense decoder (``granitemoehybrid`` with no experts) as its
``config.json`` gives it, with what that file does not carry taken from the
configuration's ``assumed``, in ``jax.numpy``, float32 math at ``highest``
over the weights in the type the configuration states. d =
``hidden_size``, eps = ``rms_norm_eps``; ``RMS`` is RMSNorm (its weight 1).

All layers: ``h0 = embedding_multiplier E[token]``; ``h <- h +
residual_multiplier Mix(RMS(h))``; ``h <- h + residual_multiplier W2
(silu(g) * u)``, ``[g | u] = W1 RMS(h)`` (``shared_intermediate_size``
each); ``logits = RMS(h) E^T / logits_scaling`` (the head tied). ``Mix`` by
the layer's entry of ``layer_types``:

* **``mamba``** (Mamba-2, ``mamba_n_groups`` 1): ``[z | xBC | dt] = W_in u``
  (``Din = mamba_n_heads x mamba_d_head``, ``Din + 2 N``, ``mamba_n_heads``;
  N = ``mamba_d_state``); ``xBC' = silu(conv(xBC) + b)``, a depthwise causal
  conv of ``mamba_d_conv`` taps over zeros before position 0; ``[x | B | C]
  = xBC'``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; a head
  ``h`` keeps ``S_h`` (N, P) float32, zero before position 0: ``S_t =
  exp(dt_t A) S_{t-1} + B_t^T (dt_t x_t)``, ``y_t = C_t S_t + D x_t`` (``D``
  1), summed a sub-chunk of ``mamba_chunk_size`` positions at a time
  (:func:`_ssd`, which the CPU tests hold to the row-at-a-time form);
  ``Mix = W_out RMS(y * silu(z))``, the norm over all ``Din`` at once.
* **``attention``**: ``q, k, v = W_q u, W_k u, W_v u`` (``num_attention_heads``
  and ``num_key_value_heads`` heads of ``d / num_attention_heads``), no
  rotation (``position_embedding_type`` nope), no bias; query head ``h``
  reads K/V head ``h // (Hq / Hkv)``; softmax at ``attention_multiplier``
  over the positions up to its own; ``Mix = W_o o``.

What is the benchmark's and not the model's: the weights are drawn here from
the seed (``assumed.weights``); every sequence runs alone, at its own
length, layer by layer, the attention a block of queries at a time and the
MLP a block of rows at a time. ``precision`` lowers every linear layer for
the control, as ``reference/qwen3_dense.py`` does.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3_dense import HI, NEXT_LOWER, PRECISIONS, _linear  # noqa: F401

F32 = jnp.float32
KINDS = {"mamba": "mamba2", "attention": "full"}


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the published config's key names."""
    assert cfg["model_type"] == "granitemoehybrid" and int(cfg["num_local_experts"]) == 0
    assert cfg["tie_word_embeddings"] and int(cfg["mamba_n_groups"]) == 1
    assert cfg["position_embedding_type"] == "nope" and cfg["hidden_act"] == "silu"
    kinds = [KINDS[k] for k in cfg["layer_types"]]
    assert len(kinds) == int(cfg["num_hidden_layers"])
    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "L": len(kinds), "kinds": kinds, "d": d, "ff": int(cfg["shared_intermediate_size"]),
        "hq": hq, "hkv": int(cfg["num_key_value_heads"]), "D": d // hq,
        "V": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "emb": float(cfg["embedding_multiplier"]), "res": float(cfg["residual_multiplier"]),
        "logit": 1.0 / float(cfg["logits_scaling"]), "attn": float(cfg["attention_multiplier"]),
        "H": int(cfg["mamba_n_heads"]), "P": int(cfg["mamba_d_head"]),
        "N": int(cfg["mamba_d_state"]), "K": int(cfg["mamba_d_conv"]),
        "c": int(cfg["mamba_chunk_size"]), "dtype": str(cfg["torch_dtype"]),
        "embed_std": float(cfg["embedding_init_std"]),
    }


# ------------------------------------------------------------- the weights


def layer_tensors(s: dict, layer: int) -> list:
    """(name, shape, how) in draw order, the recipe of ``assumed.weights``:
    ``how`` None is normal x fan_in^-1/2, a number normal times it, ``a_log``
    the log of a uniform draw in [1, 16], ``dt`` the bias whose softplus is
    log-uniform in [1e-3, 1e-1]."""
    d, ff = s["d"], s["ff"]
    if s["kinds"][layer] == "full":
        qw, kvw = s["hq"] * s["D"], s["hkv"] * s["D"]
        mix = [("w_in", (d, qw + 2 * kvw), None), ("w_o", (qw, d), None)]
    else:
        din = s["H"] * s["P"]
        conv = din + 2 * s["N"]
        mix = [("w_in", (d, din + conv + s["H"]), None), ("conv_w", (s["K"], conv), None),
               ("conv_b", (conv,), 0.02), ("a_log", (s["H"],), "a_log"),
               ("dt_bias", (s["H"],), "dt"), ("w_out", (din, d), None)]
    return mix + [("w1", (d, 2 * ff), None), ("w2", (ff, d), None)]


def _draw(key, shape, how, dtype):
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if how == "dt":
        u = jax.random.uniform(key, shape, F32)
        dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus's inverse
    x = jax.random.normal(key, shape, F32)
    if dtype == F32:
        x = jax.lax.optimization_barrier(x)  # float32 toys: round as the op-by-op form does
    return (x * (1.0 / math.sqrt(shape[0]) if how is None else how)).astype(dtype)


def make_weights(cfg: dict, key, devices) -> dict:
    """The configuration's weights from ``key`` (a legacy uint32[2] key) on
    the first device, one fused draw a tensor; ``a_log`` and ``dt_bias``
    float32."""
    s = sizes(cfg)
    dt = jnp.dtype(s["dtype"])
    draw = jax.jit(_draw, static_argnums=(1, 2, 3))
    with jax.default_device(list(devices)[0]):
        key = jnp.asarray(key)
        top = jax.random.fold_in(key, s["L"])
        out = {"embed": draw(jax.random.fold_in(top, 0), (s["V"], s["d"]), s["embed_std"], dt),
               "layers": []}
        for layer in range(s["L"]):
            lk = jax.random.fold_in(key, layer)
            out["layers"].append({
                name: draw(jax.random.fold_in(lk, i), shape, how, dt)
                for i, (name, shape, how) in enumerate(layer_tensors(s, layer))})
    return out


# ------------------------------------------------------------ the equations


def _rms(x, eps):  # the weight is 1
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _ssd(s, x, dt, A, B, C):
    """The recurrence over x (T, H, P), dt (T, H), B, C (T, N), a sub-chunk
    of ``c`` positions at a time: within one, with ``a_t`` the running sum
    of ``dt A``, ``y_t = exp(a_t) C_t S_0 + sum_{j <= t} exp(a_t - a_j) (C_t .
    B_j) dt_j x_j + D x_t`` and ``S' = exp(a_last) S_0 + sum_j exp(a_last -
    a_j) dt_j B_j^T x_j``, which is the row-at-a-time sum written out.
    Returns y (T, H, P)."""
    T, H, P = x.shape
    c = min(s["c"], T)
    pad = (-T) % c  # zero steps after the last position: nothing decays or adds
    cut = lambda z: jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1)).reshape(
        (T + pad) // c, c, *z.shape[1:])
    t = jnp.arange(c)
    causal = t[:, None] >= t[None, :]

    def block(S, rows):
        xb, db, bb, cb = rows
        a = jnp.cumsum(db * A, axis=0)  # (c, H)
        L = jnp.where(causal[:, :, None], jnp.exp(
            jnp.minimum(a[:, None, :] - a[None, :, :], 0.0)), 0.0)  # (t, j, H)
        w = jnp.einsum("tn,jn->tj", cb, bb, precision=HI)[:, :, None] * L
        y = jnp.einsum("tjh,jhp->thp", w, db[:, :, None] * xb, precision=HI)
        y = y + jnp.exp(a)[:, :, None] * jnp.einsum("tn,hnp->thp", cb, S, precision=HI)
        left = jnp.exp(a[-1][None] - a) * db  # (c, H)
        S = jnp.exp(a[-1])[:, None, None] * S + jnp.einsum(
            "jn,jhp->hnp", bb, left[:, :, None] * xb, precision=HI)
        return S, y + xb

    _, y = jax.lax.scan(block, jnp.zeros((H, s["N"], P), F32), (cut(x), cut(dt), cut(B), cut(C)))
    return y.reshape(T + pad, H, P)[:T]


def _mamba2(s, prec, lp, u):
    """u (T, d) -> Mix (T, d)."""
    T = u.shape[0]
    H, P, N, K = s["H"], s["P"], s["N"], s["K"]
    din = H * P
    zxd = _linear(u, lp["w_in"], prec)
    z, xbc, dt = zxd[:, :din], zxd[:, din:din + din + 2 * N], zxd[:, -H:]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(w[i] * xp[i:i + T] for i in range(K)) + lp["conv_b"].astype(F32))
    x, B, C = xbc[:, :din], xbc[:, din:din + N], xbc[:, din + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["a_log"])
    y = _ssd(s, x.reshape(T, H, P), dt, A, B, C).reshape(T, din)
    return _linear(_rms(y * jax.nn.silu(z), s["eps"]), lp["w_out"], prec)


def _full(s, prec, q_block, lp, u):
    """u (T, d) -> Mix (T, d): a block of queries at a time."""
    T = u.shape[0]
    hq, hkv, D = s["hq"], s["hkv"], s["D"]
    G = hq // hkv
    qkv = _linear(u, lp["w_in"], prec)
    q = qkv[:, :hq * D].reshape(T, hkv, G, D)
    k = qkv[:, hq * D:(hq + hkv) * D].reshape(T, hkv, D)
    v = qkv[:, (hq + hkv) * D:].reshape(T, hkv, D)
    token = jnp.arange(T)

    def block(qb, pb):
        att = jnp.einsum("tgrd,sgd->grts", qb, k, precision=HI) * s["attn"]
        att = jnp.where((token[None, :] <= pb[:, None])[None, None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        return jnp.einsum("grts,sgd->tgrd", att, v, precision=HI).reshape(-1, hq * D)

    q_block = min(q_block, T)
    pad = (-T) % q_block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (T + pad) // q_block, q_block, *a.shape[1:])
    o = jax.lax.map(lambda xs: block(*xs), (cut(q), cut(token)))
    return _linear(o.reshape(T + pad, hq * D)[:T], lp["w_o"], prec)


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


#: queries a block of the attention, rows a block of the MLP
Q_BLOCK = 512
MLP_ROWS = 2048


def _layer(s, kind, prec, q_block, lp, x):
    """A layer of ``kind`` over x (T, d) float32."""
    u = _rms(x, s["eps"])
    mix = _mamba2(s, prec, lp, u) if kind == "mamba2" else _full(s, prec, q_block, lp, u)
    x = x + s["res"] * mix
    return x + s["res"] * _mlp(s, prec, lp, x, MLP_ROWS)


def _head(s, prec, x, rows, embed):
    return _linear(_rms(x[rows], s["eps"]), embed.T, prec) * s["logit"]


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
              for k in set(s["kinds"])}
    head = jax.jit(partial(_head, s, precision))
    out = []
    for seq, at in zip(tokens, rows):
        T = min(len(seq), -(-(int(at.max()) + 1) // 512) * 512)
        x = s["emb"] * weights["embed"][jnp.asarray(seq[:T])].astype(F32)
        for kind, lp in zip(s["kinds"], weights["layers"]):
            x = jitted[kind](lp, x)
            jax.block_until_ready(x)  # one layer on the device at a time
        out.append(head(x, jnp.asarray(at), weights["embed"]))
    return jnp.stack(out)
