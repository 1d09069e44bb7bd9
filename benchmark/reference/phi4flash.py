"""Plain reference for ``"architecture": "phi4flash"``: the benchmark's own
copy, which imports nothing of the program and takes nothing it made.

Phi-4-mini-flash-reasoning's decoder (SambaY, arXiv:2507.06607) as its
``config.json`` and the ``phi4flash`` config class's defaults give it, in
``jax.numpy``, float32 math at ``highest`` over the weights in the type the
configuration states. With d = ``hidden_size`` 2560, d_in = ``expand`` x d =
5120, N = ``d_state`` 16, R = ``dt_rank`` 160, K = ``d_conv`` 4, 40 query
heads, 20 key/value heads, head 64, I = ``intermediate_size`` 10240, w =
``sliding_window`` 512, 32 layers:

Every layer ``l``: ``h <- h + Mix_l(LN(h))``, then ``h <- h + W2 (silu(g) *
u)`` with ``[g | u] = W1 LN'(h)`` (``W1``: d -> 2I, ``W2``: I -> d, no bias).
``LN`` is LayerNorm with weight and bias, eps ``layer_norm_eps`` 1e-5. After
layer 31 a final LayerNorm, then logits ``= h E^T`` with the embedding ``E``
(``tie_word_embeddings``). No positional encoding anywhere (the config has
no rope key). ``Mix_l`` by kind:

* **Mamba**, ``l`` even, ``l <= 16`` (Mamba-1): ``[x | z] = W_in u`` (d -> 2
  d_in); ``x <- silu(conv(x))``, ``conv`` causal, depthwise, K = 4, with
  bias; ``[delta | B | C] = W_x x`` (d_in -> R + 2N); ``Delta = softplus(W_dt
  delta + b_dt)`` (R -> d_in); ``A = -exp(A_log)`` (``[d_in, N]``); ``s_t =
  exp(Delta_t * A) * s_{t-1} + (Delta_t * x_t) B_t^T``; ``y_t = s_t C_t + D *
  x_t``; ``Mix = W_out (y * silu(z))`` (d_in -> d). ``Delta``, ``A``, ``s``
  and the scan in float32. Layer 16 also hands on ``m_t = y_t``, the scan's
  output before the gate.
* **Gated memory unit**, ``l`` even, ``l >= 18``: ``Mix = W_out (m * silu(W_in
  u))``, ``W_in``: d -> d_in, ``W_out``: d_in -> d, ``m`` layer 16's, of the
  same token. No state of its own.
* **Attention**, ``l`` odd, ``l <= 17``: ``[q | k | v] = W_qkv u + b`` (d ->
  2560 + 1280 + 1280). Layers 1-15 attend positions ``i - 511 ... i``
  (``sliding_window`` 512, the query's own position counted), layer 17 all
  positions ``<= i``. Layer 17's ``k``, ``v`` are what the cross layers read.
* **Cross-attention**, ``l`` odd, ``l >= 19``: ``q = W_q u + b`` (d -> 2560);
  keys and values are layer 17's, all positions ``<= i``; no K/V projection
  and no cache of its own.
* **Differential attention**, in both: for pair ``p = 0 ... 19``, ``g = p //
  2``: ``S1 = softmax(q_{2p} K_{2g}^T / 8)``, ``S2 = softmax(q_{2p+1}
  K_{2g+1}^T / 8)``, ``V_g = [v_{2g} | v_{2g+1}]`` (128 wide), ``o_p = (1 -
  lambda_init) RMSNorm_128((S1 - lambda S2) V_g)`` (weight, eps 1e-5),
  ``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``, the four ``lambda`` vectors of 64
  a layer's own; ``Mix = W_o [o_0 | ... | o_19] + b``.

Assumed (the configuration file's ``assumed`` gives each with its ground):
the Mamba sizes ``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank``
ceil(d / 16), conv bias, no projection bias; which layers are which
(``SambaYDecoderLayer``: Mamba where ``l % mb_per_layer == 0``; the second
half, from ``l = 16``, is the cross-decoder: 16 publishes ``m``, 17 publishes
K/V, from 18 on the mixers borrow); differential attention in the form of
Diff Transformer's ``multihead_flashdiff_2``; attention biases; the window's
edge (``i - 511 ... i``, as flash-attention's ``(w - 1, 0)``); ``torch_dtype``
bfloat16 with ``A_log``, ``D`` and ``b_dt`` float32; the weights' recipe:
tensor ``i`` of layer ``l`` from ``fold_in(fold_in(key, l), i)`` in the order
of :func:`layer_tensors`, normal x fan_in^-1/2 (biases and the embedding x
0.02, the ``lambda`` vectors x 0.1), ``b_dt`` such that ``softplus(b_dt)`` is
log-uniform in [1e-3, 1e-1]; ``A_log = log(1 ... N)``, ``D`` 1, norm weights 1
and biases 0, not drawn and not stored. Nothing is left out.

What is the benchmark's and not the model's: the weights are drawn here from
the seed; every sequence runs alone, at its own length, layer by layer, the
attention a block of queries at a time and the head a slice of the
vocabulary at a time, so that 2.5k positions fit beside 7.7 GB of weights;
the scan is a plain ``lax.scan`` over the positions. ``precision`` lowers
every linear layer for the control, as ``reference/qwen3_dense.py`` does.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3_dense import HI, NEXT_LOWER, PRECISIONS, _linear  # noqa: F401

F32 = jnp.float32
SCAN_F32 = ("b_dt",)


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the published config's key names."""
    a = cfg["assumed"]
    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    L = int(cfg["num_hidden_layers"])
    assert int(cfg["mb_per_layer"]) == 2 and L % 4 == 0 and bool(cfg["tie_word_embeddings"])
    return {
        "L": L, "half": L // 2, "d": d, "din": int(a["expand"]) * d, "N": int(a["d_state"]),
        "K": int(a["d_conv"]), "R": int(a["dt_rank"]), "hq": hq,
        "hkv": int(cfg["num_key_value_heads"]), "D": d // hq,
        "ff": int(cfg["intermediate_size"]), "w": int(cfg["sliding_window"]),
        "V": int(cfg["vocab_size"]), "eps": float(cfg["layer_norm_eps"]),
        "dtype": str(cfg["torch_dtype"]),
    }


def layer_kind(s: dict, layer: int) -> str:
    if layer % 2 == 0:
        return "mamba" if layer <= s["half"] else "gmu"
    if layer < s["half"]:
        return "window"
    return "full" if layer == s["half"] + 1 else "cross"


# ------------------------------------------------------------- the weights


def layer_tensors(s: dict, layer: int) -> list:
    """(name, shape, how) in draw order: the recipe of ``assumed.weights``.
    ``how``: None for normal / sqrt(shape[0]), a number for normal times
    it, ``"dt"`` for the bias whose softplus is log-uniform in [1e-3, 1e-1]."""
    d, din, n, k, r = s["d"], s["din"], s["N"], s["K"], s["R"]
    qw, kvw = s["hq"] * s["D"], s["hkv"] * s["D"]
    lam = [(f"lam_{x}", (s["D"],), 0.1) for x in ("q1", "k1", "q2", "k2")]
    attn = [("w_qkv", (d, qw + 2 * kvw), None), ("b_qkv", (qw + 2 * kvw,), 0.02),
            ("w_o", (qw, d), None), ("b_o", (d,), 0.02)] + lam
    mix = {
        "mamba": [("w_in", (d, 2 * din), None), ("conv_w", (k, din), None),
                  ("conv_b", (din,), 0.02), ("w_x", (din, r + 2 * n), None),
                  ("w_dt", (r, din), None), ("b_dt", (din,), "dt"), ("w_out", (din, d), None)],
        "gmu": [("w_in", (d, din), None), ("w_out", (din, d), None)],
        "cross": [("w_q", (d, qw), None), ("b_q", (qw,), 0.02),
                  ("w_o", (qw, d), None), ("b_o", (d,), 0.02)] + lam,
        "window": attn, "full": attn,
    }
    return mix[layer_kind(s, layer)] + [("w1", (d, 2 * s["ff"]), None), ("w2", (s["ff"], d), None)]


def _draw(key, shape, how, dtype):
    if how == "dt":
        u = jax.random.uniform(key, shape, F32)
        dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)
    x = jax.random.normal(key, shape, F32)
    if dtype == F32:
        x = jax.lax.optimization_barrier(x)  # float32 toys: round as the op-by-op form does
    return (x * (1.0 / math.sqrt(shape[0]) if how is None else how)).astype(dtype)


def make_weights(cfg: dict, key, devices) -> dict:
    """The configuration's weights from ``key`` (a legacy uint32[2] key) on
    the first device, one fused draw a tensor; the embedding, which is the
    head too, is tensor 0 of "layer" ``L``."""
    s = sizes(cfg)
    dt = jnp.dtype(s["dtype"])
    draw = jax.jit(_draw, static_argnums=(1, 2, 3))
    with jax.default_device(list(devices)[0]):
        key = jnp.asarray(key)
        out = {"embed": draw(jax.random.fold_in(jax.random.fold_in(key, s["L"]), 0),
                             (s["V"], s["d"]), 0.02, dt), "layers": []}
        for layer in range(s["L"]):
            lk = jax.random.fold_in(key, layer)
            out["layers"].append({
                name: draw(jax.random.fold_in(lk, i), shape, how, F32 if name in SCAN_F32 else dt)
                for i, (name, shape, how) in enumerate(layer_tensors(s, layer))})
    return out


# ------------------------------------------------------------ the equations


def _layer_norm(x, eps):  # weight 1, bias 0
    mu = jnp.mean(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, axis=-1, keepdims=True) + eps)


def _mamba(s, prec, lp, u):
    """u (T, d) -> (Mix (T, d), y (T, d_in))."""
    T = u.shape[0]
    din, k, n, r = s["din"], s["K"], s["N"], s["R"]
    xz = _linear(u, lp["w_in"], prec)
    x, z = xz[:, :din], xz[:, din:]
    xp = jnp.concatenate([jnp.zeros((k - 1, din), F32), x])
    conv_w = lp["conv_w"].astype(F32)
    x = jax.nn.silu(sum(xp[i:i + T] * conv_w[i] for i in range(k)) + lp["conv_b"].astype(F32))
    dbc = _linear(x, lp["w_x"], prec)
    delta = jax.nn.softplus(_linear(dbc[:, :r], lp["w_dt"], prec) + lp["b_dt"].astype(F32))
    B, C = dbc[:, r:r + n], dbc[:, r + n:]
    A = -jnp.arange(1, n + 1, dtype=F32)[None, :]  # -exp(log(1 ... N)), every channel's

    def step(st, row):
        x_t, d_t, b_t, c_t = row
        st = jnp.exp(d_t[:, None] * A) * st + (d_t * x_t)[:, None] * b_t[None, :]
        return st, jnp.sum(st * c_t[None, :], axis=1) + x_t  # D = 1

    _, y = jax.lax.scan(step, jnp.zeros((din, n), F32), (x, delta, B, C))
    return _linear(y * jax.nn.silu(z), lp["w_out"], prec), y


def _diff_attention(s, prec, lp, lam_init, q, k, v, window, q_block):
    """q (T, hq, D), k, v (T, hkv, D) -> (T, d): a block of queries at a time."""
    T = q.shape[0]
    hq, hkv, D = s["hq"], s["hkv"], s["D"]
    G, rep = hkv // 2, hq // hkv
    f = lambda n: lp[n].astype(F32)
    lam = (jnp.exp(jnp.sum(f("lam_q1") * f("lam_k1")))
           - jnp.exp(jnp.sum(f("lam_q2") * f("lam_k2"))) + lam_init)
    k4 = k.reshape(T, G, 2, D)
    vg = v.reshape(T, G, 2 * D)
    pos = jnp.arange(T)

    def block(qb, pb):
        ok = pb[:, None] >= pos[None, :]
        if window:
            ok &= pos[None, :] > pb[:, None] - s["w"]
        sc = jnp.einsum("tgprd,sgrd->gprts", qb.reshape(-1, G, rep, 2, D), k4,
                        precision=HI) / math.sqrt(D)
        pr = jax.nn.softmax(jnp.where(ok[None, None, None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("gpts,sgv->tgpv", pr[:, :, 0] - lam * pr[:, :, 1], vg, precision=HI)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + s["eps"])
        return ((1.0 - lam_init) * o).reshape(-1, hq * D)  # the norm's weight is 1

    q_block = min(q_block, T)
    pad = (-T) % q_block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (T + pad) // q_block, q_block, *a.shape[1:])
    o = jax.lax.map(lambda xs: block(*xs), (cut(q), cut(pos)))
    o = o.reshape(T + pad, hq * D)[:T]
    return _linear(o, lp["w_o"], prec) + lp["b_o"].astype(F32)


def _layer(s, kind, prec, q_block, lp, x, carried, lam_init):
    """A layer of ``kind`` over x (T, d) float32; ``carried`` = (m, k, v) of
    the layers that publish them (the newest Mamba layer's scan, so the
    last one's for the layers above it), None until they have run."""
    m, k, v = carried
    T = x.shape[0]
    hq, hkv, D = s["hq"], s["hkv"], s["D"]
    u = _layer_norm(x, s["eps"])
    if kind == "mamba":
        mix, m = _mamba(s, prec, lp, u)
    elif kind == "gmu":
        mix = _linear(m * jax.nn.silu(_linear(u, lp["w_in"], prec)), lp["w_out"], prec)
    elif kind == "cross":
        q = (_linear(u, lp["w_q"], prec) + lp["b_q"].astype(F32)).reshape(T, hq, D)
        mix = _diff_attention(s, prec, lp, lam_init, q, k, v, False, q_block)
    else:
        qkv = _linear(u, lp["w_qkv"], prec) + lp["b_qkv"].astype(F32)
        q = qkv[:, :hq * D].reshape(T, hq, D)
        k_own = qkv[:, hq * D:(hq + hkv) * D].reshape(T, hkv, D)
        v_own = qkv[:, (hq + hkv) * D:].reshape(T, hkv, D)
        if kind == "full":
            k, v = k_own, v_own
        mix = _diff_attention(s, prec, lp, lam_init, q, k_own, v_own, kind == "window",
                              q_block)
    x = x + mix
    gu = _linear(_layer_norm(x, s["eps"]), lp["w1"], prec)
    x = x + _linear(jax.nn.silu(gu[:, :s["ff"]]) * gu[:, s["ff"]:], lp["w2"], prec)
    return x, (m, k, v)


def _head_part(s, prec, width, out, x, rows, embed, seq, part):
    """``out`` (S, R, V) with sequence ``seq``'s logits at ``rows`` over the
    vocabulary's slice ``part`` (``width`` wide) put in. ``out`` is donated:
    beside the weights there is room for one copy of the result, not two."""
    h = _layer_norm(x[rows], s["eps"])
    e = jax.lax.dynamic_slice(embed, (part * width, 0), (width, embed.shape[1]))
    return jax.lax.dynamic_update_slice(out, _linear(h, e.T, prec)[None], (seq, 0, part * width))


#: queries a block of the attention
Q_BLOCK = 256


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
    # Layers of one kind share a program (``lambda_init`` is an argument); x
    # is donated, so a layer's result takes its place.
    kinds = [layer_kind(s, layer) for layer in range(s["L"])]
    jitted = {k: jax.jit(partial(_layer, s, k, precision, q_block), donate_argnums=(1,))
              for k in set(kinds)}
    parts = 8 if s["V"] % 8 == 0 else 1  # a slice of the vocabulary at a time
    head = jax.jit(partial(_head_part, s, precision, s["V"] // parts), donate_argnums=(0,))
    out = jnp.zeros(rows.shape + (s["V"],), F32)
    for i, (seq, at) in enumerate(zip(tokens, rows)):
        T = min(len(seq), -(-(int(at.max()) + 1) // 512) * 512)
        x = weights["embed"][jnp.asarray(seq[:T])].astype(F32)
        carried = (None, None, None)
        for layer, lp in enumerate(weights["layers"]):
            x, carried = jitted[kinds[layer]](
                lp, x, carried, jnp.float32(0.8 - 0.6 * math.exp(-0.3 * layer)))
            jax.block_until_ready(x)  # one layer on the device at a time
        for part in range(parts):
            out = head(out, x, jnp.asarray(at), weights["embed"], i, part)
    return out
