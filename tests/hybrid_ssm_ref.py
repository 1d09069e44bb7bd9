"""Plain reference for the Mamba / sliding-window / shared-K/V decoder
(``triton_dist_tpu/models/hybrid_ssm.py``; Phi-4-mini-flash-reasoning's
``phi4flash``): the forward pass of ONE sequence in ``jax.numpy``, float32,
every product at ``highest``; no cache, no ring, no carried state, no
kernels, no chunks: the scan is a ``lax.scan`` over the whole sequence from
a zero state, the window a mask over the whole score matrix.

Every layer ``l``: ``h += Mix_l(LN(h))``; ``h += W2 (silu(g) * u)``, ``[g |
u] = W1 LN'(h)``; LayerNorm with weight and bias, eps ``layer_norm_eps``.
After the last layer a final LayerNorm, and logits ``= h E^T`` with the
embedding ``E``. No positional encoding anywhere. ``Mix_l`` by kind
(``HybridSSMConfig.layer_kind``; L layers, half = L / 2):

* **Mamba** (``l`` even, ``l <= half``; Mamba-1): ``[x | z] = W_in u``; ``x <-
  silu(conv(x))``, causal, depthwise, ``d_conv`` taps, with bias; ``[delta |
  B | C] = W_x x``; ``Delta = softplus(W_dt delta + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(Delta_t * A) * s_{t-1} + (Delta_t * x_t) B_t^T``;
  ``y_t = s_t C_t + D * x_t``; ``Mix = W_out (y * silu(z))``. Layer ``half``
  also hands on ``m_t = y_t``, the scan's output before the gate.
* **Gated memory unit** (``l`` even, ``l > half``): ``Mix = W_out (m *
  silu(W_in u))``, ``m`` layer ``half``'s, of the same token.
* **Attention** (``l`` odd, ``l <= half + 1``): ``[q | k | v] = W_qkv u + b``.
  Below ``half`` a query at ``i`` attends positions ``i - w + 1 ... i``
  (``sliding_window`` ``w``, its own position counted); layer ``half + 1``
  attends every position ``<= i``, and its ``k``, ``v`` are what the cross
  layers read.
* **Cross-attention** (``l`` odd, ``l > half + 1``): ``q = W_q u + b``; keys
  and values are layer ``half + 1``'s, positions ``<= i``.
* **Differential attention**, in both: pair ``p`` of query heads reads the
  pair ``g = p // n_rep`` of key heads (``n_rep`` = query heads / key
  heads): ``S1 = softmax(q_{2p} K_{2g}^T / sqrt(D))``, ``S2 =
  softmax(q_{2p+1} K_{2g+1}^T / sqrt(D))``, ``V_g = [v_{2g} | v_{2g+1}]``,
  ``o_p = (1 - lambda_init) RMSNorm_{2D}((S1 - lambda S2) V_g)``, ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``Mix = W_o [o_0 | ... ] + b``.

``c`` is anything with ``HybridSSMConfig``'s attributes; ``params`` the
program's parameter pytree (any float type; taken to float32; ``a_log`` is
held as ``[N, d_in]``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def mamba(lp, u):
    """u (T, d) -> (Mix (T, d), y (T, Din))."""
    T = u.shape[0]
    din, k = lp["w_out"].shape[0], lp["conv_w"].shape[0]
    n, r = lp["a_log"].shape[0], lp["w_dt"].shape[0]
    xz = _mm(u, lp["w_in"])
    x, z = xz[:, :din], xz[:, din:]
    xp = jnp.concatenate([jnp.zeros((k - 1, din)), x])
    x = jax.nn.silu(sum(xp[i:i + T] * lp["conv_w"][i] for i in range(k)) + lp["conv_b"])
    dbc = _mm(x, lp["w_x"])
    delta = jax.nn.softplus(_mm(dbc[:, :r], lp["w_dt"]) + lp["b_dt"])
    B, C = dbc[:, r:r + n], dbc[:, r + n:]
    A = -jnp.exp(lp["a_log"]).T  # (Din, N)

    def step(s, row):
        x_t, d_t, b_t, c_t = row
        s = jnp.exp(d_t[:, None] * A) * s + (d_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=1) + lp["d"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((din, n)), (x, delta, B, C))
    return _mm(y * jax.nn.silu(z), lp["w_out"]), y


def diff_attention(c, lp, layer, q, k, v, mask):
    """q (T, Hq, D), k, v (T, Hkv, D), mask (T, T) -> (T, d), pair by pair."""
    hq, hkv, D = c.num_q_heads, c.num_kv_heads, c.head_dim
    rep = hq // hkv
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"]))
           - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam_init)

    def probs(qh, kh):
        sc = jnp.einsum("td,sd->ts", qh, kh, precision=HI) / math.sqrt(D)
        return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)

    outs = []
    for p in range(hq // 2):
        g = p // rep
        a = probs(q[:, 2 * p], k[:, 2 * g]) - lam * probs(q[:, 2 * p + 1], k[:, 2 * g + 1])
        o = _mm(a, jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.layer_norm_eps)
        outs.append((1.0 - lam_init) * o * lp["subln"])
    return _mm(jnp.concatenate(outs, axis=-1), lp["w_o"]) + lp["b_o"]


def forward(c, params, tokens):
    """tokens (T,) int32 -> float32 logits (T, V)."""
    p = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    T = tokens.shape[0]
    hq, hkv, D = c.num_q_heads, c.num_kv_heads, c.head_dim
    i = jnp.arange(T)
    causal = i[:, None] >= i[None, :]
    window = causal & (i[None, :] > i[:, None] - c.sliding_window)
    h = p["embed"][tokens]
    m = shared_k = shared_v = None
    for layer, lp in enumerate(p["layers"]):
        kind = c.layer_kind(layer)
        u = _ln(h, lp["ln1_w"], lp["ln1_b"], c.layer_norm_eps)
        if kind == "mamba":
            mix, m = mamba(lp, u)
        elif kind == "gmu":
            mix = _mm(m * jax.nn.silu(_mm(u, lp["w_in"])), lp["w_out"])
        elif kind == "cross":
            q = (_mm(u, lp["w_q"]) + lp["b_q"]).reshape(T, hq, D)
            mix = diff_attention(c, lp, layer, q, shared_k, shared_v, causal)
        else:
            qkv = _mm(u, lp["w_qkv"]) + lp["b_qkv"]
            q = qkv[:, :hq * D].reshape(T, hq, D)
            k = qkv[:, hq * D:(hq + hkv) * D].reshape(T, hkv, D)
            v = qkv[:, (hq + hkv) * D:].reshape(T, hkv, D)
            if kind == "full":
                shared_k, shared_v = k, v
            mix = diff_attention(c, lp, layer, q, k, v, causal if kind == "full" else window)
        h = h + mix
        gu = _mm(_ln(h, lp["ln2_w"], lp["ln2_b"], c.layer_norm_eps), lp["w1"])
        ff = gu.shape[-1] // 2
        h = h + _mm(jax.nn.silu(gu[:, :ff]) * gu[:, ff:], lp["w2"])
    return _mm(_ln(h, p["final_w"], p["final_b"], c.layer_norm_eps), p["embed"].T)
