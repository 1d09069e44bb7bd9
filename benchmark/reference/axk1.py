"""Plain reference for ``"architecture": "axk1"``: the benchmark's own copy,
which imports nothing of the program and takes nothing it made.

The A.X-K1 decoder as its ``config.json`` gives it (the DeepSeek-V3 block:
latent attention over every earlier position, no indexer), in ``jax.numpy``,
float32 math at ``highest`` over the weights in the type the configuration
states. Per layer, pre-norm, RMS norms with weight (1 in this recipe):

1. ``h = rms(x)``; ``c_q = rms(h W_dq)``, ``q = c_q W_uq`` per head ``[nope
   | rope]``; ``[c_kv | k_r] = h W_dkv``, ``c_kv = rms(c_kv)``.
2. Rotary on ``q_rope`` and ``k_r`` over interleaved pairs with YaRN's
   table (:func:`yarn_inv_freq`): ``f / factor`` on the slow pairs, ``f`` on
   the fast ones, a linear ramp between the pairs that turn ``beta_fast``
   and ``beta_slow`` times over the original context; cos and sin times
   ``m(mscale) / m(mscale_all_dim)``, ``m(t) = 0.1 t ln(factor) + 1``.
3. Causal softmax over every earlier position at scale ``(nope + rope)^-0.5
   m(mscale_all_dim)^2``: ``k_h = [c_kv W_uk_h | k_r]``, ``v_h = c_kv
   W_uv_h``; ``x += concat_h(o_h) W_o``.
4. ``h = rms(x)``. The leading layer: SwiGLU. The others: ``s = sigmoid(h
   W_r)`` in float32 over all published experts, the ``num_experts_per_tok``
   largest (ties to the lower index: ``lax.top_k``), gates ``s_e / sum of
   the chosen`` times ``routed_scaling_factor``; of the chosen, only
   ``experts_held`` are computed (one chip's share of the deployment the
   configuration states), plus the shared expert.
5. Final norm, untied head, float32 logits.

``topk_method`` "none" is read as the plain top-k of the scores: no
correction bias and no group limit (``assumed`` in the configuration file);
any other method than it raises here.

What is the benchmark's and not the model's: the weights are drawn here from
the seed by the recipe the configuration names, **a tensor when it is asked
for and not before** (:class:`Weights`): the model held here is 11 GB in
bfloat16, a 33k-position sequence in float32 is 1 GB a copy and the dense
layer's weights in float32 1.6 GB, so the reference keeps one layer's
weights on the device at a time and runs every sequence of the sample
through it before it draws the next. Every sequence runs alone, at its own
length, and inside a layer in blocks of heads, of queries and of rows; an
expert computes the rows that chose it. ``precision`` lowers every linear
layer for the control, as ``reference/qwen3_dense.py`` does.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# What is arithmetic of any decoder of this family (a norm with weight 1,
# SwiGLU, a function over blocks of rows, the head a slice of the vocabulary
# at a time) is the GLM reference's, as its linear layer is the dense one's.
from benchmark.reference.glm_moe_dsa import _blocked, _ffn, _head, _rms
from benchmark.reference.qwen3_dense import HI, NEXT_LOWER, PRECISIONS, _linear  # noqa: F401

F32 = jnp.float32
ROUTING = ("none",)  # the ``topk_method`` this file computes


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the published config's key names."""
    first, count = cfg["experts_held"]
    assert count == int(cfg["n_routed_experts"]), "n_routed_experts counts the experts held here"
    if cfg["topk_method"] not in ROUTING:
        raise ValueError(f"topk_method {cfg['topk_method']!r}: this reference computes {ROUTING}")
    if cfg["scoring_func"] != "sigmoid" or int(cfg["n_shared_experts"]) != 1:
        raise ValueError("sigmoid scores and one shared expert")
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("the rotary table is YaRN's")
    return {
        "L": int(cfg["num_hidden_layers"]), "dense": int(cfg["first_k_dense_replace"]),
        "d": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]), "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]),
        "ff": int(cfg["intermediate_size"]), "fe": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["published"]["n_routed_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "first": int(first), "held": int(count),
        "scaling": float(cfg["routed_scaling_factor"]), "norm_topk": bool(cfg["norm_topk_prob"]),
        "V": int(cfg["vocab_size"]), "theta": float(cfg["rope_theta"]),
        "yarn": {k: float(rs[k]) for k in ("factor", "original_max_position_embeddings",
                                           "beta_fast", "beta_slow", "mscale", "mscale_all_dim")},
        "eps": float(cfg["rms_norm_eps"]), "dtype": str(cfg["torch_dtype"]),
    }


# ---------------------------------------------------------------- the rotary


def _m(factor: float, t: float) -> float:
    return 0.1 * t * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_ramp_ends(dim: int, theta: float, y: dict) -> tuple[int, int]:
    corr = lambda n: dim * math.log(y["original_max_position_embeddings"] / (n * 2 * math.pi)) / (
        2 * math.log(theta))
    return max(math.floor(corr(y["beta_fast"])), 0), min(math.ceil(corr(y["beta_slow"])), dim - 1)


def yarn_inv_freq(dim: int, theta: float, y: dict):
    """(dim / 2,) float64 frequencies of the pairs."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_ramp_ends(dim, theta, y)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(s: dict) -> float:
    y = s["yarn"]
    m_all = _m(y["factor"], y["mscale_all_dim"]) if y["mscale_all_dim"] else 1.0
    return (s["nope"] + s["rope"]) ** -0.5 * m_all * m_all


def _rope(s, x, pos):
    """x (T, ..., D): the pair (2i, 2i+1) turns by pos * inv_freq[i]."""
    y = s["yarn"]
    freqs = jnp.asarray(yarn_inv_freq(x.shape[-1], s["theta"], y), F32)
    mscale = _m(y["factor"], y["mscale"]) / _m(y["factor"], y["mscale_all_dim"])
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * freqs
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


# ------------------------------------------------------------- the weights


def layer_tensors(s: dict, layer: int) -> list:
    """(name, shape, scale or None for 1/sqrt(shape[0])) in draw order: the
    recipe of ``assumed.weights``."""
    d, H = s["d"], s["H"]
    out = [
        ("w_dq", (d, s["q_rank"]), None),
        ("w_uq", (s["q_rank"], H * (s["nope"] + s["rope"])), None),
        ("w_dkv", (d, s["kv_rank"] + s["rope"]), None),
        ("w_uk", (s["kv_rank"], H, s["nope"]), 1 / math.sqrt(s["kv_rank"])),
        ("w_uv", (s["kv_rank"], H, s["vd"]), 1 / math.sqrt(s["kv_rank"])),
        ("w_o", (H * s["vd"], d), None),
    ]
    if layer < s["dense"]:
        return out + [("w_gate", (d, s["ff"]), None), ("w_up", (d, s["ff"]), None),
                      ("w_down", (s["ff"], d), None)]
    fe, n = s["fe"], s["held"]
    return out + [("router", (d, s["E"]), None),
                  ("e_gate", (n, d, fe), 1 / math.sqrt(d)), ("e_up", (n, d, fe), 1 / math.sqrt(d)),
                  ("e_down", (n, fe, d), 1 / math.sqrt(fe)),
                  ("s_gate", (d, fe), None), ("s_up", (d, fe), None), ("s_down", (fe, d), None)]


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, scale, dtype):
    scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
    x = jax.random.normal(key, shape, F32)
    if dtype == F32:
        x = jax.lax.optimization_barrier(x)  # float32 toys: round as the op-by-op form does
    return (x * scale).astype(dtype)


class _Layers:
    def __init__(self, weights):
        self._w = weights

    def __len__(self):
        return self._w.s["L"]

    def __getitem__(self, layer: int) -> dict:
        return self._w.layer(layer)

    def __iter__(self):
        return (self[i] for i in range(len(self)))


class Weights:
    """The configuration's weights from a key (a legacy uint32[2] key) on
    one device, each tensor drawn when it is asked for and kept by whoever
    asked: tensor ``i`` of layer ``l`` from ``fold_in(fold_in(key, l), i)``,
    the embedding and the head as tensors 0 and 1 of "layer" ``L``; norm
    weights are 1 and are not stored; the router is float32. ``w["embed"]``,
    ``w["head"]``, ``w["layers"][l][name]``."""

    def __init__(self, cfg: dict, key, device):
        self.s = sizes(cfg)
        self.device = device
        with jax.default_device(device):
            self.key = jnp.asarray(key)

    def _dt(self):
        return jnp.dtype(self.s["dtype"])

    def __getitem__(self, name: str):
        s = self.s
        if name == "layers":
            return _Layers(self)
        with jax.default_device(self.device):
            top = jax.random.fold_in(self.key, s["L"])
            if name == "embed":
                return _draw(jax.random.fold_in(top, 0), (s["V"], s["d"]), 0.02, self._dt())
            if name == "head":
                return _draw(jax.random.fold_in(top, 1), (s["d"], s["V"]), None, self._dt())
        raise KeyError(name)

    def layer(self, layer: int) -> dict:
        with jax.default_device(self.device):
            lk = jax.random.fold_in(self.key, layer)
            return {name: _draw(jax.random.fold_in(lk, i), shape, scale,
                                F32 if name == "router" else self._dt())
                    for i, (name, shape, scale) in enumerate(layer_tensors(self.s, layer))}


def make_weights(cfg: dict, key, devices) -> Weights:
    return Weights(cfg, key, list(devices)[0])


# ------------------------------------------------------------ the equations


def _attention(s, prec, lp, c_q, c_kv, k_r, pos, head_group, q_block):
    """(T, d): expanded latent attention over every earlier position, a
    group of heads and a block of queries at a time."""
    T = c_q.shape[0]
    H, N, R, V = s["H"], s["nope"], s["rope"], s["vd"]
    g = min(head_group, H)
    G = H // g
    scale = softmax_scale(s)
    per_group = (
        lp["w_uq"].reshape(-1, G, g * (N + R)).transpose(1, 0, 2),
        lp["w_uk"].reshape(-1, G, g * N).transpose(1, 0, 2),
        lp["w_uv"].reshape(-1, G, g * V).transpose(1, 0, 2),
        lp["w_o"].reshape(G, g * V, -1),
    )

    def group(acc, ws):
        w_uq, w_uk, w_uv, w_o = ws
        q = _linear(c_q, w_uq, prec).reshape(T, g, N + R)
        q_nope, q_rope = q[..., :N], _rope(s, q[..., N:], pos)
        k_nope = _linear(c_kv, w_uk, prec).reshape(T, g, N)
        v = _linear(c_kv, w_uv, prec).reshape(T, g, V)

        def block(qn, qr, pb):
            sc = jnp.einsum("thn,shn->hts", qn, k_nope, precision=HI)
            sc = (sc + jnp.einsum("thr,sr->hts", qr, k_r, precision=HI)) * scale
            ok = pb[:, None] >= pos[None, :]
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hts,shv->thv", p, v, precision=HI)

        o = _blocked(block, T, q_block, q_nope, q_rope, pos).reshape(T, g * V)
        return acc + _linear(o, w_o, prec), None

    out, _ = jax.lax.scan(group, jnp.zeros((T, lp["w_o"].shape[-1]), F32), per_group)
    return out


def _route(s, prec, lp, x):
    """The plain top-k of the sigmoid scores -> (idx (T, k), gates (T, k))."""
    sc = jax.nn.sigmoid(_linear(x, lp["router"], prec))
    g, idx = jax.lax.top_k(sc, s["k"])
    if s["norm_topk"]:
        g = g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, g * s["scaling"]


def _routed(s, prec, lp, x, cap=None):
    """``sum over chosen and held of gate * ffn_e(x)``, an expert at a time:
    the rows that chose it, gathered (at most ``cap``; all rows, every one
    computed, where more chose it)."""
    T = x.shape[0]
    cap = min(T, max(256, T // 8) if cap is None else cap)
    idx, g = _route(s, prec, lp, x)

    def expert(y, ws):
        wg, wu, wd, e = ws
        gate = jnp.sum(jnp.where(idx == s["first"] + e, g, 0.0), axis=-1)  # (T,)
        chose = gate > 0

        def some(_):
            rows = jnp.argsort(~chose, stable=True)[:cap]
            return y.at[rows].add(gate[rows, None] * _ffn(prec, x[rows], wg, wu, wd))

        def all_rows(_):
            return y + gate[:, None] * _ffn(prec, x, wg, wu, wd)

        return jax.lax.cond(chose.sum() <= cap, some, all_rows, None), None

    ws = (lp["e_gate"], lp["e_up"], lp["e_down"], jnp.arange(s["held"]))
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), ws)
    return y


def _layer(s, dense: bool, prec, blocks, lp, x):
    """One decoder block over x (T, d) float32."""
    head_group, q_block, row_block = blocks
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, s["eps"])
    c_q = _rms(_linear(h, lp["w_dq"], prec), s["eps"])
    ckv = _linear(h, lp["w_dkv"], prec)
    c_kv = _rms(ckv[:, : s["kv_rank"]], s["eps"])
    k_r = _rope(s, ckv[:, s["kv_rank"]:], pos)
    x = x + _attention(s, prec, lp, c_q, c_kv, k_r, pos, head_group, q_block)
    h = _rms(x, s["eps"])
    if dense:
        ffn = lambda hb: _ffn(prec, hb, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + _blocked(ffn, T, row_block, h)
    shared = lambda hb: _ffn(prec, hb, lp["s_gate"], lp["s_up"], lp["s_down"])
    return x + _routed(s, prec, lp, h) + _blocked(shared, T, row_block, h)


#: (heads a group, queries a block, rows a block of the feed-forward)
BLOCKS = (8, 128, 2048)


def logits_at(cfg: dict, weights: Weights, tokens, rows, precision: str = "stated",
              block=None):
    """Float32 logits (S, R, V) of sequences ``tokens`` (S, T) int32 at
    positions ``rows`` (S, R) int32. Each sequence runs alone and is cut
    after the last position asked for (rounded up to 256: a few lengths, so
    a few compiled shapes); what lies beyond is in every asked row's
    future. A layer's weights are drawn once and every sequence goes
    through them before the next layer's are."""
    s = sizes(cfg)
    blocks = BLOCKS if block is None else block
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    jitted = {dense: jax.jit(partial(_layer, s, dense, precision, blocks), donate_argnums=(1,))
              for dense in (True, False)}
    head = jax.jit(partial(_head, s, precision))
    embed = weights["embed"]
    xs = []
    for seq, at in zip(tokens, rows):
        T = min(len(seq), -(-(int(at.max()) + 1) // 256) * 256)
        xs.append(embed[jnp.asarray(seq[:T])].astype(F32))
    del embed
    for layer in range(s["L"]):
        lp = weights["layers"][layer]
        for i, x in enumerate(xs):
            xs[i] = jitted[layer < s["dense"]](lp, x)
            # One program on the device at a time: a program's results are
            # allocated when it is enqueued.
            jax.block_until_ready(xs[i])
        del lp
    w_head = weights["head"]
    return jnp.stack([head(x, jnp.asarray(at), w_head) for x, at in zip(xs, rows)])
