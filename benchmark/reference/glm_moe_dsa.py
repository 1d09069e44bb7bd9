"""Plain reference for ``"architecture": "glm_moe_dsa"``: the benchmark's own
copy, which imports nothing of the program and takes nothing it made.

The GLM-5.x decoder as its ``config.json`` and the DeepSeek-V3.2 description
it points to give it, in ``jax.numpy``, float32 math at ``highest`` over the
weights in the type the configuration states:

* pre-norm residual blocks, RMS norms, RoPE over interleaved pairs;
* latent attention in the expanded form: ``c_q = rms(x W_dq)``, ``q = c_q
  W_uq`` per head ``[nope | rope]``; ``[c_kv | k_r] = x W_dkv``, ``c_kv``
  normed, ``k_r`` roped and shared by all heads; ``[k_nope | v] = c_kv
  W_ukv`` per head; softmax of ``(q_nope.k_nope + q_rope.k_r) / sqrt(qk)``
  over the selected positions;
* the indexer on ``full`` layers: ``I[t, s] = sum_h w[t, h] relu(q^I[t, h] .
  k^I[s])`` for ``s <= t`` and exactly the ``index_topk`` largest a query
  (``lax.top_k``: equal scores go to the lower position), all of them while
  ``t < index_topk``; a ``shared`` layer takes the selection of the nearest
  ``full`` layer below it;
* experts: sigmoid scores in float32 over all published experts, the top
  ``num_experts_per_tok`` by score plus bias, gates ``routed_scaling_factor
  * s / sum of the chosen s``; of the chosen, only ``experts_held`` are
  computed (one chip's share of the deployment the configuration states),
  plus the shared expert; nothing stands in for the absent experts.

Departures from the published model (also in the configuration file): the
multi-token-prediction layer is not part of the logits and is not here; the
index path's rotation and fp8 storage are left out; the assumed details of
the indexer are those listed under ``assumed``.

What is the benchmark's and not the model's: the weights are drawn here from
the seed by the recipe the configuration names; every sequence runs alone,
at its own length, layer by layer, and inside a layer in blocks of heads,
of queries and of rows, so that 16.5k positions fit beside the weights; an
expert computes the rows that chose it (up to a quarter of the sequence, and
all rows in the rare case that more did: no row is ever dropped).
``precision`` lowers every linear layer for the control, as
``reference/qwen3_dense.py`` does.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3_dense import HI, NEXT_LOWER, PRECISIONS, _linear  # noqa: F401

F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the published config's key names."""
    first, count = cfg["experts_held"]
    assert count == int(cfg["n_routed_experts"]), "n_routed_experts counts the experts held here"
    L = int(cfg["num_hidden_layers"])
    assert len(cfg["mlp_layer_types"]) == len(cfg["indexer_types"]) == L
    return {
        "L": L, "d": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]), "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]),
        "Hi": int(cfg["index_n_heads"]), "Di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]), "Ri": int(cfg["assumed"]["index_rope_dim"]),
        "index_eps": float(cfg["assumed"]["index_norm_eps"]),
        "mlp": tuple(cfg["mlp_layer_types"]), "index": tuple(cfg["indexer_types"]),
        "ff": int(cfg["intermediate_size"]), "fe": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["published"]["n_routed_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "first": int(first), "held": int(count),
        "scaling": float(cfg["routed_scaling_factor"]), "norm_topk": bool(cfg["norm_topk_prob"]),
        "V": int(cfg["vocab_size"]), "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]), "dtype": str(cfg["torch_dtype"]),
    }


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
    if s["index"][layer] == "full":
        out += [("w_iq", (s["q_rank"], s["Hi"] * s["Di"]), None),
                ("w_ik", (d, s["Di"]), None), ("w_iw", (d, s["Hi"]), None)]
    if s["mlp"][layer] == "dense":
        out += [("w_gate", (d, s["ff"]), None), ("w_up", (d, s["ff"]), None),
                ("w_down", (s["ff"], d), None)]
    else:
        fe, n = s["fe"], s["held"]
        out += [("router", (d, s["E"]), None), ("router_bias", (s["E"],), 0.1),
                ("e_gate", (n, d, fe), 1 / math.sqrt(d)), ("e_up", (n, d, fe), 1 / math.sqrt(d)),
                ("e_down", (n, fe, d), 1 / math.sqrt(fe)),
                ("s_gate", (d, fe), None), ("s_up", (d, fe), None), ("s_down", (fe, d), None)]
    return out


def _draw(key, shape, scale, dtype):
    scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
    x = jax.random.normal(key, shape, F32)
    if dtype == F32:
        x = jax.lax.optimization_barrier(x)  # float32 toys: round as the op-by-op form does
    return (x * scale).astype(dtype)


def make_weights(cfg: dict, key, devices) -> dict:
    """The configuration's weights from ``key`` (a legacy uint32[2] key) on
    the first device, one fused draw a tensor: tensor ``i`` of layer ``l``
    from ``fold_in(fold_in(key, l), i)``, the embedding and the head as
    tensors 0 and 1 of "layer" ``L``; norm weights are 1, the index key
    norm's bias 0, and are not stored. The router and its bias are float32."""
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
                name: draw(jax.random.fold_in(lk, i), shape, scale,
                           F32 if name.startswith("router") else dt)
                for i, (name, shape, scale) in enumerate(layer_tensors(s, layer))})
    return out


# ------------------------------------------------------------ the equations


def _rms(x, eps):  # norm weights are 1 in this recipe
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_norm(x, eps):  # weight 1, bias 0
    mu = jnp.mean(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (T, ..., D): the pair (2i, 2i+1) turns by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _blocked(fn, n: int, block: int, *args):
    """``fn`` over blocks of ``block`` rows of the (n, ...) ``args``, one
    after the other (``lax.map``), the last block padded with row 0."""
    block = min(block, n)
    pad = (-n) % block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n + pad) // block, block, *a.shape[1:])
    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(a) for a in args))
    return out.reshape((n + pad,) + out.shape[2:])[:n]


def _selection(s, prec, lp, h, c_q, pos, q_block):
    """(T, T) bool: the positions each query attends to on a ``full`` layer."""
    T = h.shape[0]
    r = s["Ri"]
    q = _linear(c_q, lp["w_iq"], prec).reshape(T, s["Hi"], s["Di"])
    q = jnp.concatenate([_rope(q[..., :r], pos, s["theta"]), q[..., r:]], axis=-1)
    k = _layer_norm(_linear(h, lp["w_ik"], prec), s["index_eps"])
    k = jnp.concatenate([_rope(k[..., :r], pos, s["theta"]), k[..., r:]], axis=-1)
    w = _linear(h, lp["w_iw"], prec) * (s["Hi"] ** -0.5 * s["Di"] ** -0.5)
    causal = lambda p: p[:, None] >= pos[None, :]
    if T <= s["topk"]:
        return causal(pos)

    def block(qb, wb, pb):
        sc = jnp.einsum("thd,sd->ths", qb, k, precision=HI)
        sc = jnp.einsum("ths,th->ts", jax.nn.relu(sc), wb, precision=HI)
        sc = jnp.where(causal(pb), sc, -jnp.inf)
        vals, idx = jax.lax.top_k(sc, s["topk"])
        rows = jnp.arange(sc.shape[0])[:, None]
        return jnp.zeros(sc.shape, bool).at[rows, idx].set(vals > -jnp.inf)

    return _blocked(block, T, q_block, q, w, pos)


def _attention(s, prec, lp, c_q, c_kv, k_r, pos, allowed, head_group, q_block):
    """(T, d): expanded latent attention over ``allowed``, a group of heads
    and a block of queries at a time."""
    T = c_q.shape[0]
    H, N, R, V = s["H"], s["nope"], s["rope"], s["vd"]
    g = min(head_group, H)
    G = H // g
    per_group = (
        lp["w_uq"].reshape(-1, G, g * (N + R)).transpose(1, 0, 2),
        lp["w_uk"].reshape(-1, G, g * N).transpose(1, 0, 2),
        lp["w_uv"].reshape(-1, G, g * V).transpose(1, 0, 2),
        lp["w_o"].reshape(G, g * V, -1),
    )

    def group(acc, ws):
        w_uq, w_uk, w_uv, w_o = ws
        q = _linear(c_q, w_uq, prec).reshape(T, g, N + R)
        q_nope, q_rope = q[..., :N], _rope(q[..., N:], pos, s["theta"])
        k_nope = _linear(c_kv, w_uk, prec).reshape(T, g, N)
        v = _linear(c_kv, w_uv, prec).reshape(T, g, V)

        def block(qn, qr, ok):
            sc = jnp.einsum("thn,shn->hts", qn, k_nope, precision=HI)
            sc = (sc + jnp.einsum("thr,sr->hts", qr, k_r, precision=HI)) / math.sqrt(N + R)
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hts,shv->thv", p, v, precision=HI)

        o = _blocked(block, T, q_block, q_nope, q_rope, allowed).reshape(T, g * V)
        return acc + _linear(o, w_o, prec), None

    out, _ = jax.lax.scan(group, jnp.zeros((T, lp["w_o"].shape[-1]), F32), per_group)
    return out


def _ffn(prec, x, wg, wu, wd):
    return _linear(jax.nn.silu(_linear(x, wg, prec)) * _linear(x, wu, prec), wd, prec)


def _route(s, prec, lp, x):
    sc = jax.nn.sigmoid(_linear(x, lp["router"], prec))
    _, idx = jax.lax.top_k(sc + lp["router_bias"], s["k"])
    g = jnp.take_along_axis(sc, idx, axis=-1)
    if s["norm_topk"]:
        g = g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, g * s["scaling"]


def _routed(s, prec, lp, x, cap=None):
    """``sum over chosen and held of gate * ffn_e(x)``, an expert at a time:
    the rows that chose it, gathered (at most ``cap``; all rows, every one
    computed, where more chose it)."""
    T = x.shape[0]
    cap = min(T, max(256, T // 4) if cap is None else cap)
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


def _layer(s, kinds, prec, blocks, lp, x, allowed):
    """One decoder block of ``kinds`` (feed-forward, indexer) over x (T, d)
    float32 -> (x, the selection it attended over)."""
    mlp_kind, index_kind = kinds
    head_group, q_block, row_block = blocks
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, s["eps"])
    c_q = _rms(_linear(h, lp["w_dq"], prec), s["eps"])
    ckv = _linear(h, lp["w_dkv"], prec)
    c_kv = _rms(ckv[:, : s["kv_rank"]], s["eps"])
    k_r = _rope(ckv[:, s["kv_rank"]:], pos, s["theta"])
    if index_kind == "full":
        allowed = _selection(s, prec, lp, h, c_q, pos, q_block)
    x = x + _attention(s, prec, lp, c_q, c_kv, k_r, pos, allowed, head_group, q_block)
    h = _rms(x, s["eps"])
    if mlp_kind == "dense":
        ffn = lambda hb: _ffn(prec, hb, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + _blocked(ffn, T, row_block, h), allowed
    shared = lambda hb: _ffn(prec, hb, lp["s_gate"], lp["s_up"], lp["s_down"])
    return x + _routed(s, prec, lp, h) + _blocked(shared, T, row_block, h), allowed


def _head(s, prec, x, rows, head, parts: int = 8):
    """Logits (R, V) at ``rows``, a slice of the vocabulary at a time (the
    whole head in float32 would be the largest thing alive)."""
    h = _rms(x[rows], s["eps"])
    V = head.shape[1]
    if V % parts:
        return _linear(h, head, prec)
    part = lambda i: _linear(
        h, jax.lax.dynamic_slice(head, (0, i * (V // parts)), (head.shape[0], V // parts)), prec)
    return jnp.moveaxis(jax.lax.map(part, jnp.arange(parts)), 0, 1).reshape(h.shape[0], V)


#: (heads a group, queries a block, rows a block of the feed-forward)
BLOCKS = (8, 128, 2048)


def logits_at(cfg: dict, weights: dict, tokens, rows, precision: str = "stated",
              block=None):
    """Float32 logits (S, R, V) of sequences ``tokens`` (S, T) int32 at
    positions ``rows`` (S, R) int32. Each sequence runs alone and is cut
    after the last position asked for (rounded up to 256: a few lengths, so
    a few compiled shapes); what lies beyond is in every asked row's
    future."""
    s = sizes(cfg)
    blocks = BLOCKS if block is None else block
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    kinds = list(zip(s["mlp"], s["index"]))
    # Layers of one kind share a program; x and the selection are donated,
    # so a layer's results take the place of what it was given.
    jitted = {k: jax.jit(partial(_layer, s, k, precision, blocks), donate_argnums=(1, 2))
              for k in set(kinds)}
    head = jax.jit(partial(_head, s, precision))
    out = []
    for seq, at in zip(tokens, rows):
        T = min(len(seq), -(-(int(at.max()) + 1) // 256) * 256)
        x = weights["embed"][jnp.asarray(seq[:T])].astype(F32)
        allowed = None
        for kind, lp in zip(kinds, weights["layers"]):
            x, allowed = jitted[kind](lp, x, allowed)
            # One layer on the device at a time: a program's results are
            # allocated when it is enqueued, and four sequences' worth of
            # them beside the weights is more than the chip holds.
            jax.block_until_ready(x)
        out.append(head(x, jnp.asarray(at), weights["head"]))
    return jnp.stack(out)
