"""Plain reference for ``"architecture": "qwen3_dense"``.

The Qwen3 dense decoder as published (RMSNorm, grouped-query attention with
the per-head RMS norm on q and k, rotate-half RoPE, SwiGLU, untied head) in
``jax.numpy``: no kernels, no cache, no batching tricks, float32 math over
the weights in the type the configuration states. It imports nothing of the
program and takes nothing the program made: the weights are drawn here from
the seed's key by the same recipe the configuration file names (``assumed``:
normal, 1/sqrt(fan_in), embedding 0.02, norm weights 1; eight sub-keys in
the order embed, qkv, o, gate, up, down, (router), head).

It answers one question for the benchmark: given token sequences, what are
the logits at chosen positions? ``precision`` lowers the arithmetic for the
control (``benchmark/correct.py``): every linear layer's weights and inputs
are rounded through the lower type before a float32 product.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HI = jax.lax.Precision.HIGHEST

#: The nearest precision below the stated one: what would tempt a later PR.
NEXT_LOWER = {"float32": "bfloat16", "bfloat16": "int8", "float16": "int8"}
PRECISIONS = ("stated", "bfloat16", "int8")


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the published config's key names."""
    return {
        "L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
        "ff": int(cfg["intermediate_size"]), "hq": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "V": int(cfg["vocab_size"]), "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]), "dtype": str(cfg["torch_dtype"]),
        "qkv_shards": int(cfg.get("serving", {}).get("tp", 1)),
    }


def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if dtype == jnp.float32:
        # float32 toys: scale after the draw is materialised, so the product
        # rounds as the recipe's op-by-op form does (XLA otherwise folds the
        # normal's own sqrt(2) into the scale, one ulp off).
        x = jax.lax.optimization_barrier(x)
    return (x * scale).astype(dtype)


def _build(s: dict, key):
    dt = jnp.dtype(s["dtype"])
    L, d, ff, hd, V = s["L"], s["d"], s["ff"], s["hd"], s["V"]
    cols = (s["hq"] + 2 * s["hkv"]) * hd
    k = jax.random.split(key, 8)
    fan = lambda n: 1.0 / math.sqrt(n)
    return {
        "embed": _draw(k[0], (V, d), 0.02, dt),
        "wqkv": _draw(k[1], (L, d, cols), fan(d), dt),
        "wo": _draw(k[2], (L, s["hq"] * hd, d), fan(s["hq"] * hd), dt),
        "gate": _draw(k[3], (L, d, ff), fan(d), dt),
        "up": _draw(k[4], (L, d, ff), fan(d), dt),
        "down": _draw(k[5], (L, ff, d), fan(ff), dt),
        "head": _draw(k[7], (d, V), fan(d), dt),
    }


def make_weights(cfg: dict, key, devices) -> dict:
    """The configuration's weights from ``key`` (a legacy uint32[2] key), in
    the stated type, in one jitted draw. Over several devices every large
    tensor is split along its last axis (the partitionable threefry draw
    does not depend on the split), so a model too large for one chip is
    never whole anywhere."""
    s = sizes(cfg)
    devices = list(devices)
    if len(devices) == 1:
        with jax.default_device(devices[0]):
            return jax.jit(partial(_build, s))(jax.device_put(key, devices[0]))
    mesh = Mesh(np.asarray(devices), ("r",))
    shard = lambda nd: NamedSharding(mesh, P(*([None] * (nd - 1)), "r"))
    out = {n: shard(2 if n in ("embed", "head") else 3)
           for n in ("embed", "wqkv", "wo", "gate", "up", "down", "head")}
    return jax.jit(partial(_build, s), out_shardings=out)(key)


# ------------------------------------------------------------ the equations


def _round_through(x, precision: str, axis: int):
    """``x`` (float32) as it reads after a trip through the lower type, with
    one scale per slice along ``axis`` (per token for inputs, per output
    channel for weights), which is how such paths are deployed."""
    if precision == "stated":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if precision == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    raise ValueError(f"unknown precision {precision!r}; known: {PRECISIONS}")


def _linear(x, w, precision: str):
    """x (..., k) float32 @ w (k, n) in the stated type -> float32."""
    w = w.astype(jnp.float32)
    x = _round_through(x, precision, axis=-1)
    w = _round_through(w, precision, axis=0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (B, T, H, D), pos (T,): rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs  # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(s: dict, precision: str, h, wqkv, wo):
    """h (B, T, d) float32 plus its attention block's output, causal over T."""
    B, T, _ = h.shape
    hq, hkv, hd, r = s["hq"], s["hkv"], s["hd"], s["qkv_shards"]
    x = _rms(h, s["eps"])  # norm weights are 1 in this recipe
    qkv = _linear(x, wqkv, precision)
    # The fused weight's columns are r blocks of [q | k | v] heads.
    qkv = qkv.reshape(B, T, r, (hq + 2 * hkv) // r, hd)
    q = qkv[:, :, :, : hq // r].reshape(B, T, hq, hd)
    k = qkv[:, :, :, hq // r: (hq + hkv) // r].reshape(B, T, hkv, hd)
    v = qkv[:, :, :, (hq + hkv) // r:].reshape(B, T, hkv, hd)
    pos = jnp.arange(T)
    q = _rope(_rms(q, s["eps"]), pos, s["theta"])
    k = _rope(_rms(k, s["eps"]), pos, s["theta"])
    g = hq // hkv
    q = q.reshape(B, T, hkv, g, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HI) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", probs, v, precision=HI)
    return h + _linear(o.reshape(B, T, hq * hd), wo, precision)


def _layer(s: dict, precision: str, h, wqkv, wo, gate, up, down):
    """One decoder block over h (B, T, d) float32."""
    h = _attention(s, precision, h, wqkv, wo)
    x = _rms(h, s["eps"])
    m = jax.nn.silu(_linear(x, gate, precision)) * _linear(x, up, precision)
    return h + _linear(m, down, precision)


def _head(s: dict, precision: str, h, rows, head):
    """Logits (B, R, V) at positions ``rows`` (B, R) of h (B, T, d)."""
    x = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    return _linear(_rms(x, s["eps"]), head, precision)


#: One layer's weights, in the order ``_layer`` takes them.
LAYER_WEIGHTS = ("wqkv", "wo", "gate", "up", "down")


def logits_at(cfg: dict, weights: dict, tokens, rows, precision: str = "stated",
              block: int | None = None):
    """Float32 logits (S, R, V) of sequences ``tokens`` (S, T) int32 at
    positions ``rows`` (S, R) int32, layer by layer and ``block`` sequences
    at a time so that nothing larger than one layer's float32 copy and one
    block's attention scores is ever live. Padding past a sequence's end
    sits in the causal future of every row asked for."""
    return by_layer_and_block(sizes(cfg), _layer, LAYER_WEIGHTS, weights, tokens, rows,
                              precision, block)


def by_layer_and_block(s: dict, layer_fn, layer_weights, weights: dict, tokens, rows,
                       precision: str, block: int | None):
    """:func:`logits_at` for any decoder whose block is ``layer_fn(s,
    precision, h, *one layer's weights)`` between this embedding and head."""
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    S, T = tokens.shape
    if block is None:
        block = max(1, int(1.0e9 // (s["hq"] * T * T * 4)))
    block = min(block, S)
    layer = jax.jit(partial(layer_fn, s, precision))
    head = jax.jit(partial(_head, s, precision))
    embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
    pad = (-S) % block
    if pad:  # whole blocks only: one compiled shape
        tokens = np.concatenate([tokens, np.repeat(tokens[-1:], pad, 0)])
        rows = np.concatenate([rows, np.repeat(rows[-1:], pad, 0)])
    hs = [embed(weights["embed"], jnp.asarray(tokens[i:i + block]))
          for i in range(0, S + pad, block)]
    for l in range(s["L"]):
        lw = [weights[n][l] for n in layer_weights]
        hs = [layer(h, *lw) for h in hs]
    out = [head(h, jnp.asarray(rows[i * block:(i + 1) * block]), weights["head"])
           for i, h in enumerate(hs)]
    return jnp.concatenate(out, axis=0)[:S]
