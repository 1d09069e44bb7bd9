"""Plain reference for ``"architecture": "qwen3_moe"``, the toy's second
architecture: the Qwen3 decoder with a sparse MLP (softmax router, the
``num_experts_per_tok`` largest probabilities renormalised, a SwiGLU expert
each, no token dropped) in float32 ``jax.numpy``, every expert computed for
every token and weighted by the router's choice. The attention block, the
embedding, the head, the rounding of the control and the walk layer by
layer are the benchmark's own dense reference's; the weights are drawn here
from the seed's key by the recipe the configuration names (``assumed``). It
imports nothing of the program."""

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import qwen3_dense as dense

NEXT_LOWER = dense.NEXT_LOWER
LAYER_WEIGHTS = ("wqkv", "wo", "router", "gate", "up", "down")


def sizes(cfg: dict) -> dict:
    return {
        "L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
        "ffe": int(cfg["moe_intermediate_size"]), "E": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]), "renorm": bool(cfg["norm_topk_prob"]),
        "hq": int(cfg["num_attention_heads"]), "hkv": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "dtype": str(cfg["torch_dtype"]), "qkv_shards": int(cfg["serving"]["tp"]),
    }


def _build(s: dict, key):
    dt = jnp.dtype(s["dtype"])
    L, d, ffe, E, hd, V = s["L"], s["d"], s["ffe"], s["E"], s["hd"], s["V"]
    k = jax.random.split(key, 8)
    fan = lambda n: 1.0 / math.sqrt(n)
    draw = dense._draw
    return {
        "embed": draw(k[0], (V, d), 0.02, dt),
        "wqkv": draw(k[1], (L, d, (s["hq"] + 2 * s["hkv"]) * hd), fan(d), dt),
        "wo": draw(k[2], (L, s["hq"] * hd, d), fan(s["hq"] * hd), dt),
        "gate": draw(k[3], (L, E, d, ffe), fan(d), dt),
        "up": draw(k[4], (L, E, d, ffe), fan(d), dt),
        "down": draw(k[5], (L, E, ffe, d), fan(ffe), dt),
        "router": draw(k[6], (L, d, E), 0.02, dt),
        "head": draw(k[7], (d, V), fan(d), dt),
    }


def make_weights(cfg: dict, key, devices) -> dict:
    """The configuration's weights from ``key``, in one jitted draw on the
    first of ``devices`` (the toy's cell has one)."""
    device = list(devices)[0]
    with jax.default_device(device):
        return jax.jit(partial(_build, sizes(cfg)))(jax.device_put(key, device))


def _layer(s: dict, precision: str, h, wqkv, wo, router, gate, up, down):
    """One decoder block over h (B, T, d) float32."""
    h = dense._attention(s, precision, h, wqkv, wo)
    x = dense._rms(h, s["eps"])
    probs = jax.nn.softmax(dense._linear(x, router, precision), axis=-1)
    top, chosen = jax.lax.top_k(probs, s["k"])
    if s["renorm"]:
        top = top / top.sum(-1, keepdims=True)
    # (B, T, E): the router's weight of each expert, nought where not chosen
    share = (jax.nn.one_hot(chosen, s["E"]) * top[..., None]).sum(-2)
    m = 0.0
    for e in range(s["E"]):
        act = jax.nn.silu(dense._linear(x, gate[e], precision)) * dense._linear(x, up[e], precision)
        m = m + share[..., e, None] * dense._linear(act, down[e], precision)
    return h + m


def logits_at(cfg: dict, weights: dict, tokens, rows, precision: str = "stated",
              block: int | None = None):
    """As the dense reference's ``logits_at``."""
    return dense.by_layer_and_block(sizes(cfg), _layer, LAYER_WEIGHTS, weights, tokens, rows,
                                    precision, block)
