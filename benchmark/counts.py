"""What the algorithm needs: operations and bytes of a prefill and of decode
steps, from the configuration and the token counts alone.

Nothing here reads the program's routing, so the same work counts the same
whatever implements it. Bytes are counted once: weights read once a step
(or once a prefill), K/V read only for the lengths that are live, K/V
written once. ``per_chip`` gives one chip's share on ``tp`` chips: its
slice of every weight matrix and of the heads (the embedding row lookup and
the norms are left out as negligible). A share over 100 % can then only be a
fault of the count or of the time.
"""

from __future__ import annotations


def _s(cfg: dict) -> dict:
    return {
        "L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
        "ff": int(cfg["intermediate_size"]), "hq": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "V": int(cfg["vocab_size"]),
        "item": {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]],
    }


def layer_weight_elems(cfg: dict) -> int:
    s = _s(cfg)
    qkv = s["d"] * (s["hq"] + 2 * s["hkv"]) * s["hd"]
    return qkv + s["hq"] * s["hd"] * s["d"] + 3 * s["d"] * s["ff"]


def matmul_weight_elems(cfg: dict) -> int:
    """Every weight a token is multiplied by: the blocks and the head."""
    s = _s(cfg)
    return s["L"] * layer_weight_elems(cfg) + s["d"] * s["V"]


def kv_bytes_per_token(cfg: dict) -> int:
    s = _s(cfg)
    return s["L"] * 2 * s["hkv"] * s["hd"] * s["item"]


def prefill(cfg: dict, p_len: int) -> dict:
    """One prompt of ``p_len`` tokens, logits for its last row only."""
    s = _s(cfg)
    flops = 2.0 * p_len * s["L"] * layer_weight_elems(cfg)
    flops += 2.0 * s["d"] * s["V"]
    # QK^T and PV over the causal half: 4 * hq * hd * p^2 / 2 a layer.
    flops += s["L"] * 2.0 * s["hq"] * s["hd"] * p_len * (p_len + 1)
    byts = matmul_weight_elems(cfg) * s["item"]
    byts += p_len * kv_bytes_per_token(cfg)  # K/V written once
    return {"flops": flops, "bytes": float(byts)}


def decode_steps(cfg: dict, steps: int, row_lengths) -> dict:
    """``steps`` decode steps that between them compute one row for every
    entry of ``row_lengths``: the number of K/V positions that row attends
    to (its own included). Weights are read once a step whatever the batch."""
    s = _s(cfg)
    rows = len(row_lengths)
    live = float(sum(row_lengths))
    flops = 2.0 * rows * matmul_weight_elems(cfg)
    flops += 4.0 * s["L"] * s["hq"] * s["hd"] * live
    byts = steps * matmul_weight_elems(cfg) * s["item"]
    byts += live * kv_bytes_per_token(cfg)  # K/V read for the live lengths
    byts += rows * kv_bytes_per_token(cfg)  # and one position written a row
    return {"flops": flops, "bytes": float(byts)}


def per_chip(work: dict, tp: int) -> dict:
    return {k: v / tp for k, v in work.items()}


def least_seconds(work: dict, peaks: dict) -> dict:
    """The least time one chip could take for ``work``, and which bound
    sets it."""
    tf = work["flops"] / peaks["bf16_flops_per_s"]
    tb = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(tf, tb), "bound": "compute" if tf >= tb else "memory"}
