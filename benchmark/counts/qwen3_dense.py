"""Counts for ``"architecture": "qwen3_dense"``: operations and bytes of a
prefill and of decode steps, and of the two attention kernels the served path
calls, from the configuration and the token counts alone.

Nothing here reads the program's routing, so the same work counts the same
whatever implements it. Bytes are counted once: weights read once a step
(or once a prefill), K/V read only for the lengths that are live, K/V
written once. The embedding row lookup and the norms are left out as
negligible. A share over 100 % can then only be a fault of the count or of
the time. (One chip's share on ``tp`` chips and the least time a chip could
take are arithmetic of any model: ``benchmark/counts/__init__.py``.)
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
    flops += flash_attention(cfg, p_len)["flops"]
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
    flops += paged_flash_decode(cfg, row_lengths)["flops"]
    byts = steps * matmul_weight_elems(cfg) * s["item"]
    byts += live * kv_bytes_per_token(cfg)  # K/V read for the live lengths
    byts += rows * kv_bytes_per_token(cfg)  # and one position written a row
    return {"flops": flops, "bytes": float(byts)}


def paged_flash_decode(cfg: dict, row_lengths) -> dict:
    """The table-walk attention kernel's calls (one a layer a step) that
    between them compute one row for every entry of ``row_lengths``: K and V
    of the live positions read once, the row's q read and its output
    written, and QK^T and PV over the live positions."""
    s = _s(cfg)
    live = float(sum(row_lengths))
    flops = 4.0 * s["L"] * s["hq"] * s["hd"] * live
    byts = live * kv_bytes_per_token(cfg)
    byts += len(row_lengths) * s["L"] * 2 * s["hq"] * s["hd"] * s["item"]
    return {"flops": flops, "bytes": float(byts)}


def flash_attention(cfg: dict, p_len: int) -> dict:
    """The prefill attention kernel's calls (one a layer) for one prompt of
    ``p_len`` tokens: QK^T and PV over the causal half (4 * hq * hd * p^2 / 2
    a layer), q, K and V read once and the output written once."""
    s = _s(cfg)
    flops = s["L"] * 2.0 * s["hq"] * s["hd"] * p_len * (p_len + 1)
    byts = p_len * (kv_bytes_per_token(cfg) + s["L"] * 2 * s["hq"] * s["hd"] * s["item"])
    return {"flops": flops, "bytes": float(byts)}
