"""Counts for ``"architecture": "qwen3_moe"``, the toy's second
architecture: a token is multiplied by the attention weights, the router
and ``num_experts_per_tok`` experts; a step reads at least that many
experts' weights (every row may choose the same ones), whatever the
program's routing reads. None of it is a dense model's count."""


def _s(cfg: dict) -> dict:
    return {
        "L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
        "ffe": int(cfg["moe_intermediate_size"]), "E": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]), "hq": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "V": int(cfg["vocab_size"]),
        "item": {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]],
    }


def token_weight_elems(cfg: dict) -> int:
    """Every weight one token is multiplied by: attention, router and its
    ``k`` experts in each layer, and the head."""
    s = _s(cfg)
    attn = s["d"] * (s["hq"] + 2 * s["hkv"]) * s["hd"] + s["hq"] * s["hd"] * s["d"]
    layer = attn + s["d"] * s["E"] + s["k"] * 3 * s["d"] * s["ffe"]
    return s["L"] * layer + s["d"] * s["V"]


def kv_bytes_per_token(cfg: dict) -> int:
    s = _s(cfg)
    return s["L"] * 2 * s["hkv"] * s["hd"] * s["item"]


def prefill(cfg: dict, p_len: int) -> dict:
    """One prompt of ``p_len`` tokens, logits for its last row only."""
    s = _s(cfg)
    flops = 2.0 * p_len * (token_weight_elems(cfg) - s["d"] * s["V"]) + 2.0 * s["d"] * s["V"]
    flops += s["L"] * 2.0 * s["hq"] * s["hd"] * p_len * (p_len + 1)
    byts = token_weight_elems(cfg) * s["item"] + p_len * kv_bytes_per_token(cfg)
    return {"flops": flops, "bytes": float(byts)}


def decode_steps(cfg: dict, steps: int, row_lengths) -> dict:
    s = _s(cfg)
    live = float(sum(row_lengths))
    flops = 2.0 * len(row_lengths) * token_weight_elems(cfg)
    flops += 4.0 * s["L"] * s["hq"] * s["hd"] * live
    byts = steps * token_weight_elems(cfg) * s["item"]
    byts += (live + len(row_lengths)) * kv_bytes_per_token(cfg)
    return {"flops": flops, "bytes": float(byts)}
