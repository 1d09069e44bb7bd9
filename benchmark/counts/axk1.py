"""Counts for ``"architecture": "axk1"``: operations and bytes of a prefill,
of one prefill chunk, of decode steps and of the two latent-attention
kernels, from the configuration and the token counts alone: what the
algorithm needs whatever implements it.

* Every weight a token meets is multiplied once: the latent projections
  (q_a, q_b, kv_a, kv_b, o: kv_b counts the same in the expanded and in the
  absorbed form), the dense feed-forward or the router, the shared expert
  and the routed experts.
* Routed experts, under uniform routing and stated as such: a row makes
  ``num_experts_per_tok`` picks over the published experts, of which the
  share held here is computed (operations for ``rows x k x held / E``
  picks); a call of ``r`` rows reads the expected number of distinct held
  experts, ``held (1 - (1 - 1/E)^(k r))``. The program's real spread is in
  ``tdt_ep_expert_tokens_total`` (``expert_rows_per_call`` reads it).
* Attention: a query attends to every earlier position and itself on every
  layer (scores over nope + rope, the sum over v, per head; in decode the
  same in the latent space: scores over kv_lora_rank + rope, the sum over
  kv_lora_rank).
* Bytes are counted once: weights once a step (or a prefill, or a chunk),
  a token's latent row written once a layer, and in decode the latent rows
  of the live length read once a slot a layer (one row serves all heads).
  A row is counted at the ``kv_lora_rank + rope`` values it holds; the pool
  keeps it in whole lanes (576 in 640), so a kernel that reads pages whole
  moves a ninth more and its share reads the lower for it. Norms, the
  rotary, the router's top-k and the embedding lookup are left out, so a
  share can only read low for them.
"""

from __future__ import annotations


def _s(cfg: dict) -> dict:
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope, vd = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q_rank, kv_rank = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    L, n_dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return {
        "item": item, "d": d, "H": H, "nope": nope, "rope": rope, "vd": vd, "kv_rank": kv_rank,
        "V": int(cfg["vocab_size"]), "L": L, "n_dense": n_dense, "n_exp": L - n_dense,
        "attn": d * q_rank + q_rank * H * (nope + rope) + d * (kv_rank + rope)
                + kv_rank * H * (nope + vd) + H * vd * d,
        "dense": 3 * d * int(cfg["intermediate_size"]),
        "expert": 3 * d * int(cfg["moe_intermediate_size"]),
        "E": int(cfg["published"]["n_routed_experts"]), "held": int(cfg["experts_held"][1]),
        "k": int(cfg["num_experts_per_tok"]),
        "row": (kv_rank + rope) * item,  # one layer's latent row
    }


def token_weight_elems(cfg: dict) -> float:
    """Weights one row is multiplied by in the layers (the head apart): the
    routed experts at the expected number of held picks a row."""
    s = _s(cfg)
    routed = s["k"] * s["held"] / s["E"] * s["expert"]
    return (s["L"] * s["attn"] + s["n_dense"] * s["dense"]
            + s["n_exp"] * (s["d"] * s["E"] + s["expert"] + routed))


def held_experts_read(cfg: dict, rows: float) -> float:
    """Expected distinct held experts a call of ``rows`` rows reads, under
    uniform routing over the published experts."""
    s = _s(cfg)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["E"]) ** (s["k"] * rows))


def layer_weight_bytes(cfg: dict, rows: float) -> float:
    """Bytes of the layers' weights one call of ``rows`` rows reads."""
    s = _s(cfg)
    fixed = (s["L"] * s["attn"] + s["n_dense"] * s["dense"] + s["n_exp"] * s["expert"]) * s["item"]
    fixed += s["n_exp"] * s["d"] * s["E"] * 4  # the router is float32
    return fixed + s["n_exp"] * held_experts_read(cfg, rows) * s["expert"] * s["item"]


def _pairs(first: int, n: int) -> float:
    """(query, position) pairs of ``n`` queries at positions ``first``..."""
    return n * first + n * (n + 1) / 2.0


def latent_prefill(cfg: dict, rows: int, first: int = 0) -> dict:
    """One call of the prefill attend of one layer over a chunk of ``rows``
    queries at positions ``first ...``: QK^T over nope + rope and PV over v
    a (head, pair), K and V made once a (head, visible position) from its
    latent row (that is kv_b's work, which the kernel does), the queries
    read, the result written, the visible rows read once. At ``first`` 0 the
    least a chunk of that many rows needs whatever its offset."""
    s = _s(cfg)
    seen = first + rows
    flops = 2.0 * s["H"] * (s["nope"] + s["rope"] + s["vd"]) * _pairs(first, rows)
    flops += 2.0 * seen * s["kv_rank"] * s["H"] * (s["nope"] + s["vd"])
    byts = rows * s["H"] * (s["nope"] + s["rope"] + s["vd"]) * s["item"] + seen * s["row"]
    return {"flops": flops, "bytes": float(byts)}


def latent_decode(cfg: dict, row_lengths) -> dict:
    """One call of the decode attend of one layer over rows that see
    ``row_lengths`` positions: scores and the weighted sum in the latent
    space a (head, position), each slot's visible latent rows read once for
    all heads, the absorbed queries read and the latent results written."""
    s = _s(cfg)
    seen = float(sum(row_lengths))
    flops = 2.0 * s["H"] * (2 * s["kv_rank"] + s["rope"]) * seen
    byts = seen * s["row"] + len(row_lengths) * s["H"] * (
        (s["kv_rank"] + s["rope"]) * s["item"] + s["kv_rank"] * 4)
    return {"flops": flops, "bytes": float(byts)}


def prefill_chunk(cfg: dict, rows: int, first: int = 0) -> dict:
    """One prefill chunk of ``rows`` rows starting at position ``first``:
    the layers' weights once, the matrix work of its rows, the latent rows
    written, attention over everything visible. The head is not in it (only
    a prompt's last chunk needs it). kv_b's work is counted once, with the
    rows' other weights (the kernel's share of it for the positions before
    the chunk is left out: it is recomputation, not the algorithm's). At
    ``first`` 0 this is the least a chunk of that many rows needs whatever
    its offset."""
    s = _s(cfg)
    flops = 2.0 * rows * token_weight_elems(cfg)
    flops += s["L"] * 2.0 * s["H"] * (s["nope"] + s["rope"] + s["vd"]) * _pairs(first, rows)
    byts = layer_weight_bytes(cfg, rows) + rows * s["L"] * s["row"]
    return {"flops": flops, "bytes": float(byts)}


def prefill(cfg: dict, p_len: int) -> dict:
    """One prompt of ``p_len`` tokens, logits for its last row only, as one
    pass (however many chunks serve it: the weights count once)."""
    s = _s(cfg)
    work = prefill_chunk(cfg, p_len)
    work["flops"] += 2.0 * s["d"] * s["V"]
    work["bytes"] += float(s["d"] * s["V"] * s["item"])
    return work


def decode_steps(cfg: dict, steps: int, row_lengths) -> dict:
    """``steps`` decode steps that between them compute one row for every
    entry of ``row_lengths``: the positions that row can see (its own
    included). Weights are read once a step whatever the batch; the routed
    experts at the expected number of distinct held experts a step."""
    s = _s(cfg)
    rows = len(row_lengths)
    attend = latent_decode(cfg, row_lengths)
    flops = 2.0 * rows * (token_weight_elems(cfg) + s["d"] * s["V"]) + s["L"] * attend["flops"]
    byts = 0.0
    if steps:
        byts += steps * (layer_weight_bytes(cfg, rows / steps) + s["d"] * s["V"] * s["item"])
    byts += s["L"] * float(sum(row_lengths)) * s["row"]  # the live rows, once a layer
    byts += rows * s["L"] * s["row"]  # one row written a layer
    return {"flops": flops, "bytes": float(byts)}
