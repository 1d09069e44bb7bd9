"""Counts for ``"architecture": "glm_moe_dsa"``: operations and bytes of a
prefill, of one prefill chunk and of decode steps, from the configuration
and the token counts alone: what the algorithm needs whatever implements it.

* Every weight a token meets is multiplied once: the latent projections
  (q_a, q_b, kv_a, kv_b, o: kv_b counts the same in the expanded and in the
  absorbed form), the indexer's on the ``full`` layers, the dense
  feed-forward or the router, the shared expert and the routed experts.
* Routed experts, under uniform routing and stated as such: a row makes
  ``num_experts_per_tok`` picks over the published experts, of which the
  share held here is computed (operations for ``rows x k x held / E``
  picks); a call of ``r`` rows reads the expected number of distinct held
  experts, ``held (1 - (1 - 1/E)^(k r))``. The program's real spread is in
  ``tdt_ep_expert_tokens_total`` (``expert_rows_per_call`` reads it).
* Attention: a query attends to ``min(visible, index_topk)`` positions on
  every layer (scores over nope + rope, the sum over v, per head; in decode
  the same in the latent space); the index scores run over everything
  visible on the ``full`` layers.
* Bytes are counted once: weights once a step (or a prefill, or a chunk),
  both kinds of cache row written once, and in decode the index keys of the
  live length on the ``full`` layers and the selected latent rows on every
  layer. Norms, RoPE, the top-k itself and the embedding lookup are left
  out, so a share can only read low for them.
"""

from __future__ import annotations


def _s(cfg: dict) -> dict:
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope, vd = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q_rank, kv_rank = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    Hi, Di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    kinds = list(cfg["mlp_layer_types"])
    fe = int(cfg["moe_intermediate_size"])
    return {
        "item": item, "d": d, "H": H, "nope": nope, "rope": rope, "vd": vd, "kv_rank": kv_rank,
        "Hi": Hi, "Di": Di, "topk": int(cfg["index_topk"]), "V": int(cfg["vocab_size"]),
        "L": len(kinds), "F": sum(1 for k in cfg["indexer_types"] if k == "full"),
        "n_dense": kinds.count("dense"), "n_exp": kinds.count("sparse"),
        "attn": d * q_rank + q_rank * H * (nope + rope) + d * (kv_rank + rope)
                + kv_rank * H * (nope + vd) + H * vd * d,
        "indexer": q_rank * Hi * Di + d * Di + d * Hi,
        "dense": 3 * d * int(cfg["intermediate_size"]),
        "expert": 3 * d * fe,
        "E": int(cfg["published"]["n_routed_experts"]), "held": int(cfg["experts_held"][1]),
        "k": int(cfg["num_experts_per_tok"]),
        "row_bytes": (len(kinds) * (kv_rank + rope)
                      + sum(1 for k in cfg["indexer_types"] if k == "full") * Di) * item,
    }


def token_weight_elems(cfg: dict) -> float:
    """Weights one row is multiplied by in the layers (the head apart): the
    routed experts at the expected number of held picks a row."""
    s = _s(cfg)
    routed = s["k"] * s["held"] / s["E"] * s["expert"]
    return (s["L"] * s["attn"] + s["F"] * s["indexer"] + s["n_dense"] * s["dense"]
            + s["n_exp"] * (s["d"] * s["E"] + s["expert"] + routed))


def held_experts_read(cfg: dict, rows: float) -> float:
    """Expected distinct held experts a call of ``rows`` rows reads, under
    uniform routing over the published experts."""
    s = _s(cfg)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["E"]) ** (s["k"] * rows))


def layer_weight_bytes(cfg: dict, rows: float) -> float:
    """Bytes of the layers' weights one call of ``rows`` rows reads."""
    s = _s(cfg)
    fixed = (s["L"] * s["attn"] + s["F"] * s["indexer"] + s["n_dense"] * s["dense"]
             + s["n_exp"] * s["expert"]) * s["item"]
    fixed += s["n_exp"] * s["d"] * s["E"] * 4  # the router is float32
    return fixed + s["n_exp"] * held_experts_read(cfg, rows) * s["expert"] * s["item"]


def _attend_flops(s: dict, attended: float, expanded: bool) -> float:
    """QK^T and PV over ``attended`` (query, position) pairs on every layer."""
    if expanded:
        per = 2.0 * s["H"] * (s["nope"] + s["rope"]) + 2.0 * s["H"] * s["vd"]
    else:  # in the latent space
        per = 2.0 * s["H"] * (s["kv_rank"] + s["rope"]) + 2.0 * s["H"] * s["kv_rank"]
    return s["L"] * per * attended


def _index_flops(s: dict, visible: float) -> float:
    return s["F"] * 2.0 * s["Hi"] * s["Di"] * visible


def _span(first: int, n: int, topk: int) -> tuple[float, float]:
    """(visible, attended) pairs of ``n`` queries at positions ``first``..."""
    visible = n * first + n * (n + 1) / 2.0
    full = max(0, min(n, topk - first))  # queries that still see fewer than topk
    attended = full * first + full * (full + 1) / 2.0 + (n - full) * topk
    return visible, attended


def prefill_chunk(cfg: dict, rows: int, first: int = 0) -> dict:
    """One prefill chunk of ``rows`` rows starting at position ``first``:
    the layers' weights once, the matrix work of its rows, both cache rows
    written, attention over the selection and the index scores. The head is
    not in it (only a prompt's last chunk needs it). At ``first`` 0 this is
    the least a chunk of that many rows needs whatever its offset."""
    s = _s(cfg)
    visible, attended = _span(first, rows, s["topk"])
    flops = 2.0 * rows * token_weight_elems(cfg)
    flops += _attend_flops(s, attended, expanded=True) + _index_flops(s, visible)
    byts = layer_weight_bytes(cfg, rows) + rows * s["row_bytes"]
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
    visible = float(sum(row_lengths))
    attended = float(sum(min(n, s["topk"]) for n in row_lengths))
    flops = 2.0 * rows * (token_weight_elems(cfg) + s["d"] * s["V"])
    flops += _attend_flops(s, attended, expanded=False) + _index_flops(s, visible)
    byts = 0.0
    if steps:
        byts += steps * (layer_weight_bytes(cfg, rows / steps) + s["d"] * s["V"] * s["item"])
    byts += visible * s["F"] * s["Di"] * s["item"]  # index keys of the live length
    byts += attended * s["L"] * (s["kv_rank"] + s["rope"]) * s["item"]  # selected latent rows
    byts += rows * s["row_bytes"]  # one of each row written
    return {"flops": flops, "bytes": float(byts)}
