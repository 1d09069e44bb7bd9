"""Counts for ``"architecture": "granite_hybrid"``: operations and bytes of a
prefill, of one prefill chunk, of decode steps and of one call of each new
kernel, from the configuration and the token counts alone: the LEAST the
architecture needs, whatever implements it.

* Every weight a row meets is multiplied once (``W_in``, ``W_out``, the
  attention's four, the SwiGLU's three a layer; the head, the embedding's
  transpose, for a row whose logits are asked for). Weights are read once a
  step, a chunk or a prefill.
* A Mamba-2 layer's row costs a head its state's update and its read,
  ``4 N P`` operations; a slot's state (``N x H x P`` float32 a layer) and
  its conv tail are read and written once a chunk or a decode step. Over a
  chunk the chunked form's products are counted (:func:`ssd_chunk`): ``C
  B^T`` once a sub-chunk, the masked product, ``C S_0`` and the state's
  update once a head.
* An attention layer's query attends every position up to its own: a
  (query head, position) pair costs ``4 D`` operations; a decode step reads
  the K and V rows of the positions it sees once a slot. Every K/V row is
  written once.
* The conv, the norms, the gates, the softplus and the embedding lookup are
  left out, so a share can only read low for them.
"""

from __future__ import annotations

import numpy as np


def _s(cfg: dict) -> dict:
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
    d, ff = int(cfg["hidden_size"]), int(cfg["shared_intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D = d // hq
    H, P, N = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]), int(cfg["mamba_d_state"])
    K, c = int(cfg["mamba_d_conv"]), int(cfg["mamba_chunk_size"])
    din = H * P
    kinds = list(cfg["layer_types"])
    mlp = 3 * d * ff
    return {
        "item": item, "d": d, "V": int(cfg["vocab_size"]), "hq": hq, "hkv": hkv, "D": D,
        "H": H, "P": P, "N": N, "c": c,
        "n_m": kinds.count("mamba"), "n_a": kinds.count("attention"),
        "mamba": d * (2 * din + 2 * N + H) + din * d + mlp,
        "attention": d * (hq + 2 * hkv) * D + hq * D * d + mlp,
        "kv_row": 2 * hkv * D * item,  # one layer's K and V of one position
        "state": N * din * 4,  # one Mamba-2 layer's, one slot
        "tail": (K - 1) * (din + 2 * N) * item,
    }


def body_elems(cfg: dict) -> float:
    """Weights every row meets: the layers'."""
    s = _s(cfg)
    return float(s["n_m"] * s["mamba"] + s["n_a"] * s["attention"])


def head_elems(cfg: dict) -> float:
    s = _s(cfg)
    return float(s["d"] * s["V"])


def ssd_chunk(cfg: dict, rows: int) -> dict:
    """One call of the chunked state-space kernel over ``rows`` rows of one
    layer (whole sub-chunks): ``C B^T`` a sub-chunk, the masked product,
    ``C S_0`` and the state's update a head; x, dt, B and C read and y
    written in the model's type (dt float32), the state in and out."""
    s = _s(cfg)
    c, N, H, P = min(s["c"], rows), s["N"], s["H"], s["P"]
    subs = -(-rows // c)
    flops = subs * (2.0 * c * c * N + H * (2.0 * c * c * P + 4.0 * c * N * P))
    byts = rows * (2 * H * P * s["item"] + 4 * H + 2 * N * s["item"]) + 2 * s["state"]
    return {"flops": flops, "bytes": float(byts)}


def ssd_step(cfg: dict, rows: int) -> dict:
    """One call of the decode kernel of one layer over ``rows`` advancing
    slots: each one's state read and written, its x, dt, B and C read and y
    written; ``4 N P`` operations a head."""
    s = _s(cfg)
    per = 2 * s["state"] + 2 * s["H"] * s["P"] * s["item"] + 4 * s["H"] + 2 * s["N"] * s["item"]
    return {"flops": 4.0 * rows * s["N"] * s["H"] * s["P"], "bytes": float(rows * per)}


def prefill_chunk(cfg: dict, rows: int) -> dict:
    """One prefill chunk of ``rows`` rows as at position 0 (the least
    whatever the offset): the layers' weights once and their matrix work,
    the chunked state-space form with one slot's state and tail in and out,
    causal attention over the chunk's own positions, the K/V rows
    written."""
    s = _s(cfg)
    flops = 2.0 * rows * body_elems(cfg) + s["n_m"] * ssd_chunk(cfg, rows)["flops"]
    flops += s["n_a"] * 4.0 * s["hq"] * s["D"] * rows * (rows + 1) / 2
    byts = body_elems(cfg) * s["item"] + s["n_a"] * rows * s["kv_row"]
    byts += 2.0 * s["n_m"] * (s["state"] + s["tail"])
    return {"flops": flops, "bytes": float(byts)}


def prefill(cfg: dict, p_len: int) -> dict:
    """One prompt of ``p_len`` tokens, logits for its last row only, as one
    pass (however many chunks serve it: the weights count once)."""
    s = _s(cfg)
    work = prefill_chunk(cfg, p_len)
    work["flops"] += 2.0 * head_elems(cfg)
    work["bytes"] += head_elems(cfg) * s["item"]
    return work


def decode_steps(cfg: dict, steps: int, row_lengths) -> dict:
    """``steps`` decode steps that between them compute one row for every
    entry of ``row_lengths``: the positions that row can see (its own
    included). Weights are read once a step whatever the batch."""
    s = _s(cfg)
    rows = len(row_lengths)
    seen = float(np.sum(row_lengths))
    flops = 2.0 * rows * (body_elems(cfg) + head_elems(cfg))
    flops += s["n_m"] * ssd_step(cfg, rows)["flops"] + s["n_a"] * 4.0 * s["hq"] * s["D"] * seen
    byts = steps * (body_elems(cfg) + head_elems(cfg)) * s["item"]
    byts += s["n_m"] * rows * 2.0 * (s["state"] + s["tail"])
    byts += s["n_a"] * (seen + rows) * s["kv_row"]
    return {"flops": flops, "bytes": float(byts)}
