"""Counts for ``"architecture": "minicpm_sala"``: operations and bytes of a
prefill, of one prefill chunk, of decode steps and of one call of each new
kernel, from the configuration and the token counts alone: the LEAST the
architecture needs, whatever implements it.

* Every weight a row meets is multiplied once (``w_in``, ``w_o``, the
  SwiGLU's three matrices a layer; the head for a row whose logits are
  asked for). Weights are read once a step, a chunk or a prefill.
* A lightning layer's row costs a head its state's update and its read:
  ``4 D^2`` operations (``k^T v`` added to the decayed state, ``q S``); a
  slot's state (``heads x D x D`` float32 a layer) is read and written once
  a chunk or a decode step.
* A sparse layer's query attends the positions ``<= i`` of at most ``topk``
  blocks, its own block in part (:func:`attended`); a (query head,
  position) pair costs ``4 D`` operations (``q . k`` and its share of ``P
  V``), and the K and V rows of the attended positions are read once a
  (query, K/V head) in decode, once a chunk in prefill. The selection costs
  a query head ``2 D`` operations a visible pooled key and reads the
  slot's pooled keys. Every K/V row is written once.
* Norms, the rotation, the gates' sigmoid, the selection's ranking and the
  embedding lookup are left out, so a share can only read low for them.
"""

from __future__ import annotations

import numpy as np


def _s(cfg: dict) -> dict:
    sp = cfg["assumed"]["sparse_config"]
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
    d, ff = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    hq, hkv, D = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                  int(cfg["head_dim"]))
    lh, ld = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    kinds = list(cfg["mixer_types"])
    mlp = 3 * d * ff
    return {
        "item": item, "d": d, "V": int(cfg["vocab_size"]), "hq": hq, "hkv": hkv, "D": D,
        "lh": lh, "ld": ld, "n_sparse": kinds.count("minicpm4"),
        "n_lin": kinds.count("lightning-attn"),
        "sparse": d * (2 * hq * D + 2 * hkv * D) + hq * D * d + mlp,
        "lightning": 5 * d * lh * ld + mlp,
        "B": int(sp["block_size"]), "topk": int(sp["topk"]), "K": int(sp["kernel_size"]),
        "stride": int(sp["kernel_stride"]),
        "kv_row": 2 * hkv * D * item,  # one layer's K and V of one position
        "state": lh * ld * ld * 4,  # one lightning layer's, one slot
    }


def body_elems(cfg: dict) -> float:
    """Weights every row meets: the layers'."""
    s = _s(cfg)
    return float(s["n_sparse"] * s["sparse"] + s["n_lin"] * s["lightning"])


def head_elems(cfg: dict) -> float:
    s = _s(cfg)
    return float(s["d"] * s["V"])


def attended(s: dict, length):
    """Positions the query at ``length - 1`` attends (``length`` an int or an
    array of them): all it sees while its blocks are at most ``topk``, else
    ``topk - 1`` whole blocks and its own block up to itself."""
    length = np.asarray(length)
    own = (length - 1) // s["B"]
    return np.where(own + 1 <= s["topk"], length,
                    (s["topk"] - 1) * s["B"] + (length - 1) % s["B"] + 1)


def _attended_sum(s: dict, first: int, rows: int) -> float:
    """The sum of :func:`attended` over queries at positions ``first ...
    first + rows - 1``."""
    return float(attended(s, first + 1 + np.arange(rows)).sum())


def _pooled_seen(s: dict, length):
    """Pooled keys wholly visible from the query at ``length - 1``."""
    return np.maximum((np.asarray(length) - s["K"]) // s["stride"] + 1, 0)


def lightning_chunk(cfg: dict, rows: int) -> dict:
    """One call of the lightning kernel over ``rows`` rows of one layer: the
    chunk's q, k, v read and o written (the model's type), the state in and
    out, the recurrence's arithmetic."""
    s = _s(cfg)
    w = s["lh"] * s["ld"]
    return {"flops": 4.0 * rows * w * s["ld"],
            "bytes": float(4 * rows * w * s["item"] + 2 * s["state"])}


def bsa_prefill(cfg: dict, rows: int, first: int = 0) -> dict:
    """One call of the prefill attend over a chunk of ``rows`` queries at
    positions ``first ...`` of one layer: the query rows read and the result
    written, the K and V rows of the positions some query attends read once
    (at least the chunk's own and ``topk`` blocks before them; all up to the
    chunk's last at position 0, the least whatever the offset), a pair's
    ``4 D`` a query head."""
    s = _s(cfg)
    pairs = _attended_sum(s, first, rows)
    return {"flops": 4.0 * s["hq"] * s["D"] * pairs,
            "bytes": float(2 * rows * s["hq"] * s["D"] * s["item"]
                           + min(first + rows, rows + s["topk"] * s["B"]) * s["kv_row"])}


def bsa_decode(cfg: dict, row_lengths) -> dict:
    """One call of the decode attend of one layer over rows that see
    ``row_lengths`` positions: the selected pages' K and V (the attended
    positions' rows), the query rows and the results."""
    s = _s(cfg)
    seen = float(attended(s, row_lengths).sum())
    return {"flops": 4.0 * s["hq"] * s["D"] * seen,
            "bytes": float(seen * s["kv_row"]
                           + len(row_lengths) * s["hq"] * s["D"] * (s["item"] + 4))}


def prefill_chunk(cfg: dict, rows: int) -> dict:
    """One prefill chunk of ``rows`` rows as at position 0 (the least
    whatever the offset): the layers' weights once and their matrix work,
    the lightning recurrence with one slot's state in and out, attention
    and selection over the chunk's own positions, the K/V rows written."""
    s = _s(cfg)
    flops = 2.0 * rows * body_elems(cfg)
    flops += s["n_lin"] * lightning_chunk(cfg, rows)["flops"]
    flops += s["n_sparse"] * bsa_prefill(cfg, rows)["flops"]
    flops += s["n_sparse"] * 2.0 * s["hq"] * s["D"] * float(
        _pooled_seen(s, 1 + np.arange(rows)).sum())
    byts = body_elems(cfg) * s["item"] + s["n_sparse"] * rows * s["kv_row"]
    byts += 2.0 * s["n_lin"] * s["state"]
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
    seen = float(attended(s, row_lengths).sum())
    pooled = float(_pooled_seen(s, row_lengths).sum())
    flops = 2.0 * rows * (body_elems(cfg) + head_elems(cfg))
    flops += rows * s["n_lin"] * 4.0 * s["lh"] * s["ld"] * s["ld"]
    flops += s["n_sparse"] * (4.0 * s["hq"] * s["D"] * seen + 2.0 * s["hq"] * s["D"] * pooled)
    byts = steps * (body_elems(cfg) + head_elems(cfg)) * s["item"]
    byts += s["n_sparse"] * (seen * s["kv_row"] + pooled * s["hkv"] * s["D"] * s["item"]
                             + rows * s["kv_row"])
    byts += rows * 2.0 * s["n_lin"] * s["state"]
    return {"flops": flops, "bytes": float(byts)}
