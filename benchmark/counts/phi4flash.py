"""Counts for ``"architecture": "phi4flash"``: operations and bytes of a
prefill, of one prefill chunk and of decode steps, from the configuration
and the token counts alone: the LEAST the architecture needs, whatever
implements it.

* Every weight a row meets is multiplied once. A prompt row that is not
  its last needs the layers up to the last Mamba layer and the full
  layer's K/V projection only (the architecture's linear prefill); the
  layers above and the head run for a prompt's last row alone. The head is
  the embedding and is read once.
* The scan is ``rows x d_in x N`` state updates a Mamba layer, 7 operations
  each (``exp(dt * A)``, the decay, the input's outer product and its add,
  the read-out's multiply and add).
* Attention: a window layer attends ``min(length, sliding_window)``
  positions, the full layer and every cross layer all of them; a position
  costs a query head its ``q . k`` and its share of ``P V`` (two softmax
  maps a pair over values ``2 x head`` wide): ``4 x heads x head``
  operations.
* Bytes are counted once: weights once a step (or a prefill, or a chunk);
  in decode, for each row, the full layer's K/V of its length once for each
  of the layers that read them, the window layers' rings over ``min(length,
  sliding_window)``, the Mamba layers' state and conv tail read and
  written; every cache row and ring row written once. Norms, biases, the
  conv's taps and the embedding lookup are left out, so a share can only
  read low for them.
"""

from __future__ import annotations


def _s(cfg: dict) -> dict:
    a = cfg["assumed"]
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hkv, L = int(cfg["num_key_value_heads"]), int(cfg["num_hidden_layers"])
    D = d // hq
    din, n, k, r = int(a["expand"]) * d, int(a["d_state"]), int(a["d_conv"]), int(a["dt_rank"])
    mlp = 3 * d * int(cfg["intermediate_size"])
    half = L // 2
    return {
        "item": item, "d": d, "hq": hq, "hkv": hkv, "D": D, "din": din, "N": n, "K": k,
        "V": int(cfg["vocab_size"]), "w": int(cfg["sliding_window"]),
        "n_mamba": half // 2 + 1, "n_window": half // 2, "n_readers": half // 2,
        # weights a row is multiplied by, a layer of each kind (its MLP in it)
        "mamba": d * 2 * din + din * (r + 2 * n) + r * din + din * d + mlp,
        "window": d * (hq + 2 * hkv) * D + hq * D * d + mlp,
        "kv_proj": d * 2 * hkv * D,
        "full_rest": d * hq * D + hq * D * d + mlp,  # the full layer less its K/V projection
        "gmu": 2 * d * din + mlp,
        "cross": 2 * d * hq * D + mlp,
        "n_gmu": half // 2 - 1, "n_cross": half // 2 - 1,
        "attend": 4.0 * hq * D,  # operations a (query, position) pair
        "kv_row": 2 * hkv * D * item,  # one layer's K and V of one position
        "state": n * din * 4 + (k - 1) * din * item,  # one Mamba layer's, one slot
    }


def below_elems(cfg: dict) -> float:
    """Weights every prompt row meets: the layers up to the last Mamba
    layer, and the full layer's K/V projection."""
    s = _s(cfg)
    return s["n_mamba"] * s["mamba"] + s["n_window"] * s["window"] + s["kv_proj"]


def above_elems(cfg: dict) -> float:
    """Weights a prompt's last row and every decode row meet beside those:
    the rest of the full layer, the layers above it, the head."""
    s = _s(cfg)
    return (s["full_rest"] + s["n_gmu"] * s["gmu"] + s["n_cross"] * s["cross"]
            + s["d"] * s["V"])


def _scan_flops(s: dict, rows: float) -> float:
    return 7.0 * rows * s["din"] * s["N"] * s["n_mamba"]


def _window_pairs(n: int, w: int) -> float:
    """(query, position) pairs of ``n`` queries at positions 0... under a
    window of ``w``."""
    full = min(n, w)  # queries that still see fewer than w
    return full * (full + 1) / 2.0 + (n - full) * w


def ssm_scan(cfg: dict, rows: int) -> dict:
    """One call of the selective-scan kernel over ``rows`` rows of one
    layer: ``x`` and ``dt`` read, ``B`` and ``C``, ``A``, ``D`` and the
    state in; ``y`` and the state out; the updates' arithmetic."""
    s = _s(cfg)
    din, n = s["din"], s["N"]
    byts = 4.0 * (3 * rows * din + 2 * rows * n + 3 * n * din + din)
    return {"flops": 7.0 * rows * din * n, "bytes": byts}


def prefill_chunk(cfg: dict, rows: int) -> dict:
    """One prefill chunk of ``rows`` rows that is not its prompt's last: the
    lower layers' weights once and their matrix work, the scan, the window
    attention as at position 0 (the least whatever the offset), the full
    layer's K/V rows written, one slot's state read and written."""
    s = _s(cfg)
    flops = 2.0 * rows * below_elems(cfg) + _scan_flops(s, rows)
    flops += s["n_window"] * s["attend"] * _window_pairs(rows, s["w"])
    byts = below_elems(cfg) * s["item"] + rows * s["kv_row"]
    byts += 2.0 * s["n_mamba"] * s["state"] + s["n_window"] * min(rows, s["w"]) * s["kv_row"]
    return {"flops": flops, "bytes": float(byts)}


def prefill(cfg: dict, p_len: int) -> dict:
    """One prompt of ``p_len`` tokens, logits for its last row only, as one
    pass (however many chunks serve it: the weights count once)."""
    s = _s(cfg)
    work = prefill_chunk(cfg, p_len)
    work["flops"] += 2.0 * above_elems(cfg) + s["n_readers"] * s["attend"] * p_len
    work["bytes"] += float(above_elems(cfg) * s["item"] + s["n_readers"] * p_len * s["kv_row"])
    return work


def decode_steps(cfg: dict, steps: int, row_lengths) -> dict:
    """``steps`` decode steps that between them compute one row for every
    entry of ``row_lengths``: the positions that row can see (its own
    included). Weights are read once a step whatever the batch."""
    s = _s(cfg)
    rows = len(row_lengths)
    seen = float(sum(row_lengths))
    in_window = float(sum(min(n, s["w"]) for n in row_lengths))
    flops = 2.0 * rows * (below_elems(cfg) + above_elems(cfg)) + _scan_flops(s, rows)
    flops += s["attend"] * (s["n_readers"] * seen + s["n_window"] * in_window)
    byts = steps * (below_elems(cfg) + above_elems(cfg)) * s["item"]
    byts += s["kv_row"] * (s["n_readers"] * seen + s["n_window"] * in_window)
    byts += rows * (2.0 * s["n_mamba"] * s["state"] + (1 + s["n_window"]) * s["kv_row"])
    return {"flops": flops, "bytes": float(byts)}
