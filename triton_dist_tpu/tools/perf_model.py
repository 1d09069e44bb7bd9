"""Analytic performance models: chip rooflines + ICI collective times.

Reference: ``python/triton_dist/kernels/nvidia/comm_perf_model.py:94-133``
(expected AG/RS time from NVLink/NIC bandwidth) and
``gemm_perf_model.py:49-127`` (GEMM TFLOPS model). TPU redesign: a chip spec
table (MXU peak, HBM, per-link ICI) + roofline and ring-collective closed
forms. These power two things:

* bench reporting: "achieved X % of the roofline / of the ring bound";
* overlap accounting: given measured fused-op time and the model's compute
  and comm legs, how much of the comm was hidden.

Numbers are public-spec approximations (the scaling-book mental model); they
parameterize *bounds*, not guarantees — tests assert against fractions of
them, never exact values.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    bf16_tflops: float  # MXU peak, dense bf16
    hbm_gbps: float  # HBM bandwidth, GB/s
    ici_link_gbps: float  # one-way bandwidth per ICI link, GB/s
    ici_links: int  # links per chip (torus degree)


# Published peaks (Google Cloud documentation of each generation). Keyed by
# jax device_kind (lowercased prefix). A device that is not here is an error,
# not a default: a roofline share against assumed peaks is no measurement.
CHIPS = {
    "tpu v5 lite": ChipSpec("tpu v5 lite", 197.0, 819.0, 45.0, 4),
    "tpu v5": ChipSpec("tpu v5", 459.0, 2765.0, 90.0, 6),  # v5p
    "tpu v4": ChipSpec("tpu v4", 275.0, 1228.0, 45.0, 6),
}


def chip_spec(device_kind: str | None = None) -> ChipSpec:
    """Spec for the current (or named) device kind. Raises ``KeyError`` for a
    kind the table does not hold (the CPU among them): callers there pass a
    ``ChipSpec`` of their own or skip the model."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for prefix, spec in sorted(CHIPS.items(), key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return spec
    raise KeyError(
        f"no published peaks for device kind {device_kind!r}; "
        f"known: {sorted(CHIPS)}"
    )


# ------------------------------------------------------------------ rooflines


def gemm_time_s(m: int, k: int, n: int, dtype, spec: ChipSpec) -> float:
    """Roofline GEMM time: max(MXU, HBM) leg (reference gemm_perf_model)."""
    item = jnp.dtype(dtype).itemsize
    flops = 2.0 * m * k * n
    bytes_ = (m * k + k * n + m * n) * item
    return max(flops / (spec.bf16_tflops * 1e12), bytes_ / (spec.hbm_gbps * 1e9))


def attention_time_s(b: int, hq: int, s: int, d: int, dtype, spec: ChipSpec,
                     causal: bool = True) -> float:
    """Flash-attention roofline: QK^T + PV flops (halved when causal)."""
    flops = 4.0 * b * hq * s * s * d * (0.5 if causal else 1.0)
    item = jnp.dtype(dtype).itemsize
    bytes_ = 4 * b * hq * s * d * item  # q, k, v, o (flash: one pass)
    return max(flops / (spec.bf16_tflops * 1e12), bytes_ / (spec.hbm_gbps * 1e9))


# ------------------------------------------------------ ring collective times


def _ring_bw(spec: ChipSpec) -> float:
    """Effective one-way bandwidth of a 1D ring embedded in the torus: a
    bidirectional ring drives 2 links concurrently."""
    return 2.0 * spec.ici_link_gbps * 1e9


def allgather_time_s(total_bytes: int, world: int, spec: ChipSpec) -> float:
    """Ring AG: each rank forwards its (total/world) shard world-1 hops
    (reference comm_perf_model.py:94)."""
    if world <= 1:
        return 0.0
    return (world - 1) * (total_bytes / world) / _ring_bw(spec)


def reduce_scatter_time_s(total_bytes: int, world: int, spec: ChipSpec) -> float:
    """Ring RS: same wire volume as AG (partials travel instead of shards)."""
    return allgather_time_s(total_bytes, world, spec)


def allreduce_time_s(total_bytes: int, world: int, spec: ChipSpec) -> float:
    """RS + AG composition: 2·(world-1)/world of the buffer over the ring."""
    return 2.0 * allgather_time_s(total_bytes, world, spec)


def all_to_all_time_s(total_bytes: int, world: int, spec: ChipSpec) -> float:
    """One-shot a2a: each rank ships (world-1)/world of its buffer; with
    world-1 concurrent puts the bisection is the torus links."""
    if world <= 1:
        return 0.0
    return (total_bytes * (world - 1) / world) / (spec.ici_link_gbps * 1e9 * spec.ici_links)


# --------------------------------------------------------- overlap accounting


def overlap_fraction(measured_s: float, compute_s: float, comm_s: float) -> float:
    """How much of the comm the measured fused op hid: 1.0 = perfect overlap
    (measured == max(compute, comm)), 0.0 = fully serial (compute + comm).
    Clipped to [0, 1]; returns 1.0 when there is nothing to hide."""
    serial = compute_s + comm_s
    perfect = max(compute_s, comm_s)
    if serial - perfect <= 0:
        return 1.0
    frac = (serial - measured_s) / (serial - perfect)
    return float(min(1.0, max(0.0, frac)))


def overlap_efficiency(measured_s: float, compute_s: float, comm_s: float) -> float:
    """Perfect-overlap bound over measured: max(compute, comm)/measured —
    BASELINE.md's "FLUX-class overlap efficiency" metric (≥0.9 target)."""
    if measured_s <= 0:
        return 0.0
    return float(max(compute_s, comm_s) / measured_s)
