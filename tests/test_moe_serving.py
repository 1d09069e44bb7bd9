"""MoE end-to-end serving tests: the EP model (``models/moe.py``) through
the full continuous-batching loop.

Acceptance bar (ISSUE 10): ``test-moe`` serves through ``InferenceServer``
(paged KV, chunked prefill) with 8 staggered requests byte-identical to
one-shot ``Engine.serve``, decode routed through the low-latency a2a path
(``ep_moe_ll_shard``) under AUTO with the cross-rank-agreed crossover, plus
a ``-m chaos`` arc (a2a abort → XLA fallback → probe → restore) mirroring
``test_chaos.py``'s dense acceptance arc.

Everything runs on CPU with world=1: every a2a leg short-circuits
``world == 1`` to identity AND the fp8 wire is skipped (no wire → nothing
to compress, ``ll_dispatch_shard``), so the low-latency, fused-composition,
and XLA routes are arithmetically identical — which is exactly what makes
byte-parity against the xla-backend reference a real invariant rather than
a tolerance. Byte-parity additionally requires capacity-safe sizes: routing
capacity is per-call, so a capacity drop in one shape but not another would
fork the streams — the parity test asserts zero drops to keep that
precondition explicit.

The world=4 test anchors the EP model's math against the established
ffe-sharded ``Qwen3MoE`` built from the SAME global weights.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.runtime import resilience, telemetry
from triton_dist_tpu.serving import InferenceServer

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    yield
    telemetry.reset()
    resilience.reset_degradation()


@pytest.fixture(scope="module")
def moe_model1():
    """world=1 test-moe EP model (E_local = E = 8; the a2a legs are
    identity, so the ROUTE taken is what the tests pin down)."""
    from triton_dist_tpu.models import EPMoELLM, PRESETS
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    return EPMoELLM(PRESETS["test-moe"], ctx, key=jax.random.PRNGKey(1))


def make_engine(model, backend="xla"):
    from triton_dist_tpu.models import Engine

    return Engine(model, backend=backend, max_len=MAX_LEN)


# Mixed prompt/gen lengths; ≥8 requests; arrivals land mid-decode.
REQUESTS = [
    ([3, 17, 42, 7, 99], 6),
    ([8, 1, 13], 4),
    ([5, 5, 5, 5, 5, 5, 5, 5], 3),
    ([100, 200, 30], 5),
    ([7, 7, 7, 7], 1),
    ([91, 12, 55, 2, 8, 41], 4),
    ([3, 3], 6),
    ([111, 4, 9, 16, 25, 36, 49], 3),
]


@pytest.fixture(scope="module")
def moe_refs(moe_model1):
    """One-shot ``Engine.serve`` references on the forced-XLA backend,
    computed ONCE for the module (the parity and chaos tests compare
    served streams against the same byte-exact baselines)."""
    eng = make_engine(moe_model1, backend="xla")
    return [
        np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0]
        for p, g in REQUESTS
    ]


def _route_count(method):
    return telemetry.counter_value(
        "tdt_ep_auto_route_total", collective="ep_a2a", method=method
    )


# ===================================== acceptance: staggered serving parity


def test_moe_server_parity_staggered(moe_model1, moe_refs):
    """8 staggered requests through ``InferenceServer`` on the dist_ar
    engine, byte-identical to one-shot serves on a separate XLA-backend
    engine — crossing the backend boundary on purpose: the AUTO-routed
    low-latency decode must be the same function as the forced-XLA path."""
    refs = moe_refs

    eng = make_engine(moe_model1, backend="dist_ar")
    xover = [
        e for e in telemetry.snapshot()["gauges"].get(
            "tdt_engine_prefill_crossover_rows", [])
        if e["labels"].get("op") == "ep_a2a"
    ]
    assert xover and xover[0]["value"] >= 1.0

    srv = InferenceServer(eng, num_slots=3, chunk=2)
    streams: dict[int, list[int]] = {}
    handles = [
        srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
            r.req_id, []).append(t))
        for p, g in REQUESTS[:4]
    ]
    assert srv.step()
    handles += [
        srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
            r.req_id, []).append(t))
        for p, g in REQUESTS[4:]
    ]
    srv.run()

    assert srv.scheduler.occupancy() == 0 and srv.scheduler.queue_depth() == 0
    for h, (prompt, gen), ref in zip(handles, REQUESTS, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)
        assert len(h.tokens) == gen

    # Decode batches (≤3 tokens) sit well under the agreed crossover: AUTO
    # must have routed the low-latency path when the decode programs traced.
    assert _route_count("low_latency") > 0.0
    # Per-expert load telemetry flowed through the dispatch path at runtime.
    assert telemetry.counter_value(
        "tdt_ep_dispatch_total", route="low_latency") > 0.0
    assert telemetry.counter_total("tdt_ep_expert_tokens_total") > 0.0
    # Capacity-safety precondition of byte-parity: zero overflow drops
    # (routing capacity is per-call, so a drop would fork chunked-vs-oneshot).
    assert telemetry.counter_total("tdt_ep_dropped_tokens_total") == 0.0
    # world=1: no wire, no wire bytes.
    assert telemetry.counter_total("tdt_ep_wire_bytes_total") == 0.0

    # The `/requests` introspection payload exposes the EP view.
    info = srv._requests_info()
    assert "ep" in info
    assert info["ep"]["routes"].get("low_latency", 0.0) > 0.0
    assert info["ep"]["crossover_t"] >= 1
    assert info["ep"]["dropped_tokens"] == 0.0
    assert sum(info["ep"]["expert_load"].values()) == pytest.approx(1.0, abs=1e-3)


def test_moe_engine_prefill_routes_fused_above_crossover(moe_model1):
    """A prompt longer than the agreed crossover must trace the FUSED
    composition for prefill while decode still routes low-latency — the
    two-regime contract the AUTO resolver exists for."""
    from triton_dist_tpu.kernels.low_latency_a2a import ep_a2a_crossover_tokens

    from triton_dist_tpu.models import Engine

    xover = ep_a2a_crossover_tokens(moe_model1.world)
    seq = xover + 4
    eng = Engine(moe_model1, backend="dist_ar", max_len=seq + 8)
    base_fused = _route_count("fused")
    ids = jnp.asarray([list(range(2, seq + 2))], jnp.int32)
    out = eng.serve(ids, gen_len=1)
    assert np.asarray(out).shape == (1, 1)
    assert _route_count("fused") > base_fused


def test_moe_mega_backend_serves(moe_model1, moe_refs):
    """The old hard rejection is gone: the EP model builds on the mega
    backend (step-graph decode with the EP MoE lowered via the builder's
    ``moe_impl`` hook) and greedy output is byte-identical to the XLA
    reference. Full serving/chaos coverage lives in test_megakernel.py."""
    import jax.numpy as jnp

    eng = make_engine(moe_model1, backend="mega")
    assert eng.preferred_backend == "mega"
    p, g = REQUESTS[1]
    out = np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0]
    np.testing.assert_array_equal(out, moe_refs[1])


# ============================================== chaos: abort → probe arc


@pytest.mark.chaos
def test_moe_chaos_abort_probe_restore(moe_model1, moe_refs, monkeypatch):
    """The MoE mirror of the dense acceptance arc: AUTO-routed serving →
    chaos abort on the second decode chunk → degraded-XLA recovery (every
    EP MLP forced onto the XLA a2a transport) → failed probe doubles the
    backoff → second probe restores the dist_ar backend in-process, zero
    token loss or duplication across the whole arc."""
    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    refs = moe_refs

    eng = make_engine(moe_model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    streams: dict[int, list[int]] = {}
    with resilience.chaos_schedule("abort@decode:1,abort@probe,heal"):
        handles = [
            srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
                r.req_id, []).append(t))
            for p, g in REQUESTS[:2]
        ]
        srv.run()
        deadline = time.monotonic() + 30.0
        while eng.backend != "dist_ar":
            assert time.monotonic() < deadline, "probe never restored fused"
            if not srv.step():
                time.sleep(0.005)

    for h, ref in zip(handles, refs[:4]):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)

    assert eng.backend == "dist_ar"
    assert not resilience.any_degraded()
    trans = [
        (e["from_state"], e["to_state"])
        for e in telemetry.events("breaker_transition")
        if e["feature"] == "collectives"
    ]
    assert trans == [
        ("closed", "open"), ("open", "half_open"), ("half_open", "open"),
        ("open", "half_open"), ("half_open", "closed"),
    ]
    assert telemetry.counter_value(
        "tdt_serving_recoveries_total", from_backend="dist_ar"
    ) == 1.0
    assert telemetry.counter_value(
        "tdt_serving_restores_total", to_backend="dist_ar"
    ) == 1.0
    # The degraded interlude really served MoE MLPs on the XLA transport
    # (the rebuilt xla engine's programs force EPMoEMethod.XLA), and the
    # restore re-traced the low-latency route.
    assert telemetry.counter_value(
        "tdt_ep_dispatch_total", route="xla") > 0.0
    assert _route_count("low_latency") > 0.0


# ==================================== world=4: EP model vs TP_MoE anchor


def test_ep_model_matches_tp_moe_world4():
    """EPMoELLM and the ffe-sharded Qwen3MoE built from the SAME global
    weights compute the same function (different parallel decompositions of
    identical expert math — summation orders differ, so allclose not
    byte-equality)."""
    from triton_dist_tpu.models import EPMoELLM, PRESETS, Qwen3MoE
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((4,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    cfg = PRESETS["test-moe"]
    key = jax.random.PRNGKey(7)
    ep = EPMoELLM(cfg, ctx, key=key)
    tp = Qwen3MoE(cfg, ctx, key=key)
    # Same init key → identical global weights, different placements.
    np.testing.assert_array_equal(
        np.asarray(ep.params.mlp_gate), np.asarray(tp.params.mlp_gate)
    )

    ids = jnp.asarray([[5, 9, 13, 2, 44, 7, 3, 19]], jnp.int32)
    eng_ep = make_engine(ep, backend="xla")
    eng_tp = make_engine(tp, backend="xla")
    logits_ep, _, _ = eng_ep._prefill(ep.params, ids)
    logits_tp, _, _ = eng_tp._prefill(tp.params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_ep), np.asarray(logits_tp), rtol=2e-4, atol=2e-4
    )
    # Greedy generations agree end-to-end at these scales.
    out_ep = np.asarray(eng_ep.serve(ids, gen_len=3))
    out_tp = np.asarray(eng_tp.serve(ids, gen_len=3))
    np.testing.assert_array_equal(out_ep, out_tp)
