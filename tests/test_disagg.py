"""Disaggregated prefill/decode serving tests.

Three tiers (same substrate conventions as ``tests/test_fleet.py``):

* **in-process, tier-1** — the KV handoff wire (pack → JSON → unpack →
  scatter byte-identical), the two-server export/import splice producing
  byte-identical greedy streams vs the unified engine, and the
  determinism fallback: a prefill pool rebuild (the in-process analog of
  a kill -9) invalidates the parked KV, export fails loudly, and the
  decode server re-derives from the journaled token history —
  byte-identical again.
* **multi-process** (``slow``) — a 2-replica Router split into
  prefill/decode pools: fresh requests place prefill-only, the router
  splices each stream onto the decode replica over
  ``kv_export``/``kv_import``, and every stream matches the one-shot
  reference byte for byte.
* **chaos** (``slow`` + ``chaos``; the ``disagg-handoff-kill`` row of
  ``scripts/run_chaos_suite.sh``) — SIGKILL the whole prefill pool
  mid-burst, and separately inject wire faults on ``kv_export``: both
  arcs fall back to journal re-derivation with byte-identical streams.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.disagg.kv_transfer import (
    blocks_for,
    pack_kv_blocks,
    unpack_kv_blocks,
)
from triton_dist_tpu.disagg.pool import ROLE_DECODE, ROLE_PREFILL, default_roles
from triton_dist_tpu.fleet import Router
from triton_dist_tpu.runtime import introspect, resilience, telemetry
from triton_dist_tpu.serving import InferenceServer

MAX_LEN = 32

REPLICA_ENV = {
    "JAX_PLATFORMS": "cpu",
    "TDT_SERVE_SLOTS": "2",
    "TDT_SERVE_CHUNK": "2",
}

REQUESTS = [
    ([5, 3, 7, 2, 9, 4], 8),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9], 6),
    ([17, 3, 17, 3, 17], 7),
    ([9, 8, 7, 6], 5),
]


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    introspect.clear_json_routes()
    yield
    telemetry.reset()
    resilience.reset_degradation()
    introspect.clear_json_routes()


@pytest.fixture(scope="module")
def engine():
    from triton_dist_tpu.models import PRESETS, DenseLLM, Engine
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    model = DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(1))
    return Engine(model, backend="xla", max_len=MAX_LEN)


def _references(eng, requests):
    return [
        list(np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0])
        for p, g in requests
    ]


def _pools(engine, monkeypatch):
    """One prefill-role and one decode-role InferenceServer over the same
    engine (separate KV pools — the in-process stand-in for two replica
    subprocesses)."""
    monkeypatch.setenv("TDT_POOL_ROLE", ROLE_PREFILL)
    pre = InferenceServer(engine, num_slots=2, chunk=2)
    monkeypatch.setenv("TDT_POOL_ROLE", ROLE_DECODE)
    dec = InferenceServer(engine, num_slots=2, chunk=2)
    monkeypatch.delenv("TDT_POOL_ROLE")
    assert pre.role == ROLE_PREFILL and dec.role == ROLE_DECODE
    return pre, dec


# ========================================================== in-process tier


def test_default_roles_split():
    assert default_roles(1) == ["unified"]
    assert default_roles(2) == ["prefill", "decode"]
    assert default_roles(5) == ["prefill"] * 2 + ["decode"] * 3


def test_kv_wire_blob_json_roundtrip(engine, monkeypatch):
    """pack → JSON text (the fleet wire) → unpack returns byte-identical
    block payloads with a validated header."""
    pre, _ = _pools(engine, monkeypatch)
    p, g = REQUESTS[0]
    h = pre.submit(p, g, prefill_only=True)
    pre.run()
    assert h.done and h.finish_reason == "handoff"
    blob = pre.export_kv(h.req_id)
    assert blob["kind"] == "tdt-paged-kv" and blob["ver"] == 1
    assert blob["length"] == len(p + list(h.tokens)[:-1])
    assert blob["n_blocks"] == blocks_for(blob["length"], blob["block_size"])
    assert blob["wire_bytes"] > 0
    wire = json.loads(json.dumps(blob))     # the actual transport format
    a = unpack_kv_blocks(wire)
    b = unpack_kv_blocks(blob)
    np.testing.assert_array_equal(a["k"], b["k"])
    np.testing.assert_array_equal(a["v"], b["v"])
    with pytest.raises(ValueError):
        unpack_kv_blocks({**blob, "ver": 99})
    with pytest.raises(ValueError):
        unpack_kv_blocks({"kind": "nope"})
    # Blocks ship in the pool's STORED format: the payload bytes equal the
    # donor cache rows exactly.
    direct = pack_kv_blocks(
        pre.cache, pre._handoffs[h.req_id]["blocks"], length=blob["length"]
    )
    assert direct["k"] == blob["k"] and direct["v"] == blob["v"]
    assert pre.release_handoff(h.req_id)


def test_disagg_streams_match_unified_bitwise(engine, monkeypatch):
    """The acceptance bar, in-process: prefill server parks + exports,
    decode server imports + decodes — every greedy stream byte-identical
    to the unified one-shot engine, and the parked refs all return to the
    pool after release."""
    refs = _references(engine, REQUESTS)
    pre, dec = _pools(engine, monkeypatch)
    handles = [pre.submit(p, g, prefill_only=True) for p, g in REQUESTS]
    pre.run()
    outs = []
    for (p, g), h in zip(REQUESTS, handles):
        assert h.done and h.finish_reason == "handoff"
        assert len(h.tokens) >= 1          # prefill samples the first token
        blob = json.loads(json.dumps(pre.export_kv(h.req_id)))
        outs.append(dec.import_kv(p, g, list(h.tokens), blob))
        assert pre.release_handoff(h.req_id)
        assert not pre.release_handoff(h.req_id)   # idempotent
    dec.run()
    for req, ref in zip(outs, refs):
        assert req.done
        assert list(req.tokens) == ref
    # Handoff bookkeeping drained: nothing parked, every exported chain's
    # extra refs returned to the allocator.
    assert not pre._handoffs
    for h in handles:
        with pytest.raises(KeyError):
            pre.export_kv(h.req_id)
    assert telemetry.events("serving_handoff_parked")
    assert telemetry.events("serving_kv_import")
    role = telemetry.gauge_value("tdt_disagg_pool_role")
    assert role in (1.0, 2.0)


def test_prefill_pool_loss_rederives_from_history(engine, monkeypatch):
    """The determinism fallback: the prefill pool rebuilds (kill/restore)
    while a handoff is parked — export raises KeyError (the router's 404
    cue) and the decode server re-derives the KV from the journaled token
    history, byte-identical to the unified stream."""
    refs = _references(engine, REQUESTS[:2])
    pre, dec = _pools(engine, monkeypatch)
    handles = [pre.submit(p, g, prefill_only=True) for p, g in REQUESTS[:2]]
    pre.run()
    assert sorted(pre._handoffs) == [h.req_id for h in handles]
    pre._fresh_cache()                     # pool rebuild: parked KV is gone
    assert not pre._handoffs
    for h in handles:
        with pytest.raises(KeyError):
            pre.export_kv(h.req_id)
    # Decode-side re-derive: seed the delivered history, recompute prefill.
    outs = [dec.resume(p, g, list(h.tokens))
            for (p, g), h in zip(REQUESTS[:2], handles)]
    dec.run()
    for req, ref in zip(outs, refs):
        assert req.done
        assert list(req.tokens) == ref


def test_import_rejects_geometry_mismatch(engine, monkeypatch):
    """A blob whose length disagrees with the prompt+history falls back to
    local prefill INSIDE the server (the kv_import consumer absorbs the
    error) — the stream still completes byte-identical."""
    refs = _references(engine, REQUESTS[:1])
    pre, dec = _pools(engine, monkeypatch)
    p, g = REQUESTS[0]
    h = pre.submit(p, g, prefill_only=True)
    pre.run()
    blob = pre.export_kv(h.req_id)
    bad = {**blob, "length": blob["length"] + 1}
    req = dec.import_kv(p, g, list(h.tokens), bad)
    dec.run()
    assert req.done and list(req.tokens) == refs[0]
    assert telemetry.events("serving_kv_import_failed")
    assert not telemetry.events("serving_kv_import")   # wire path never ran


# ============================================================ multi-process


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_fleet_disagg_pools_stream_parity(engine, tmp_path):
    """2-replica fleet split prefill/decode: every fresh request prefills
    on the prefill pool, hands its KV over the wire, decodes on the
    decode pool — streams byte-identical to the unified reference."""
    refs = _references(engine, REQUESTS)
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV,
                roles=[ROLE_PREFILL, ROLE_DECODE]) as router:
        assert router.disagg
        router.start()
        frs = [router.submit(p, g) for p, g in REQUESTS]
        router.serve_all(timeout_s=300)
        for fr, ref in zip(frs, refs):
            assert fr.done and fr.finish_reason == "ok"
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
            assert fr.handoff == "ok"
        assert telemetry.counter_value(
            "tdt_disagg_handoffs_total", outcome="ok") == float(len(REQUESTS))
        assert telemetry.counter_value(
            "tdt_disagg_handoff_bytes_total") > 0
        (hist,) = telemetry.snapshot()["histograms"][
            "tdt_disagg_handoff_seconds"]
        assert hist["count"] == len(REQUESTS)
        # Every prefill ran on the prefill replica, every decode admit on
        # the decode replica.
        topo = router.topology()
        assert topo["disagg"]
        assert topo["pools"] == {"prefill": [0], "decode": [1]}
        roles = {r["idx"]: r["role"] for r in topo["replicas"]}
        assert roles == {0: ROLE_PREFILL, 1: ROLE_DECODE}
        # Replica subprocesses self-describe their role over the wire.
        st0 = router._http(router.replicas[0], "/fleet/status")
        st1 = router._http(router.replicas[1], "/fleet/status")
        assert st0["role"] == ROLE_PREFILL and st1["role"] == ROLE_DECODE
        assert st0["parked_handoffs"] == 0   # all released after splice


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_kill_prefill_pool_mid_handoff(engine, tmp_path):
    """Acceptance: SIGKILL the WHOLE prefill pool mid-burst. In-flight
    prefills, parked handoffs, and fresh placements all fall back — the
    decode replica re-derives every stream from journaled history and the
    router widens placement across pools — byte-identical, zero dropped,
    zero duplicated tokens."""
    reqs = [([3 + i, 17, (i % 5) + 1, 7, 2 * i + 1], 8) for i in range(6)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}

    def collect(fr, tok, idx):
        streams.setdefault(fr.fleet_id, []).append(tok)

    with Router(2, tmp_path / "fleet", env=REPLICA_ENV,
                roles=[ROLE_PREFILL, ROLE_DECODE]) as router:
        router.start()
        frs = [router.submit(p, g, on_token=collect) for p, g in reqs]
        # Let the burst get genuinely mid-flight: at least one stream has
        # started (so at least one handoff is parked or spliced), while
        # later requests are still prefilling.
        deadline = time.monotonic() + 120
        while sum(len(s) for s in streams.values()) < 2:
            assert time.monotonic() < deadline, "burst never started"
            if not router.pump():
                time.sleep(0.01)
        stranded = len(router.replicas[0].inflight)
        router.kill(0)                      # the whole prefill pool, -9
        router.serve_all(timeout_s=300)
        for fr, ref in zip(frs, refs):
            assert fr.done
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
            assert streams[fr.fleet_id] == ref   # zero drop / zero dup
        # The decode replica absorbed cross-pool work: fresh placements
        # widened (pool fallback) and/or stranded prefills re-derived.
        fb = telemetry.counter_total("tdt_disagg_pool_fallbacks_total")
        fell_back = telemetry.counter_value(
            "tdt_disagg_handoffs_total", outcome="fallback")
        migrated = telemetry.counter_total("tdt_fleet_migrations_total")
        if stranded:
            assert fb + fell_back + migrated >= 1.0


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_export_wire_fault_falls_back(engine, tmp_path, monkeypatch):
    """Deterministic wire chaos on ``kv_export``: the first handoff's
    export drops on every retry, the router falls back to journal
    re-derivation (outcome="fallback"), later handoffs splice normally —
    every stream byte-identical throughout."""
    monkeypatch.setenv("TDT_FLEET_RETRIES", "2")   # 3 attempts = 3 drops
    refs = _references(engine, REQUESTS[:3])
    chaos = ",".join(["drop@/fleet/kv_export"] * 3) + ",heal"
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV,
                roles=[ROLE_PREFILL, ROLE_DECODE],
                wire_chaos=chaos) as router:
        router.start()
        frs = [router.submit(p, g) for p, g in REQUESTS[:3]]
        router.serve_all(timeout_s=300)
        for fr, ref in zip(frs, refs):
            assert fr.done
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
        assert telemetry.counter_value(
            "tdt_disagg_handoffs_total", outcome="fallback") >= 1.0
        assert {fr.handoff for fr in frs} <= {"ok", "fallback"}
        assert "fallback" in {fr.handoff for fr in frs}
