"""Scripted chaos harness tests: ChaosSchedule semantics and the full
degrade → probe → restore serving arcs the single-shot FaultPlan cannot
express.

The serving arcs run the world=1 test-dense engine on the ``dist_ar``
backend (every collective short-circuits world==1 to plain XLA, so the
backend label is what changes — no TPU interpret machinery needed) and
assert the ISSUE acceptance bar: fused serving → injected abort →
degraded-XLA recovery with zero token loss/duplication → half-open probe
→ fused routing restored IN-PROCESS, with every transition visible in
telemetry.

Run the suite standalone via ``scripts/run_chaos_suite.sh``.
"""

import os
import time

import jax
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
def model1():
    from triton_dist_tpu.models import PRESETS, DenseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    return DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(1))


def make_engine(model1, backend="xla"):
    from triton_dist_tpu.models import Engine

    return Engine(model1, backend=backend, max_len=MAX_LEN)


REQUESTS = [
    ([3, 17, 42, 7, 99], 6),
    ([8, 1, 13], 4),
    ([100, 200, 30], 5),
    ([91, 12, 55, 2, 8, 41], 4),
]


def _references(eng):
    import jax.numpy as jnp

    return [
        np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0]
        for p, g in REQUESTS
    ]


@pytest.fixture(scope="module")
def refs(model1):
    """The xla engine's one-shot greedy streams of ``REQUESTS``, which every
    served stream below must equal. Greedy serve is deterministic, so the
    module computes them once."""
    return _references(make_engine(model1, backend="xla"))


# ================================================= ChaosSchedule (host)


def test_chaos_schedule_parse_and_consume():
    s = resilience.ChaosSchedule("abort@decode:1, abort@probe ,heal")
    assert [(e.action, e.site, e.skip) for e in s.events] == [
        ("abort", "decode", 1), ("abort", "probe", 0),
    ]
    assert not s.exhausted
    # Checks naming other sites pass through without consuming the head.
    assert s.take("prefill") is None
    # skip=1: the first matching check passes, the second fires.
    assert s.take("decode") is None
    assert s.take("probe") is None  # still queued behind the decode event
    ev = s.take("decode")
    assert ev is not None and ev.action == "abort"
    ev2 = s.take("probe")
    assert ev2 is not None and s.exhausted
    assert s.take("probe") is None  # exhausted programs stay exhausted


@pytest.mark.parametrize("spec", [
    "heal,abort@decode",        # heal must be last
    "explode@decode",           # unknown action
    "abort@",                   # empty site
    "abort@decode:x",           # non-integer skip
    "abortdecode",              # missing @
])
def test_chaos_schedule_rejects_bad_specs(spec):
    with pytest.raises(ValueError):
        resilience.ChaosSchedule(spec)


def test_chaos_check_context_beats_env(monkeypatch):
    monkeypatch.setenv("TDT_CHAOS_SCHEDULE", "abort@decode")
    with resilience.chaos_schedule("heal"):
        resilience.chaos_check("decode")  # context program is empty: no-op
    assert not resilience.is_degraded("collectives")
    # A malformed env spec is logged and ignored, never raises.
    monkeypatch.setenv("TDT_CHAOS_SCHEDULE", "garbage")
    resilience.chaos_check("decode")
    assert not resilience.is_degraded("collectives")


def test_chaos_check_abort_marks_and_raises():
    with resilience.chaos_schedule("abort@prefill,heal"):
        with pytest.raises(resilience.CollectiveAbortError):
            resilience.chaos_check("prefill")
        resilience.chaos_check("prefill")  # program exhausted: clean
    assert resilience.is_degraded("collectives")
    assert telemetry.counter_value(
        "tdt_resilience_chaos_injected_total", site="prefill"
    ) == 1.0
    (ev,) = telemetry.events("chaos_inject")
    assert ev["site"] == "prefill" and ev["action"] == "abort"


def test_chaos_check_stall_wedges_caller_then_runs_clean(monkeypatch):
    """``stall`` wedges the CALLING thread for ``TDT_CHAOS_STALL_S`` while
    the process stays alive — the gray-failure shape the fleet progress
    watchdog detects. Nothing is marked degraded and no error is raised:
    from the inside, a wedged loop looks perfectly healthy."""
    monkeypatch.setenv("TDT_CHAOS_STALL_S", "0.05")
    with resilience.chaos_schedule("stall@decode,heal"):
        t0 = time.monotonic()
        resilience.chaos_check("decode")
        assert time.monotonic() - t0 >= 0.05
        resilience.chaos_check("decode")     # program exhausted: clean
    assert not resilience.is_degraded("collectives")
    assert telemetry.counter_value(
        "tdt_resilience_chaos_injected_total", site="decode") == 1.0


# ======================================== probe arc: degrade → restore


@pytest.mark.chaos
def test_chaos_probe_arc_restores_fused_backend(model1, refs, monkeypatch):
    """The ISSUE acceptance arc: fused serving → chaos abort on the second
    decode chunk → degraded-XLA recovery (zero loss/dup) → first half-open
    probe FAILS (scripted) and doubles the backoff → second probe succeeds
    → fused routing restored in-process, breaker CLOSED, all transitions
    visible in telemetry."""
    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    eng = make_engine(model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    streams: dict[int, list[int]] = {}
    with resilience.chaos_schedule("abort@decode:1,abort@probe,heal"):
        handles = [
            srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
                r.req_id, []).append(t))
            for p, g in REQUESTS
        ]
        srv.run()
        # The queue drained; keep stepping until the probe ladder converges
        # back onto the preferred backend (backoffs are 10–20ms here).
        deadline = time.monotonic() + 30.0
        while eng.backend != "dist_ar":
            assert time.monotonic() < deadline, "probe never restored fused"
            if not srv.step():
                time.sleep(0.005)

    # Zero token loss, zero duplication, byte-identical to the one-shot
    # greedy reference across the whole degrade/restore arc.
    for h, ref in zip(handles, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)

    assert eng.backend == "dist_ar"
    assert not resilience.any_degraded()
    # Breaker walked open → half_open → open (failed probe, backoff
    # doubled) → half_open → closed, and telemetry saw every transition.
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
        "tdt_resilience_probes_total", feature="collectives", outcome="failed"
    ) == 1.0
    assert telemetry.counter_value(
        "tdt_resilience_probes_total", feature="collectives", outcome="ok"
    ) == 1.0
    assert telemetry.counter_value(
        "tdt_serving_recoveries_total", from_backend="dist_ar"
    ) == 1.0
    assert telemetry.counter_value(
        "tdt_serving_restores_total", to_backend="dist_ar"
    ) == 1.0
    assert telemetry.counter_value(
        "tdt_resilience_chaos_injected_total", site="decode"
    ) == 1.0
    assert telemetry.counter_value(
        "tdt_resilience_chaos_injected_total", site="probe"
    ) == 1.0
    # The dashboard gauge ends healthy.
    (g,) = telemetry.snapshot()["gauges"]["tdt_degrade_state"]
    assert g["labels"] == {"feature": "collectives"} and g["value"] == 0.0
    # The failed probe left its event; both probes left server-trace spans.
    assert len(telemetry.events("serving_probe_failed")) == 1
    assert len(telemetry.events("serving_restore")) == 1


def _pool_state(srv):
    """What a probe must leave alone: the serving pool (the very arrays),
    the ledger's books, and the counter that says which decode ran."""
    return {
        "cache": srv.cache,
        "pool": (np.asarray(srv.cache.k), np.asarray(srv.cache.v)),
        "tables": np.asarray(srv.cache.tables),
        "ledger": srv.kv_ledger.stats(),
        "occupied": len(srv.scheduler.occupied_slots()),
        "pool_chunks": telemetry.counter_value(
            "tdt_engine_decode_chunks_total", path="pool"),
        "pool_bytes": {
            g["labels"]["kind"]: g["value"]
            for g in telemetry.snapshot()["gauges"]["tdt_kv_pool_bytes"]
        },
    }


def _assert_pool_untouched(before, after):
    assert after["cache"] is before["cache"]
    for got, want in zip(after["pool"], before["pool"]):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(after["tables"], before["tables"])
    assert after["ledger"] == before["ledger"]


def _degraded_server(model1, monkeypatch, refs):
    """A dist_ar server whose second decode chunk aborts, its requests
    submitted: (engine, server, handles, streams, xla references)."""
    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    eng = make_engine(model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    streams: dict[int, list[int]] = {}
    handles = [
        srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
            r.req_id, []).append(t))
        for p, g in REQUESTS
    ]
    return eng, srv, handles, streams, refs


@pytest.mark.chaos
def test_chaos_probe_runs_the_programs_that_serve(model1, refs, monkeypatch):
    """The probe's sandbox is a pool of its own driven through the join's and
    the chunk's programs: a passing probe counts one in-place decode chunk
    (``tdt_engine_decode_chunks_total{path="pool"}``) and, up to the moment
    the restore begins, leaves the serving pool's bytes, its tables and the
    ledger's books as they were — with tenants in their slots."""
    eng, srv, handles, streams, refs = _degraded_server(model1, monkeypatch, refs)
    before, after = {}, {}
    dispatch, restore = srv._probe_dispatch, srv._restore_streams

    def probe_dispatch():
        before.update(_pool_state(srv))
        dispatch()

    def restore_streams():
        after.update(_pool_state(srv))
        restore()

    monkeypatch.setattr(srv, "_probe_dispatch", probe_dispatch)
    monkeypatch.setattr(srv, "_restore_streams", restore_streams)
    with resilience.chaos_schedule("abort@decode:1,heal"):
        srv.run()
    assert eng.backend == "dist_ar" and not resilience.any_degraded()
    assert before["occupied"] > 0, "the probe found no tenant to disturb"
    _assert_pool_untouched(before, after)
    assert after["pool_chunks"] == before["pool_chunks"] + 1.0
    for h, ref in zip(handles, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)


@pytest.mark.chaos
def test_chaos_probe_fails_on_a_fault_in_the_paged_step(model1, refs, monkeypatch):
    """A fault in the decode chunk that serves, and in nothing else, fails
    the probe: the breaker re-opens, the engine goes back to xla, and the
    live streams end there byte for byte, their pool and its gauge as the
    probe found them."""
    eng, srv, handles, streams, refs = _degraded_server(model1, monkeypatch, refs)
    before, after = [], []
    dispatch = srv._probe_dispatch

    def boom(*_a, **_k):
        raise RuntimeError("planted in decode_chunk_paged")

    def faulty_probe_dispatch():
        before.append(_pool_state(srv))
        with monkeypatch.context() as m:
            # after the probe's rebuild: a rebuild makes the programs anew
            m.setattr(eng, "_decode_chunk_paged", boom)
            dispatch()

    monkeypatch.setattr(srv, "_probe_dispatch", faulty_probe_dispatch)
    maybe_probe = srv._maybe_probe

    def probe_then_look():
        n = len(before)
        worked = maybe_probe()
        if len(before) > n:
            after.append(_pool_state(srv))
        return worked

    monkeypatch.setattr(srv, "_maybe_probe", probe_then_look)
    with resilience.chaos_schedule("abort@decode:1,heal"):
        srv.run()
    assert before and before[0]["occupied"] > 0
    for b, a in zip(before, after):
        _assert_pool_untouched(b, a)
        assert a["pool_bytes"] == b["pool_bytes"]
        assert a["pool_chunks"] == b["pool_chunks"] + 1.0  # it got that far
    assert eng.backend == "xla" and resilience.is_degraded("collectives")
    failed = telemetry.events("serving_probe_failed")
    assert len(failed) == len(before)
    assert all("planted in decode_chunk_paged" in e["error"] for e in failed)
    assert telemetry.counter_value(
        "tdt_resilience_probes_total", feature="collectives", outcome="failed"
    ) == float(len(before))
    assert not telemetry.events("serving_restore")
    trans = [
        (e["from_state"], e["to_state"])
        for e in telemetry.events("breaker_transition")
        if e["feature"] == "collectives"
    ]
    assert trans[:3] == [
        ("closed", "open"), ("open", "half_open"), ("half_open", "open")]
    for h, ref in zip(handles, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)


@pytest.mark.chaos
def test_chaos_double_fault_recovery_stays_degraded(model1, refs, monkeypatch):
    """Double fault: the chunk abort's recovery re-prefill is ITSELF
    aborted (site 'recovery'). The bounded retry loop absorbs it on a
    fresh cache and — with probing disabled — the engine stays pinned on
    xla, still with zero token loss or duplication."""
    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0")  # sticky: no un-degrade
    eng = make_engine(model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    streams: dict[int, list[int]] = {}
    with resilience.chaos_schedule("abort@decode:1,abort@recovery,heal"):
        handles = [
            srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
                r.req_id, []).append(t))
            for p, g in REQUESTS
        ]
        srv.run()

    for h, ref in zip(handles, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)

    assert eng.backend == "xla"
    assert resilience.probe_due() == []  # probing disabled: stays sticky
    assert resilience.is_degraded("collectives")
    assert telemetry.counter_value("tdt_serving_recovery_retries_total") == 1.0
    assert telemetry.counter_value(
        "tdt_resilience_chaos_injected_total", site="recovery"
    ) == 1.0
    (retry,) = telemetry.events("serving_recovery_retry")
    assert retry["attempt"] == 1
    # One recovery total: the double fault retried INSIDE it, not a second
    # full recovery.
    assert telemetry.counter_value(
        "tdt_serving_recoveries_total", from_backend="dist_ar"
    ) == 1.0


# ==================================== rank-death arc: die → fence → revive


@pytest.mark.chaos
def test_chaos_rank_death_arc_fails_fast_and_recovers(model1, refs, monkeypatch):
    """The rank-loss acceptance arc: scripted ``die@1`` mid-decode kills a
    peer on the health board → the in-flight collective fails fast with
    ``dead_peer`` (NO bounded-wait timeout storm: zero aborts on the
    ledger) → the mesh epoch bumps → ONE recovery rebuilds the engine on
    the surviving configuration → scripted ``revive@1`` during recovery
    brings the rank back (second epoch bump) → probes restore the fused
    backend, and every stream is byte-identical to the one-shot
    reference."""
    from triton_dist_tpu.runtime import mesh

    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    eng = make_engine(model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    # Huge heartbeat so only the scripted die — never a wall-clock lease
    # expiry on a slow CI box — can kill a rank.
    board = mesh.init_health_board(world=2, heartbeat_s=1000.0)
    streams: dict[int, list[int]] = {}
    try:
        # skip=2 burns the two join prefills: the death lands MID-DECODE.
        with resilience.chaos_schedule("die@1:2,revive@1,heal"):
            handles = [
                srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
                    r.req_id, []).append(t))
                for p, g in REQUESTS
            ]
            srv.run()
            deadline = time.monotonic() + 30.0
            while eng.backend != "dist_ar":
                assert time.monotonic() < deadline, "probe never restored fused"
                if not srv.step():
                    time.sleep(0.005)

        for h, ref in zip(handles, refs):
            assert h.done
            np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
            assert streams[h.req_id] == list(h.tokens)

        # The mesh healed: rank 1 alive again, epoch fenced twice
        # (death + revival), nothing left degraded.
        assert board.alive(1)
        assert resilience.dead_ranks() == {}
        assert resilience.mesh_epoch() == 2
        assert eng.backend == "dist_ar"
        assert not resilience.any_degraded()

        # THE no-timeout-storm property: the dead peer was refused at the
        # dead_peer fail-fast gate, so the bounded-wait abort ledger — a
        # timeout per collective in a naive design — stayed EMPTY.
        assert telemetry.counter_total("tdt_resilience_aborts_total") == 0.0
        assert telemetry.counter_total(
            "tdt_resilience_dead_peer_failfast_total"
        ) >= 1.0
        assert telemetry.counter_value(
            "tdt_health_deaths_total", rank=1
        ) == 1.0
        assert telemetry.counter_value(
            "tdt_health_revivals_total", rank=1
        ) == 1.0
        # Exactly ONE recovery absorbed the death (no per-collective storm),
        # and one restore brought fused routing back.
        assert telemetry.counter_value(
            "tdt_serving_recoveries_total", from_backend="dist_ar"
        ) == 1.0
        assert telemetry.counter_value(
            "tdt_serving_restores_total", to_backend="dist_ar"
        ) == 1.0
        kinds = [e["kind"] for e in telemetry.events()]
        assert "rank_dead" in kinds and "rank_revived" in kinds
        assert kinds.count("mesh_epoch") == 2
    finally:
        mesh.reset_health_board()
