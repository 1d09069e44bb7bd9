"""SLO-guardrail tests: deadlines, cancellation, and overload shedding.

Scheduler-level tests are pure host (no jax); the server-level tests run
the same world=1 test-dense engine as ``test_serving.py`` — every
collective short-circuits to plain XLA, so only the generic-interpreter
fallback for the single-device Pallas kernels is needed.

The contract under test (see ``docs/resilience.md``):

* a request whose deadline cannot be met never spends a slot — rejected at
  submit (``shed_deadline``) or expired by the queue sweep;
* a burst beyond the EWMA-projected decode capacity sheds low-priority
  traffic BEFORE admission (``shed_overload``), priority 0 exempt, and
  /healthz turns not-ready for the shed window;
* ``cancel`` finalizes a queued request immediately and frees a running
  slot at the next chunk boundary; terminal requests are never
  re-finalized (no double-free).
"""

import os
import time

import jax
import numpy as np
import pytest

from triton_dist_tpu.runtime import introspect, resilience, telemetry
from triton_dist_tpu.serving import (
    InferenceServer,
    RequestState,
    Scheduler,
    SlotState,
)

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    introspect.set_health_provider(None)
    yield
    telemetry.reset()
    resilience.reset_degradation()
    introspect.set_health_provider(None)


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


# =================================================== deadlines (scheduler)


def test_nonpositive_deadline_sheds_at_submit():
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    r = sched.submit([1, 2], max_new=2, ttft_deadline_s=0.0)
    assert r.state is RequestState.REJECTED and r.reject_reason == "shed_deadline"
    r2 = sched.submit([1, 2], max_new=2, deadline_s=-1.0)
    assert r2.reject_reason == "shed_deadline"
    assert sched.queue_depth() == 0
    assert telemetry.counter_value(
        "tdt_serving_shed_total", reason="shed_deadline", priority=1
    ) == 2.0


def test_env_default_deadlines(monkeypatch):
    monkeypatch.setenv("TDT_DEADLINE_TTFT_S", "1.5")
    monkeypatch.setenv("TDT_DEADLINE_TOTAL_S", "9.0")
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    r = sched.submit([1, 2], max_new=2)
    assert r.ttft_deadline_s == 1.5 and r.deadline_s == 9.0
    # Explicit args override the env defaults.
    r2 = sched.submit([1, 2], max_new=2, ttft_deadline_s=0.25, deadline_s=2.0)
    assert r2.ttft_deadline_s == 0.25 and r2.deadline_s == 2.0


def test_queue_time_expiry_frees_nothing_and_fires_callbacks():
    """A queued request whose TTFT budget lapses before a slot frees is
    expired by the join sweep — even when NO slot is free — with the
    overrun recorded and on_finish fired exactly once."""
    finished = []
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    a = sched.submit([1, 2], max_new=4, now_s=0.0)
    (slot,) = sched.join_free_slots(now_s=0.0)
    assert slot.request is a  # occupies the only slot
    b = sched.submit(
        [3, 4], max_new=4, now_s=0.0, ttft_deadline_s=1.0,
        on_finish=lambda r: finished.append(r.req_id),
    )
    # Sweep with no free slot: b is past its budget and must not keep
    # waiting for capacity it can no longer use.
    assert sched.join_free_slots(now_s=2.5) == []
    assert b.state is RequestState.REJECTED
    assert b.reject_reason == "shed_deadline"
    assert finished == [b.req_id]
    assert sched.queue_depth() == 0
    assert telemetry.counter_value(
        "tdt_serving_deadline_expiries_total", where="queue"
    ) == 1.0
    (h,) = telemetry.snapshot()["histograms"]["tdt_serving_deadline_overrun_seconds"]
    assert h["count"] == 1 and abs(h["sum"] - 1.5) < 1e-9
    # A not-yet-arrived request can NOT expire: its clock has not started.
    c = sched.submit([5], max_new=2, arrival_time_s=10.0, now_s=0.0,
                     ttft_deadline_s=0.5)
    sched.join_free_slots(now_s=5.0)
    assert c.state is RequestState.QUEUED


# ==================================================== shedding (scheduler)


def test_overload_shed_priority_classes():
    sched = Scheduler(num_slots=1, max_len=MAX_LEN, shed_wait_s=0.05,
                      shed_priority=1)
    # Never shed blind: before any decode observation est_wait_s is None.
    assert sched.est_wait_s() is None
    a = sched.submit([1, 2], max_new=8, now_s=0.0)
    assert a.state is RequestState.QUEUED
    # 10 tokens/s EWMA, 8 tokens backlogged -> projected wait 0.8s >> 0.05s.
    sched.note_decode_rate(10, 1.0)
    assert sched.est_wait_s() == pytest.approx(0.8)
    low = sched.submit([3, 4], max_new=4, now_s=1.0, priority=1)
    assert low.state is RequestState.REJECTED
    assert low.reject_reason == "shed_overload"
    # Priority 0 rides through the same overload.
    vip = sched.submit([5, 6], max_new=4, now_s=1.0, priority=0)
    assert vip.state is RequestState.QUEUED
    assert telemetry.counter_value(
        "tdt_serving_shed_total", reason="shed_overload", priority=1
    ) == 1.0
    # /healthz signal: not-ready inside the shed window, ready after.
    assert sched.shedding(now_s=1.0 + sched.shed_health_s - 0.1)
    assert not sched.shedding(now_s=1.0 + sched.shed_health_s + 0.1)


def test_shed_against_request_ttft_budget():
    """With no global shed budget, the request's own TTFT deadline is the
    overload bound: a projected wait beyond it sheds at submit."""
    sched = Scheduler(num_slots=1, max_len=MAX_LEN, shed_wait_s=0.0)
    sched.submit([1, 2], max_new=8, now_s=0.0)
    sched.note_decode_rate(10, 1.0)  # projected wait now 0.8s
    r = sched.submit([3, 4], max_new=4, now_s=0.0, ttft_deadline_s=0.5)
    assert r.reject_reason == "shed_overload"
    # A budget the projection fits is admitted.
    ok = sched.submit([3, 4], max_new=4, now_s=0.0, ttft_deadline_s=5.0)
    assert ok.state is RequestState.QUEUED
    # No budget at all (and no global one): nothing to shed against.
    free = sched.submit([3, 4], max_new=4, now_s=0.0)
    assert free.state is RequestState.QUEUED


def test_healthz_not_ready_under_shed_pressure(model1):
    eng = make_engine(model1)
    srv = InferenceServer(eng, num_slots=1, chunk=2, shed_wait_s=0.01)
    code, body = introspect._healthz()
    assert code == 200 and body["status"] == "ok" and body["ready"]
    assert body["serving"]["backend"] == "xla"
    # Force a shed: prime the EWMA, backlog one queued request, submit.
    srv.submit([1, 2], max_new=8)
    srv.scheduler.note_decode_rate(1, 1.0)  # 1 token/s: any queue blows 10ms
    shed = srv.submit([3, 4], max_new=8)
    assert shed.reject_reason == "shed_overload"
    code, body = introspect._healthz()
    assert code == 503 and body["status"] == "shedding" and not body["ready"]
    assert body["serving"]["shedding"] is True
    assert body["degraded"] == {}  # shedding is not a breaker state


# ================================================ cancellation (scheduler)


def test_cancel_queued_finalizes_immediately():
    finished = []
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    r = sched.submit([1, 2], max_new=4,
                     on_finish=lambda q: finished.append(q.req_id))
    assert sched.cancel(r.req_id) is True
    assert r.state is RequestState.CANCELLED and r.finish_reason == "cancelled"
    assert sched.queue_depth() == 0 and finished == [r.req_id]
    assert telemetry.counter_value(
        "tdt_serving_cancelled_total", where="queued"
    ) == 1.0
    # Terminal: a second cancel is refused, callbacks do not re-fire.
    assert sched.cancel(r.req_id) is False
    assert finished == [r.req_id]
    # The sweep never resurrects it.
    assert sched.join_free_slots(now_s=0.0) == []


def test_cancel_running_flags_only():
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    r = sched.submit([1, 2], max_new=4)
    (slot,) = sched.join_free_slots(now_s=0.0)
    assert sched.cancel(r.req_id) is True
    assert r.cancel_requested and r.state is RequestState.RUNNING
    assert slot.state is SlotState.PREFILL  # the scheduler does NOT free it
    assert sched.cancel(r.req_id) is True  # idempotent while running
    assert len(telemetry.events("serving_cancel")) == 1  # flagged once
    # Unknown ids are refused.
    assert sched.cancel(10_000) is False


def test_cancel_race_with_sweep_cannot_double_free():
    """cancel() finalizing a queued request concurrently with the join
    sweep: the sweep must skip the CANCELLED tombstone, not admit it."""
    sched = Scheduler(num_slots=2, max_len=MAX_LEN)
    a = sched.submit([1], max_new=2)
    b = sched.submit([2], max_new=2)
    assert sched.cancel(a.req_id)
    (slot,) = sched.join_free_slots(now_s=0.0)
    assert slot.request is b  # a's tombstone was skipped, order held
    assert a.state is RequestState.CANCELLED


# ======================================= satellite: scheduler edge cases


def test_queue_full_rejects_even_with_free_slots():
    """The queue bound is an admission bound, not a capacity bound: slots
    only fill at the join sweep, so a bounded queue can reject while every
    slot is FREE."""
    sched = Scheduler(num_slots=4, max_len=MAX_LEN, queue_limit=1)
    assert all(s.state is SlotState.FREE for s in sched.slots)
    a = sched.submit([1], max_new=2)
    b = sched.submit([2], max_new=2)
    assert a.state is RequestState.QUEUED
    assert b.state is RequestState.REJECTED and b.reject_reason == "queue_full"
    # After the sweep drains the queue, admission reopens.
    sched.join_free_slots(now_s=0.0)
    c = sched.submit([3], max_new=2)
    assert c.state is RequestState.QUEUED


def test_fcfs_preserved_across_deferrals_and_expiries():
    """One sweep mixing a future arrival, an expired request, an admit, and
    a no-capacity deferral must keep strict submission order in the queue
    — expiry and deferral must not reorder anything."""
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    future = sched.submit([1], max_new=2, arrival_time_s=5.0, now_s=0.0)
    doomed = sched.submit([2], max_new=2, now_s=0.0, ttft_deadline_s=0.5)
    a = sched.submit([3], max_new=2, now_s=0.0)
    b = sched.submit([4], max_new=2, now_s=0.0)
    (slot,) = sched.join_free_slots(now_s=1.0)
    assert slot.request is a  # first *eligible* submitter wins
    assert doomed.reject_reason == "shed_deadline"
    assert future.state is RequestState.QUEUED
    assert b.state is RequestState.QUEUED
    assert sched.queue_depth() == 2
    # Free the slot past `future`'s arrival: submission order (future came
    # first) decides, not eligibility order.
    sched.start_decode(slot)
    sched.finish(slot)
    sched.release(slot)
    (s2,) = sched.join_free_slots(now_s=6.0)
    assert s2.request is future
    sched.finish(s2)
    sched.release(s2)
    (s3,) = sched.join_free_slots(now_s=6.0)
    assert s3.request is b


# ===================================================== server-level SLOs


def test_mid_decode_cancel_frees_slot_within_one_chunk(model1):
    eng = make_engine(model1)
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    finished = []
    r = srv.submit([3, 17, 42], max_new=12,
                   on_finish=lambda q: finished.append(q.finish_reason))
    other = srv.submit([8, 1], max_new=4)
    assert srv.step()  # join + prefill + one decode chunk
    assert r.state is RequestState.RUNNING and len(r.tokens) >= 1
    n_before = len(r.tokens)
    assert srv.cancel(r.req_id) is True
    srv.step()  # the next chunk boundary reaps it BEFORE decoding
    assert r.state is RequestState.CANCELLED and r.finish_reason == "cancelled"
    assert len(r.tokens) == n_before  # nothing streamed after the cancel
    assert finished == ["cancelled"]
    assert telemetry.counter_value(
        "tdt_serving_cancelled_total", where="running"
    ) == 1.0
    # The slot is genuinely free: a double cancel is refused and the other
    # stream (and a new tenant) drain normally through the freed capacity.
    assert srv.cancel(r.req_id) is False
    late = srv.submit([5, 5, 5], max_new=3)
    srv.run()
    assert other.done and len(other.tokens) == 4
    assert late.done and len(late.tokens) == 3
    assert srv.scheduler.occupancy() == 0
    # Cancelled streams do not count as completions.
    assert telemetry.counter_value("tdt_serving_requests_completed_total") == 2.0


def test_mid_decode_deadline_truncates_with_distinct_reason(model1, monkeypatch):
    eng = make_engine(model1)
    srv = InferenceServer(eng, num_slots=1, chunk=1)
    # The server's clock is the test's: what a loaded machine takes for a
    # submit, a join and a chunk (compiles included) is none of the budget's.
    clock = [0.0]
    monkeypatch.setattr(srv, "_now", lambda: clock[0])
    r = srv.submit([3, 17, 42], max_new=20, deadline_s=0.3)
    assert srv.step()
    assert r.state is RequestState.RUNNING
    clock[0] = 0.35  # blow the total budget mid-decode
    srv.step()  # reaped at the chunk boundary
    assert r.state is RequestState.DONE and r.finish_reason == "deadline"
    assert 0 < len(r.tokens) < 20  # truncated, not completed or dropped
    assert srv.scheduler.occupancy() == 0
    assert telemetry.counter_value(
        "tdt_serving_deadline_expiries_total", where="decode"
    ) == 1.0
    # A truncated stream is no completion.
    assert telemetry.counter_value("tdt_serving_requests_completed_total") == 0.0
    (overrun,) = telemetry.snapshot()["histograms"]["tdt_serving_deadline_overrun_seconds"]
    assert overrun["count"] == 1 and overrun["sum"] == pytest.approx(0.05)


# ================================================== live SLO engine (PR 18)


def test_record_finish_classifies_against_own_deadlines():
    """Pure-host outcome accounting: each request is judged by ITS OWN
    deadline fields; outcomes land in goodput/violation counters and
    per-(tenant, tier) latency digests."""
    from triton_dist_tpu.runtime import slo

    class R:
        def __init__(self, **kw):
            self.tenant = kw.get("tenant", "default")
            self.priority = kw.get("priority", 1)
            self.ttft_deadline_s = kw.get("ttft_deadline_s")
            self.deadline_s = kw.get("deadline_s")
            self.arrived_at = kw.get("arrived_at", 0.0)
            self.finished_at = kw.get("finished_at", 1.0)
            self.ttft_s = kw.get("ttft_s", 0.1)
            self.tpot_s = kw.get("tpot_s", 0.01)

    # No deadline = the SLO is trivially met.
    assert slo.record_finish(R(tenant="a"), "ok") == "met"
    # Met its explicit budgets.
    assert slo.record_finish(
        R(tenant="a", ttft_deadline_s=0.5, deadline_s=2.0), "ok") == "met"
    # Blew the TTFT budget (checked before the e2e one).
    assert slo.record_finish(
        R(tenant="a", ttft_s=0.9, ttft_deadline_s=0.5, deadline_s=0.5),
        "ok") == "ttft_deadline"
    # Blew the total budget.
    assert slo.record_finish(
        R(tenant="a", finished_at=3.0, deadline_s=2.0), "ok") == "deadline"
    # A non-ok finish IS the violation reason (mid-decode truncation).
    assert slo.record_finish(R(tenant="b"), "deadline") == "deadline"
    # Cancels spend no error budget in either direction.
    assert slo.record_finish(R(tenant="b"), "cancelled") is None

    assert telemetry.counter_value(
        "tdt_slo_goodput_total", tenant="a", tier="1") == 2.0
    assert telemetry.counter_value(
        "tdt_slo_violations_total", tenant="a", tier="1",
        reason="ttft_deadline") == 1.0
    assert telemetry.counter_value(
        "tdt_slo_violations_total", tenant="b", tier="1",
        reason="deadline") == 1.0
    # Latency digests are per-(tenant, tier); cancels recorded nothing
    # (tenant b saw one non-cancel finish).
    assert telemetry.digest_merged("tdt_slo_ttft_seconds").n == 5
    s = slo.slo_summary()
    assert s["tenants"]["a"]["goodput_frac"] == pytest.approx(0.5)
    assert "1" in s["tenants"]["a"]["tiers"]
    assert s["tenants"]["a"]["tiers"]["1"]["ttft"]["count"] == 4


def test_record_reject_counts_only_capacity_violations():
    from triton_dist_tpu.runtime import slo

    class R:
        tenant, priority = "agg", 2

    assert slo.record_reject(R(), "queue_full") == "queue_full"
    assert slo.record_reject(R(), "shed_overload") == "shed_overload"
    # Client-fixable rejects are neither goodput nor violations.
    assert slo.record_reject(R(), "empty") is None
    assert slo.record_reject(R(), "kv_budget") is None
    assert telemetry.counter_total("tdt_slo_violations_total") == 2.0


def test_burn_rate_monitor_fire_clear_hysteresis():
    """The multi-window state machine under a pinned clock: a burst fires
    exactly once (both windows hot, min_events met), stays firing while
    the fast window is hot, and clears exactly once when it drains —
    sustained healthy traffic never fires."""
    from triton_dist_tpu.runtime import slo

    mon = slo.BurnRateMonitor(
        "agg", objective=0.99, fast_window_s=10.0, slow_window_s=60.0,
        fast_burn=14.0, slow_burn=6.0, clear_burn=1.0, min_events=5,
    )
    # Healthy traffic: burn 0, never fires.
    for i in range(20):
        mon.record(True, float(i) * 0.1)
    assert mon.tick(2.0) is None and not mon.firing

    # Burst: 10 violations inside the fast window.
    for i in range(10):
        mon.record(False, 3.0 + i * 0.1)
    assert mon.tick(4.0) == "fire"
    fast, slow = mon.burn_rates(4.0)
    assert fast >= 14.0 and slow >= 6.0
    # Still hot: no second fire (hysteresis — one burst, one alert).
    assert mon.tick(5.0) is None and mon.firing

    # The fast window drains past the burst: exactly one clear.
    assert mon.tick(15.0) == "clear"
    assert mon.tick(16.0) is None and not mon.firing
    assert (mon.fires, mon.clears) == (1, 1)

    # Sub-threshold background errors (1% at a 99% objective = burn 1.0)
    # never fire: that is the budget, not an incident.
    mon2 = slo.BurnRateMonitor(
        "bg", objective=0.9, fast_window_s=10.0, slow_window_s=10.0,
        fast_burn=14.0, slow_burn=6.0, min_events=5,
    )
    for i in range(100):
        mon2.record(i % 10 != 0, 5.0)   # 10% bad = burn 1.0 exactly
    assert mon2.tick(5.0) is None and not mon2.firing


def test_server_finish_feeds_slo_engine_and_slo_route(model1):
    """End-to-end on a live server: finishes land in per-tenant digests
    and goodput counters (tiered by priority), a mid-decode deadline
    truncation lands as that tenant's violation, and the /slo introspect
    route serves the rollup plus the engine's step-phase digests."""
    eng = make_engine(model1)
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    warm = srv.submit([3, 17, 42], max_new=2)
    srv.run()
    assert warm.done

    a = srv.submit([1, 2, 3], max_new=4, tenant="vip", priority=0,
                   deadline_s=60.0)
    b = srv.submit([4, 5], max_new=3, tenant="batch", priority=2)
    srv.run()
    assert a.finish_reason == "ok" and b.finish_reason == "ok"
    assert telemetry.counter_value(
        "tdt_slo_goodput_total", tenant="vip", tier="0") == 1.0
    assert telemetry.counter_value(
        "tdt_slo_goodput_total", tenant="batch", tier="2") == 1.0
    assert telemetry.digest_quantile(
        "tdt_slo_ttft_seconds", 0.5, tenant="vip", tier="0") is not None

    # Blow a budget mid-decode: the truncation is vip's violation.
    r = srv.submit([3, 17, 42], max_new=20, deadline_s=0.3, tenant="vip",
                   priority=0)
    srv.step()
    time.sleep(0.35)
    srv.step()
    assert r.finish_reason == "deadline"
    assert telemetry.counter_value(
        "tdt_slo_violations_total", tenant="vip", tier="0",
        reason="deadline") == 1.0

    code, payload = srv._r_slo("GET", "", None)
    assert code == 200
    vip = payload["tenants"]["vip"]
    assert vip["goodput"] == 1.0 and vip["violations"] == 1.0
    assert vip["goodput_frac"] == pytest.approx(0.5)
    assert vip["tiers"]["0"]["ttft"]["count"] >= 1
    assert "p99" in vip["tiers"]["0"]["ttft"]
    # Step-phase digests: the serve loop stamped admission/dispatch/
    # host_sync for this (xla) backend.
    phases = payload["phases"]["xla"]
    for phase in ("admission", "dispatch", "host_sync"):
        assert phases[phase]["count"] > 0, phases.keys()
    assert payload["alpha"] == telemetry.DIGEST_ALPHA

    # The route is live on the introspection registry and unmounts at
    # shutdown.
    entry, _ = introspect._resolve_route("/slo")
    assert entry is not None
    srv.shutdown()
    entry, _ = introspect._resolve_route("/slo")
    assert entry is None


def test_slo_sites_are_noops_when_telemetry_disabled(model1):
    """TDT_TELEMETRY=0 contract: every SLO instrumentation site reduces to
    the cached-bool early return — zero registry writes, no burn-rate
    events, and the engine's phase fences never run."""
    from triton_dist_tpu.runtime import slo

    telemetry.reset(enabled_override=False)
    try:
        eng = make_engine(model1)
        srv = InferenceServer(eng, num_slots=1, chunk=2)
        r = srv.submit([1, 2, 3], max_new=3, tenant="vip", deadline_s=60.0)
        srv.run()
        assert r.done

        class R:
            tenant, priority = "x", 1
            ttft_deadline_s = deadline_s = None
            arrived_at, finished_at = 0.0, 1.0
            ttft_s, tpot_s = 0.1, 0.01

        assert slo.record_finish(R(), "ok") is None
        assert slo.record_reject(R(), "queue_full") is None
        snap = telemetry.snapshot()
        assert snap["counters"] == {} and snap["digests"] == {}
        srv.shutdown()
    finally:
        telemetry.reset()
