"""Serving-layer tests: scheduler admission/join semantics, the masked
KV/decode primitives, and the continuous-batching acceptance bar —
``InferenceServer`` over staggered requests must produce byte-identical
greedy tokens to per-request one-shot ``Engine.serve``.

Everything here runs on CPU with world=1 (``tp`` axis of size 1): every
collective kernel short-circuits ``world == 1`` to the plain XLA path, so
no TPU interpret machinery is needed — only the generic-interpreter
fallback for the single-device Pallas kernels (flash-attn/-decode), same
as the serve-path telemetry tests.

The ``chaos``-marked test injects a ``CollectiveAbortError`` mid-serving
and asserts the degraded-mode contract: the engine rebuilds on ``xla``
WITHOUT dropping the queue, and every stream completes with zero dropped
and zero duplicated tokens.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.runtime import resilience, telemetry
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
    yield
    telemetry.reset()
    resilience.reset_degradation()


@pytest.fixture(scope="module")
def model1():
    """world=1 test-dense model: serving semantics don't need parallelism,
    and every collective kernel short-circuits world==1 to plain XLA."""
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


# ================================================== scheduler (pure host)


def test_admission_rejects():
    sched = Scheduler(num_slots=2, max_len=MAX_LEN, queue_limit=2)
    # KV budget: the whole generation must fit one max_len slot row.
    r = sched.submit([1] * 20, max_new=20)
    assert r.state is RequestState.REJECTED and r.reject_reason == "kv_budget"
    # Degenerate requests.
    assert sched.submit([], max_new=4).reject_reason == "empty"
    assert sched.submit([1, 2], max_new=0).reject_reason == "empty"
    # Bounded queue.
    a = sched.submit([1, 2, 3], max_new=4)
    b = sched.submit([4, 5], max_new=4)
    c = sched.submit([6], max_new=4)
    assert a.state is RequestState.QUEUED and b.state is RequestState.QUEUED
    assert c.state is RequestState.REJECTED and c.reject_reason == "queue_full"
    # Rejected requests are NOT queued; counters carry the reason label.
    assert sched.queue_depth() == 2
    assert telemetry.counter_value("tdt_serving_requests_total") == 6.0
    for reason, n in (("kv_budget", 1.0), ("empty", 2.0), ("queue_full", 1.0)):
        assert (
            telemetry.counter_value(
                "tdt_serving_admission_rejects_total", reason=reason
            )
            == n
        )
    # An admissible boundary case: prompt + max_new == max_len.
    ok = Scheduler(num_slots=1, max_len=MAX_LEN).submit([1] * 28, max_new=4)
    assert ok.state is RequestState.QUEUED


def test_queue_wait_histogram_records_arrival_to_admission():
    """Queue delay is its own histogram (TTFT no longer has to conflate
    queueing with prefill): wait = join time - effective arrival."""
    sched = Scheduler(num_slots=1, max_len=MAX_LEN)
    a = sched.submit([1, 2], max_new=2, arrival_time_s=0.0, now_s=0.0)
    b = sched.submit([3, 4], max_new=2, arrival_time_s=1.0, now_s=0.0)
    # a joins at t=0.5 after waiting 0.5s; b hasn't arrived yet.
    (s,) = sched.join_free_slots(now_s=0.5)
    assert s.request is a
    snap = telemetry.snapshot()["histograms"]["tdt_serving_queue_wait_seconds"]
    assert snap[0]["count"] == 1
    assert abs(snap[0]["sum"] - 0.5) < 1e-9
    # b joins at t=3.0 after "arriving" at t=1.0: wait is 2.0s, measured
    # from the synthetic arrival, not from submit.
    sched.finish(s)
    sched.release(s)
    (s2,) = sched.join_free_slots(now_s=3.0)
    assert s2.request is b
    snap = telemetry.snapshot()["histograms"]["tdt_serving_queue_wait_seconds"]
    assert snap[0]["count"] == 2
    assert abs(snap[0]["sum"] - 2.5) < 1e-9


def test_fcfs_join_evict_ordering():
    sched = Scheduler(num_slots=2, max_len=MAX_LEN)
    reqs = [sched.submit([1, 2], max_new=3) for _ in range(4)]
    joined = sched.join_free_slots(now_s=0.0)
    # FCFS into the lowest-indexed free slots.
    assert [s.idx for s in joined] == [0, 1]
    assert [s.request for s in joined] == reqs[:2]
    assert all(s.state is SlotState.PREFILL for s in joined)
    assert sched.queue_depth() == 2
    assert sched.join_free_slots(now_s=0.0) == []  # no free slot
    # Evict slot 1 first: the NEXT queued request lands there.
    sched.start_decode(joined[1])
    sched.finish(joined[1])
    assert sched.release(joined[1]) is reqs[1]
    (s1,) = sched.join_free_slots(now_s=0.0)
    assert s1.idx == 1 and s1.request is reqs[2]
    # State machine is enforced.
    with pytest.raises(AssertionError):
        sched.release(joined[0])  # PREFILL, not DONE
    sched.start_decode(joined[0])
    with pytest.raises(AssertionError):
        sched.start_decode(joined[0])  # DECODE, not PREFILL


def test_arrival_time_deferral_keeps_order():
    sched = Scheduler(num_slots=2, max_len=MAX_LEN)
    late = sched.submit([1], max_new=2, arrival_time_s=5.0, now_s=0.0)
    early = sched.submit([2], max_new=2, arrival_time_s=0.0, now_s=0.0)
    # The future arrival defers WITHOUT blocking the one behind it.
    (s,) = sched.join_free_slots(now_s=0.0)
    assert s.request is early
    assert sched.queue_depth() == 1
    assert sched.next_arrival_s() == 5.0
    # Once its arrival passes, the deferred request joins (front of queue).
    (s2,) = sched.join_free_slots(now_s=6.0)
    assert s2.request is late
    assert late.arrived_at == 5.0  # effective arrival, not submit time


def _gauge(snap, name):
    (entry,) = snap["gauges"][name]
    return entry["value"]


def test_slot_occupancy_gauges():
    sched = Scheduler(num_slots=2, max_len=MAX_LEN)
    sched.submit([1], max_new=2)
    sched.submit([2], max_new=2)
    assert _gauge(telemetry.snapshot(), "tdt_serving_queue_depth") == 2.0
    (s, s2) = sched.join_free_slots(now_s=0.0)
    snap = telemetry.snapshot()
    assert _gauge(snap, "tdt_serving_queue_depth") == 0.0
    assert _gauge(snap, "tdt_serving_slot_occupancy") == 2.0
    for slot in (s, s2):
        sched.start_decode(slot)
        sched.finish(slot)
        sched.release(slot)
    assert _gauge(telemetry.snapshot(), "tdt_serving_slot_occupancy") == 0.0


# ========================================================== KVCache mask


def test_inc_offset_active_mask():
    cache = KVCache(
        k=jnp.zeros((1, 3, 1, 8, 2)),
        v=jnp.zeros((1, 3, 1, 8, 2)),
        lengths=jnp.asarray([3, 5, 0], jnp.int32),
    )
    # Legacy unmasked behavior is unchanged.
    np.testing.assert_array_equal(np.asarray(cache.inc_offset().lengths), [4, 6, 1])
    # Masked: only active slots advance — a finished/padded slot must not
    # grow past its real content (slot-reuse prerequisite).
    act = jnp.asarray([True, False, True])
    np.testing.assert_array_equal(
        np.asarray(cache.inc_offset(active=act).lengths), [4, 5, 1]
    )
    np.testing.assert_array_equal(
        np.asarray(cache.inc_offset(2, active=jnp.asarray([0, 1, 0])).lengths),
        [3, 7, 0],
    )
    assert cache.inc_offset(active=act).lengths.dtype == jnp.int32


# ================================================= engine step programs


def test_pad_path_is_single_program(model1):
    eng = make_engine(model1)
    # The per-pad-size concat-lambda dict is gone; padding is ONE jitted
    # dynamic_update_slice whose shape cache keys off the prefill length.
    assert not hasattr(eng, "_pad_fns")
    ids = jnp.asarray([[3, 17, 42, 7, 99]], jnp.int32)
    _, ks, vs = eng._prefill(eng.model.params, ids)
    cache = eng._make_cache(ks, vs, 5)
    assert cache.k.shape[3] == MAX_LEN
    np.testing.assert_array_equal(np.asarray(cache.lengths), [5])
    # Tail beyond the prefill content is zero-initialized.
    assert float(jnp.abs(cache.k[:, :, :, 5:]).sum()) == 0.0
    assert float(jnp.abs(cache.v[:, :, :, 5:]).sum()) == 0.0


def test_paged_join_and_masked_decode(model1):
    """Ragged masks through the programs that serve: joins of two lengths
    into a three-slot pool, then one masked chunk whose streams are
    ``Engine.serve``'s."""
    from paged_drive import alloc_chains, join

    eng = make_engine(model1)
    prompts = {0: [3, 17, 42, 7, 99], 2: [8, 1, 13]}
    paged = alloc_chains(eng, 3)
    t0a, paged = join(eng, paged, 0, prompts[0])
    t0c, paged = join(eng, paged, 2, prompts[2])
    np.testing.assert_array_equal(np.asarray(paged.lengths), [5, 0, 3])
    # Masked chunk: slot 1 is empty (inactive), slot 0 runs dry mid-chunk.
    remaining = jnp.asarray([2, 0, 3], jnp.int32)
    tokens = jnp.asarray([t0a, 0, t0c], jnp.int32)
    out, last, paged, rem = eng.decode_steps_paged(paged, tokens, remaining, chunk=3)
    out = np.asarray(out)
    assert out.shape == (3, 3)
    # Inactive slots emit -1 sentinels; lengths freeze for them.
    assert (out[1] == -1).all()
    assert out[0, 2] == -1
    np.testing.assert_array_equal(np.asarray(paged.lengths), [7, 0, 6])
    np.testing.assert_array_equal(np.asarray(rem), [0, 0, 0])
    for slot, t0, n in ((0, t0a, 2), (2, t0c, 3)):
        ref = np.asarray(
            eng.serve(jnp.asarray([prompts[slot]], jnp.int32), gen_len=n + 1))[0]
        np.testing.assert_array_equal([t0, *out[slot, :n]], ref)


# ======================================== acceptance: server vs one-shot

# Mixed prompt/gen lengths; ≥8 requests; arrivals land mid-decode.
REQUESTS = [
    ([3, 17, 42, 7, 99], 6),
    ([8, 1, 13], 4),
    ([5, 5, 5, 5, 5, 5, 5, 5], 3),
    ([100, 200, 30], 5),
    ([7, 7, 7, 7], 1),  # single-token generation: finishes at join
    ([91, 12, 55, 2, 8, 41], 4),
    ([3, 3], 6),
    ([111, 4, 9, 16, 25, 36, 49], 3),
]


def _references(eng):
    return [
        np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0]
        for p, g in REQUESTS
    ]


def test_server_parity_staggered(model1):
    eng = make_engine(model1)
    refs = _references(eng)

    srv = InferenceServer(eng, num_slots=3, chunk=2)
    streams: dict[int, list[int]] = {}
    finished: list[int] = []

    def on_token(req, token, index):
        streams.setdefault(req.req_id, []).append(token)
        assert index == len(streams[req.req_id]) - 1

    def on_finish(req):
        finished.append(req.req_id)

    # First wave: more requests than slots, so one queues behind the batch.
    handles = [
        srv.submit(p, g, on_token=on_token, on_finish=on_finish)
        for p, g in REQUESTS[:4]
    ]
    assert srv.step()  # joins 3, runs one decode chunk
    # The shortest tenant may already have finished its chunk, but the batch
    # is still mid-flight with a request queued behind it.
    assert srv.scheduler.occupancy() >= 2
    assert srv.step()
    # Second wave arrives MID-decode (in-flight slots still generating).
    assert any(h.state is RequestState.RUNNING and not h.done for h in handles[:3])
    handles += [
        srv.submit(p, g, on_token=on_token, on_finish=on_finish)
        for p, g in REQUESTS[4:]
    ]
    srv.run()

    assert srv.scheduler.occupancy() == 0 and srv.scheduler.queue_depth() == 0
    assert len(finished) == len(REQUESTS)
    for h, (prompt, gen), ref in zip(handles, REQUESTS, refs):
        assert h.done
        # Byte-identical greedy tokens vs one-shot serve, both as the
        # request handle's history and as the streamed callback sequence.
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)
        assert len(h.tokens) == gen
        assert h.ttft_s is not None and h.ttft_s >= 0.0
        if gen > 1:
            assert h.tpot_s is not None and h.tpot_s >= 0.0

    snap = telemetry.snapshot()
    assert telemetry.counter_value("tdt_serving_requests_total") == float(len(REQUESTS))
    assert telemetry.counter_value("tdt_serving_requests_completed_total") == float(len(REQUESTS))
    assert telemetry.counter_value("tdt_serving_decode_chunks_total") > 0
    assert telemetry.counter_value("tdt_serving_tokens_total") == float(
        sum(g for _, g in REQUESTS) - len(REQUESTS)  # token0s come from prefill
    )
    hist_names = set()
    for name, entries in snap["histograms"].items():
        if entries:
            hist_names.add(name)
    assert "tdt_serving_ttft_seconds" in hist_names
    assert "tdt_serving_tpot_seconds" in hist_names


def test_server_synthetic_arrivals(model1):
    """Offered-load staggering: future arrival_time_s defers joins but the
    run loop drains everything, and TTFT is measured from effective arrival."""
    eng = make_engine(model1)
    refs = _references(eng)
    srv = InferenceServer(eng, num_slots=2, chunk=3)
    handles = [
        srv.submit(p, g, arrival_time_s=i * 0.02)
        for i, (p, g) in enumerate(REQUESTS)
    ]
    srv.run()
    for h, ref in zip(handles, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)


# ================================================== satellite: serve fix


def test_serve_profile_dir_counts_once(model1, tmp_path):
    eng = make_engine(model1)
    ids = jnp.asarray([[3, 17, 42, 7, 99]], jnp.int32)
    plain = np.asarray(eng.serve(ids, gen_len=4))
    assert telemetry.counter_value("tdt_engine_serve_total", backend="xla") == 1.0
    profiled = np.asarray(eng.serve(ids, gen_len=4, profile_dir=str(tmp_path)))
    # The profiled path used to re-enter serve(): double-counted serves and
    # nested a second watchdog inside the capture. Now: exactly once each.
    assert telemetry.counter_value("tdt_engine_serve_total", backend="xla") == 2.0
    np.testing.assert_array_equal(profiled, plain)
    assert any(tmp_path.iterdir())  # the capture actually wrote something


# ============================================================== chaos


@pytest.mark.chaos
def test_chaos_abort_midserving_no_token_loss(model1):
    """A collective abort mid-serving degrades the engine to xla WITHOUT
    dropping the queue: every in-flight slot re-prefills from its token
    history and every stream completes with zero dropped or duplicated
    tokens (byte-identical to the greedy one-shot reference)."""
    ref_eng = make_engine(model1, backend="xla")
    refs = _references(ref_eng)

    eng = make_engine(model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)

    # Inject: the SECOND decode chunk aborts the way a bounded-wait
    # collective does (sticky degradation + CollectiveAbortError). The
    # recovery rebuild replaces eng._decode_chunk_paged, removing the hook.
    orig = eng._decode_chunk_paged
    calls = {"n": 0}

    def boom(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            resilience.mark_degraded("collectives", "injected abort (test)")
            raise resilience.CollectiveAbortError("injected abort (test)")
        return orig(*args, **kwargs)

    eng._decode_chunk_paged = boom

    streams: dict[int, list[int]] = {}
    handles = [
        srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(r.req_id, []).append(t))
        for p, g in REQUESTS[:4]
    ]
    srv.run()

    assert calls["n"] == 2  # the hook fired and was removed by the rebuild
    assert eng.backend == "xla"
    assert (
        telemetry.counter_value("tdt_serving_recoveries_total", from_backend="dist_ar")
        == 1.0
    )
    assert telemetry.counter_value("tdt_serving_preemptions_total") >= 1.0
    assert [e["from_backend"] for e in telemetry.events("serving_recovery")] == ["dist_ar"]
    for h, ref in zip(handles, refs[:4]):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)  # zero drops, zero dups


# ================================ the loop's spans, counters and self times


@pytest.fixture(scope="module")
def traced_serve(model1, tmp_path_factory):
    """ONE toy serve under a profiler session, after a warm-up serve of the
    same requests: the tokens, the program's counters and self-time digest
    as the difference of two snapshots round it, and the ``tdt_*`` events
    the profiler wrote on the host line, as (name, start_ns, end_ns)."""
    import glob

    from triton_dist_tpu.runtime import tracing

    telemetry.reset()
    tracing.reset()
    eng = make_engine(model1)
    refs = _references(eng)
    warm = InferenceServer(eng, num_slots=3, chunk=2)
    warm_handles = [warm.submit(p, g) for p, g in REQUESTS]
    warm.run()

    srv = InferenceServer(eng, num_slots=3, chunk=2)
    before = telemetry.snapshot()
    log_dir = tmp_path_factory.mktemp("serve_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        handles = [srv.submit(p, g) for p, g in REQUESTS]
        srv.run()
    finally:
        jax.profiler.stop_trace()
    after = telemetry.snapshot()

    (path,) = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines for e in line.events if e.name.startswith("tdt_")
    ]

    def counters(snap):
        return {n: sum(e["value"] for e in es) for n, es in snap["counters"].items()}

    def self_s(snap, field="sum"):
        return {e["labels"]["phase"]: e[field]
                for e in snap["digests"].get("tdt_span_self_seconds", [])}

    def chunks(snap):
        return sum(e["sum"] for e in snap["histograms"]["tdt_serving_prefill_chunks"])

    c0, c1, s0, s1 = counters(before), counters(after), self_s(before), self_s(after)
    n0, n1 = self_s(before, "count"), self_s(after, "count")
    telemetry.reset()
    tracing.reset()
    return {
        "tokens": [list(h.tokens) for h in handles], "refs": refs,
        "warm_tokens": [list(h.tokens) for h in warm_handles],
        "counters": {n: v - c0.get(n, 0.0) for n, v in c1.items()},
        "self_s": {n: v - s0.get(n, 0.0) for n, v in s1.items()},
        "self_n": {n: v - n0.get(n, 0) for n, v in n1.items()},
        "prefill_chunks": chunks(after) - chunks(before),
        "events": sorted(events, key=lambda e: (e[1], -e[2])),
    }


def _own_times(events):
    """[(name, start, end, own_ns, outermost ancestor's start)]
    by the nesting of the intervals, as a profile viewer would draw them."""
    out, stack = [], []
    for name, a, b in events:
        while stack and stack[-1][2] <= a:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= b - a
        stack.append([name, a, b, b - a, stack[0][1] if stack else None])
    out.extend(tuple(s) for s in reversed(stack))
    return out


def test_loop_phases_and_engine_spans_add_up_to_each_step(traced_serve):
    """Every ``tdt_serving_step`` in the profiler's trace is covered by the
    spans beneath it: the self time of the loop's own spans (the
    iteration's glue included) plus the durations of the calls it makes
    into the scheduler and the engine are the iteration, to the nanosecond
    where the nesting is sound, and the glue no span names is under 2 % of
    the iterations' time. Every span the profiler saw fed the program's
    digest exactly once. The digest's seconds, on the program's own clock,
    agree with the profiler's nesting within 5 % + 2 ms over all phases
    together and within 25 % + 1 ms a phase: a span's interval opens before
    its annotation and closes after it, so a pause of the host between the
    two (this sandbox's other workers, the collector) lands in one clock's
    phase and the other's parent. What self time is to the microsecond,
    ``tests/test_tracing.py`` holds on a hand-made nest."""
    own = _own_times(traced_serve["events"])
    steps = [o for o in own if o[0] == "tdt_serving_step"]
    assert len(steps) >= 4 and all(o[4] is None for o in steps)
    below = {"tdt_serving_health", "tdt_serving_join", "tdt_serving_reap",
             "tdt_serving_prefill", "tdt_serving_prefill_complete",
             "tdt_serving_dispatch", "tdt_serving_fetch", "tdt_serving_emit",
             "tdt_engine_decode_steps_paged", "tdt_engine_prefill_chunk",
             "tdt_engine_complete_paged_prefill", "tdt_engine_dispatch",
             "tdt_engine_host_sync", "tdt_scheduler_join_free_slots"}
    assert below <= {o[0] for o in own}
    events = traced_serve["events"]
    for name, a, b, glue, _ in steps:
        inside = [o for o in own if o[4] == a and o[0] != "tdt_serving_step"]
        loop_self = sum(o[3] for o in inside if o[0].startswith("tdt_serving_"))
        # outermost spans of the layers below: not nested in another of them
        lower = [e for e in events if a <= e[1] and e[2] <= b
                 and not e[0].startswith("tdt_serving_")]
        outer = [e for e in lower if not any(
            o is not e and o[1] <= e[1] and e[2] <= o[2] for o in lower)]
        calls = sum(e[2] - e[1] for e in outer)
        assert glue + loop_self + calls == b - a
    assert sum(o[3] for o in steps) <= 0.02 * sum(o[2] - o[1] for o in steps)
    by_phase: dict = {}
    seen: dict = {}
    for name, _, _, own_ns, _ in own:
        by_phase[name] = by_phase.get(name, 0.0) + own_ns / 1e9
        seen[name] = seen.get(name, 0) + 1
    assert seen == traced_serve["self_n"]
    self_s = traced_serve["self_s"]
    assert sum(self_s.values()) == pytest.approx(
        sum(by_phase.values()), rel=0.05, abs=2e-3)
    for name, seconds in self_s.items():
        assert seconds == pytest.approx(by_phase[name], rel=0.25, abs=1e-3), name


def test_loop_counters_equal_the_joins_and_prefill_chunks(traced_serve):
    """``tdt_serving_joins_total`` is the requests joined; the prefill
    chunks run are the sum of the histogram the server already kept
    (``tdt_serving_prefill_chunks``, one observation a prefill), which is why
    this PR adds no counter of them."""
    c = traced_serve["counters"]
    assert c["tdt_serving_joins_total"] == len(REQUESTS)
    # every prompt is shorter than the chunk knob: one chunk a prefill
    assert traced_serve["prefill_chunks"] == len(REQUESTS)
    assert traced_serve["self_n"]["tdt_engine_prefill_chunk"] == len(REQUESTS)
    steps = traced_serve["self_n"]["tdt_serving_step"]
    assert 0 < c["tdt_serving_decode_chunks_total"] <= steps
    # every chunk the server dispatched ran against the pool in place: the
    # engine counted each, and the bounce's scatter span never opened
    assert c["tdt_engine_decode_chunks_total"] == c["tdt_serving_decode_chunks_total"]
    assert "tdt_engine_cache_scatter" not in traced_serve["self_n"]
    assert c.get("tdt_jit_lowerings_total", 0.0) == 0.0  # warmed: nothing recompiled


def test_traced_serve_streams_the_same_tokens(traced_serve):
    """Byte for byte what one-shot ``serve`` gives (what the server was held
    to before it had spans), with the spans on and a profiler attached, and
    what the same server streamed a moment earlier with no profiler."""
    assert traced_serve["tokens"] == traced_serve["warm_tokens"]
    for got, ref in zip(traced_serve["tokens"], traced_serve["refs"]):
        np.testing.assert_array_equal(np.asarray(got, np.int32), ref)


def test_a_chunks_wait_is_the_engines_span_under_the_fetch(traced_serve):
    """Every decode chunk's wait for the device lies under
    ``tdt_engine_host_sync``, the fetch's child, the iteration's
    grandchild: the loop's own spans hold no device time, whichever chunk
    the iteration landed."""
    events = traced_serve["events"]
    waits = [e for e in events if e[0] == "tdt_engine_host_sync"]
    assert len(waits) == traced_serve["counters"]["tdt_serving_decode_chunks_total"]
    for _, a, b in waits:
        over = [e[0] for e in events if e[1] <= a and b <= e[2]
                and e[0] != "tdt_engine_host_sync"]
        assert over == ["tdt_serving_step", "tdt_serving_fetch"]


# ============================================= one decode chunk in flight


@pytest.fixture(scope="module")
def hybrid_engine():
    """The tiny ``test-hybrid-ssm`` preset: per-slot state (Mamba state,
    window rings) rides each chunk beside the pool."""
    from triton_dist_tpu.models import HYBRID_SSM_PRESETS, Engine, HybridSSMLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(
        devices=jax.devices()[:1], axis_names=("tp",), set_default=False)
    model = HybridSSMLLM(HYBRID_SSM_PRESETS["test-hybrid-ssm"], ctx,
                         key=jax.random.PRNGKey(7))
    return Engine(model, backend="dist", max_len=MAX_LEN)


@pytest.fixture(scope="module")
def engine1(model1):
    """One engine for the tests below that need no fresh one: its programs
    compile once."""
    return make_engine(model1)


@contextlib.contextmanager
def _lowerings():
    """The names of the programs jax lowers inside the block, as they come."""
    from jax._src import monitoring

    from triton_dist_tpu.runtime import tracing

    lowered = []

    def on_duration(event, duration, fun_name=None, **_):
        if event == tracing.LOWERING_EVENT:
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield lowered
    finally:
        monitoring.unregister_event_duration_listener(on_duration)


def _fence_every_chunk(mp, eng):
    """The seam: every prefill chunk waited for as it is issued, as the
    engine did before a chunk could be left to the device."""
    inner = eng.prefill_chunk_state
    mp.setattr(eng, "prefill_chunk_state", lambda *args, wait=True: inner(*args))



def _landings(srv):
    """Record every landing of ``srv`` as (why, slots dropped)."""
    seen, inner = [], srv._land

    def land(chunk, why, drop=()):
        seen.append((why, sorted(drop)))
        return inner(chunk, why, drop)

    srv._land = land
    return seen


# (prompt, max_new, what its own token stream does to it): three slots at
# chunk 2, more clients than slots, so that requests join mid-decode. The
# cancel and the deadline are set off by a token of their own request (the
# deadline through the server's clock, which only that token moves), so
# both loops meet them at the same token; each falls while the loop runs
# ahead, with the next chunk already issued.
SCRIPT = [
    ([3, 17, 42, 7, 99], 17, ("cancel", 6)),
    ([8, 1, 13], 11, None),
    ([5, 5, 5, 5, 5], 21, ("deadline", 12)),
    ([100, 200, 30], 6, None),
    ([7, 7, 7, 7, 7], 9, None),
    ([91, 12, 55], 14, None),
    ([3, 3, 9], 1, None),
]


def _serve_script(eng, journal_path, ahead: bool, script=None, **server_args):
    """``ahead`` false: the loop that lands every decode chunk before
    ``step()`` returns and waits for every prefill chunk as it is issued."""
    script = SCRIPT if script is None else script
    srv = InferenceServer(
        eng, num_slots=3, chunk=2, journal=str(journal_path), **server_args)
    if not ahead:
        srv._sync_reason = lambda chunk: "other"  # the seam: never in flight
    clock = [0.0]
    srv._now = lambda: clock[0]
    landings = _landings(srv)
    streams: dict[int, list[int]] = {}
    reqs = []

    def on_token(req, token, index):
        streams.setdefault(req.req_id, []).append(token)
        what = script[reqs.index(req)][2]
        if what == ("cancel", index):
            srv.cancel(req.req_id)
        elif what == ("deadline", index):
            clock[0] = 100.0

    for prompt, max_new, what in script:
        reqs.append(srv.submit(
            prompt, max_new, on_token=on_token,
            deadline_s=50.0 if what and what[0] == "deadline" else None))
    with pytest.MonkeyPatch.context() as mp:
        if not ahead:
            _fence_every_chunk(mp, eng)  # ... and no chunk left to the device
        srv.run()
    assert srv._in_flight is None and srv.scheduler.occupancy() == 0
    by_req: dict = {}
    for rec in srv.journal_records():
        by_req.setdefault(rec["req_id"] - reqs[0].req_id, []).append(
            {k: v for k, v in rec.items() if k != "req_id"})
    alloc = srv.kv_ledger.allocator
    left = {
        "tokens": [list(r.tokens) for r in reqs],
        "streamed": [streams.get(r.req_id, []) for r in reqs],
        "reasons": [r.finish_reason for r in reqs],
        "journal": by_req,
        "blocks": (sorted(alloc._free), dict(alloc._ref), srv.kv_ledger.stats()),
    }
    srv.shutdown(drain=False)
    return left, landings


@pytest.mark.parametrize("kind", ["dense", "hybrid-ssm"])
def test_in_flight_streams_are_the_landed_loops_byte_for_byte(
        kind, engine1, hybrid_engine, tmp_path):
    """The script above through the loop as it is and through the loop that
    lands every chunk before ``step()`` returns: the same tokens to the
    same callbacks, the same finish reasons, the same journal a request and
    the same blocks left in the allocator."""
    eng = engine1 if kind == "dense" else hybrid_engine
    got, landings = _serve_script(eng, tmp_path / "ahead.jsonl", ahead=True)
    want, landed = _serve_script(eng, tmp_path / "landed.jsonl", ahead=False)
    assert got == want
    assert got["tokens"] == got["streamed"]
    assert got["reasons"] == ["cancelled", "ok", "deadline", "ok", "ok", "ok", "ok"]
    assert [len(t) for t in got["tokens"]] == [7, 11, 13, 6, 9, 14, 1]
    # the loop did run ahead, and both reaps found a chunk in flight and
    # kept its tokens from the slot they freed
    assert sum(1 for why, _ in landings if why is None) >= 4
    assert [drop for _, drop in landings if drop] == [[0], [2]]
    assert len(landings) == len(landed) and all(why == "other" for why, _ in landed)


def test_in_flight_counters_are_the_hosts_count_of_the_boundaries(model1):
    """Two slots at chunk 2, 8 and 12 tokens after the first: chunks 1-3
    are landed behind the next one's issue; chunk 4 finishes a slot; chunk
    5 runs beside a free slot; chunk 6 finishes the other. The two
    counters sum to the chunks."""
    srv = InferenceServer(make_engine(model1), num_slots=2, chunk=2)
    landings = _landings(srv)
    reqs = [srv.submit([3, 17, 42], 9), srv.submit([8, 1, 13], 13)]
    steps = 0
    with _lowerings() as lowered:
        while srv.step():
            steps += 1
            # in flight exactly where the next boundary changes nothing
            assert (srv._in_flight is not None) == (steps <= 3)
    assert [len(r.tokens) for r in reqs] == [9, 13] and steps == 6
    assert [why for why, _ in landings] == [
        None, None, None, "finish", "free_slot", "finish"]
    sync = lambda why: telemetry.counter_value(
        "tdt_serving_decode_sync_boundaries_total", why=why)
    assert telemetry.counter_value("tdt_serving_decode_chunks_ahead_total") == 3.0
    assert (sync("finish"), sync("free_slot")) == (2.0, 1.0)
    assert telemetry.counter_total("tdt_serving_decode_sync_boundaries_total") == 3.0
    assert telemetry.counter_value("tdt_serving_decode_chunks_total") == 6.0
    # one executable for both routes: this engine's chunk program was
    # lowered once, fed from the host (chunks 1, 5, 6) and from the device's
    # own last tokens (chunks 2-4)
    assert lowered.count("jit(decode_chunk_paged)") == 1
    srv.shutdown(drain=False)


def test_a_cancel_between_steps_drops_the_chunk_in_flight(engine1):
    """A cancel that arrives from outside while a chunk is in flight: the
    next boundary lands the chunk, the cancelled request gets nothing of it
    and nothing after, and the other streams are untouched. A server told
    to drain lands the chunk it had in flight and leaves none after it."""
    eng = engine1
    want = np.asarray(eng.serve(jnp.asarray([[8, 1, 13]], jnp.int32), gen_len=15))[0]
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    streamed: list[int] = []
    gone = srv.submit([3, 17, 42], 13, on_token=lambda r, t, i: streamed.append(t))
    kept = srv.submit([8, 1, 13], 15)
    later = srv.submit([8, 1, 13], 9)
    srv.step()
    srv.step()
    assert srv._in_flight is not None and len(gone.tokens) == 3
    srv.cancel(gone.req_id)
    srv.step()
    assert gone.finish_reason == "cancelled" and streamed == list(gone.tokens)
    assert len(gone.tokens) == 3 and len(kept.tokens) == 7
    while srv._in_flight is None:  # ``later`` joins the slot; then ahead again
        assert srv.step()
    srv.drain_begin()
    srv.step()
    assert srv._in_flight is None
    assert telemetry.counter_value(
        "tdt_serving_decode_sync_boundaries_total", why="drain") == 1.0
    while not srv.drained:
        srv.step()
        assert srv._in_flight is None
    np.testing.assert_array_equal(np.asarray(kept.tokens, np.int32), want)
    np.testing.assert_array_equal(np.asarray(later.tokens, np.int32), want[:9])
    srv.shutdown()


@pytest.mark.chaos
def test_chaos_fault_at_the_landing_of_a_chunk_in_flight(engine1):
    """The scripted decode fault fires where the host comes for chunk 2,
    with chunk 3 already issued from its tokens: neither streams, recovery
    re-prefills both requests from the history chunk 1 left, and the
    streams are those of the undisturbed serve, nothing twice."""
    eng = engine1
    sizes = [([3, 17, 42], 9), ([8, 1, 13], 13)]

    def serve(schedule):
        srv = InferenceServer(eng, num_slots=2, chunk=2)
        landings = _landings(srv)
        streams: dict[int, list[int]] = {}
        reqs = [srv.submit(p, n, on_token=lambda r, t, i: streams.setdefault(
            r.req_id, []).append((i, t))) for p, n in sizes]
        with resilience.chaos_schedule(schedule):
            srv.run()
        srv.shutdown(drain=False)
        for r in reqs:  # every position once, in order
            assert streams[r.req_id] == list(enumerate(r.tokens))
        return [list(r.tokens) for r in reqs], landings

    want, _ = serve("heal")
    chunks = lambda: telemetry.counter_value("tdt_serving_decode_chunks_total")
    before = chunks()
    got, landings = serve("abort@decode:1,heal")
    assert got == want and [len(t) for t in got] == [9, 13]
    assert telemetry.counter_value("tdt_serving_recoveries_total", from_backend="xla") == 1.0
    # chunk 1 landed behind chunk 2's issue; chunk 2's landing raised with
    # chunk 3 in flight, and neither of them was counted as a chunk
    assert landings[:2] == [(None, []), (None, [])]
    assert chunks() - before == len(landings) - 1


@pytest.mark.parametrize("chunked", [False, True], ids=["one-chunk", "chunked"])
def test_phases_are_stamped_and_the_fence_is_the_landings(engine1, monkeypatch, chunked):
    """``host_sync`` is the wait at the landing, one a chunk and under the
    engine's span; ``dispatch`` is observed as before. ``admission`` and the
    fence under ``tdt_engine_prefill_chunk`` are one a PROMPT: its last
    chunk's, from that chunk's issue to the end of its compute (with what
    was queued on the device before it). Where every prompt is one chunk
    that is one a chunk, as it was; a prompt of three chunks is fenced once."""
    from triton_dist_tpu.runtime import tracing

    fences = []
    inner = jax.block_until_ready

    def fence(x):
        cur = tracing.current_span()
        fences.append(cur and cur["name"])
        return inner(x)

    monkeypatch.setattr(jax, "block_until_ready", fence)
    srv = InferenceServer(engine1, num_slots=2, chunk=2, prefill_chunk=4 if chunked else 16)
    reqs = [srv.submit([3, 17, 42], 9), srv.submit(LONG12 if chunked else [8, 1, 13], 13)]
    srv.run()
    assert all(r.done for r in reqs)
    chunks = telemetry.counter_value("tdt_serving_decode_chunks_total")
    assert fences.count("tdt_engine_host_sync") == chunks == (8.0 if chunked else 6.0)
    n = {e["labels"]["phase"]: e["n"] for e in
         telemetry.snapshot()["digests"]["tdt_engine_phase_seconds"]}
    assert n["host_sync"] == n["dispatch"] == chunks and n["admission"] == 2
    assert fences.count("tdt_engine_prefill_chunk") == 2
    (hist,) = telemetry.snapshot()["histograms"]["tdt_serving_prefill_chunks"]
    assert hist["sum"] == (1.0 + 3.0 if chunked else 2.0)
    assert telemetry.counter_value(
        "tdt_serving_prefill_chunks_unfenced_total") == hist["sum"] - 2.0
    srv.shutdown(drain=False)


# ===================== prefill chunks behind work in flight (chunked prompts)
#
# ``prefill_chunk=4``: a prompt of 9 or 12 takes three chunks, one of 14
# four (the last one padded). A chunk that is not its prompt's last is issued
# and not waited for, and a slot that prefills does not land the decode chunk
# beside it; the turn of a prompt's last chunk waits for that chunk and lands
# what is in flight (``why="prefill"``) before it touches the pool.

LONG9 = [61, 62, 63, 64, 65, 66, 67, 68, 69]
LONG12 = [21, 22, 23, 24, 25, 26, 27, 28, 29, 30, 31, 32]
LONG14 = [41, 42, 43, 44, 45, 46, 47, 48, 49, 50, 51, 52, 53, 54]

# Three slots at chunk 2, more clients than slots: chunked prompts join
# beside decoding slots at the opening and after every finish. The cancel
# and the deadline are set off by a token of their own request while other
# slots are mid-prompt.
CHUNKED_SCRIPT = [
    ([3, 17, 42], 15, None),
    (LONG12, 7, None),
    (LONG14, 9, ("cancel", 4)),
    (LONG9, 6, None),
    ([8, 1, 13], 12, ("deadline", 7)),
    (LONG14, 3, None),
    (LONG12, 1, None),
]


@pytest.mark.parametrize("kind", ["dense", "hybrid-ssm"])
def test_chunked_prompts_beside_decoding_slots_stream_the_fenced_loops_bytes(
        kind, engine1, hybrid_engine, tmp_path):
    """Prompts of three and four chunks among decoding slots, through the
    loop as it is and through the loop that waits for every prefill chunk
    and lands every decode chunk before ``step()`` returns: the same tokens
    to the same callbacks, finish reasons, journal and allocator, for a
    stateless model and for one whose prompt state rides (donated) from
    chunk to chunk."""
    eng = engine1 if kind == "dense" else hybrid_engine
    unfenced = lambda: telemetry.counter_value("tdt_serving_prefill_chunks_unfenced_total")
    got, landings = _serve_script(
        eng, tmp_path / "ahead.jsonl", True, CHUNKED_SCRIPT, prefill_chunk=4)
    n_unfenced = unfenced()
    want, landed = _serve_script(
        eng, tmp_path / "landed.jsonl", False, CHUNKED_SCRIPT, prefill_chunk=4)
    assert got == want
    assert got["tokens"] == got["streamed"]
    assert got["reasons"] == ["ok", "ok", "cancelled", "ok", "deadline", "ok", "ok"]
    assert [len(t) for t in got["tokens"]] == [15, 7, 5, 6, 9, 3, 1]
    whys = [why for why, _ in landings]
    # the loop ran ahead beside prefilling slots and last chunks landed what
    # was in flight; both loops issued the same chunks, one of them unwaited
    assert whys.count(None) >= 3 and whys.count("prefill") >= 2
    assert len(landings) == len(landed) and all(why == "other" for why, _ in landed)
    assert n_unfenced == 2 * (2 + 3) + 2 == unfenced() - n_unfenced
    assert not eng._unwaited_stats


def test_a_chunk_stays_in_flight_across_a_step_that_prefills(model1):
    """Three slots at chunk 2: a short prompt decodes 20 tokens while a
    prompt of 12 (three chunks) and one of 14 (four) prefill beside it. The
    host's count of every boundary: which steps leave a chunk in flight, why
    each chunk was landed, the chunks nobody waited for."""
    srv = InferenceServer(make_engine(model1), num_slots=3, chunk=2, prefill_chunk=4)
    landings = _landings(srv)
    reqs = [srv.submit([3, 17, 42], 21), srv.submit(LONG12, 9), srv.submit(LONG14, 7)]
    unfenced = lambda: telemetry.counter_value("tdt_serving_prefill_chunks_unfenced_total")
    in_flight, prefilling, counted = [], [], []
    while True:
        before = sorted(srv._prefilling)
        if not srv.step():
            break
        in_flight.append(srv._in_flight is not None)
        prefilling.append(before + sorted(srv._prefilling))
        counted.append(unfenced())
    assert [len(r.tokens) for r in reqs] == [21, 9, 7]
    # step 1: three joins, the short prompt's one chunk (its last, waited
    # for) and the first chunk of the other two; decode chunk 1 over slot 0
    # stays in flight though slots 1 and 2 prefill. Step 2: their second
    # chunks behind it, chunk 2 issued ahead. Step 3: slot 1's last chunk is
    # waited for and lands chunk 2 (``prefill``); chunk 3, over slots 0 and
    # 1, in flight beside slot 2's third. Step 4: slot 2's last lands chunk
    # 3. Steps 5-6: all three decode, chunks 4 and 5 landed behind the next
    # one's issue; slots 1 and 2 run out in chunk 6, landed ``finish``; from
    # there a slot is free.
    assert in_flight[:7] == [True, True, True, True, True, False, False]
    assert prefilling[0] == [1, 2] and prefilling[1] == [1, 2, 1, 2]
    assert prefilling[2] == [1, 2, 2] and prefilling[3] == [2]
    assert counted[:5] == [2.0, 4.0, 5.0, 5.0, 5.0] and counted[-1] == 5.0
    whys = [why for why, _ in landings]
    assert whys[:6] == [None, "prefill", "prefill", None, None, "finish"]
    assert set(whys[6:]) <= {"finish", "free_slot"}
    sync = lambda why: telemetry.counter_value(
        "tdt_serving_decode_sync_boundaries_total", why=why)
    chunks = telemetry.counter_value("tdt_serving_decode_chunks_total")
    assert chunks == len(landings)
    assert telemetry.counter_value("tdt_serving_decode_chunks_ahead_total") == 3.0
    # ``prefill`` is the prompts' last chunks that found a chunk in flight
    assert sync("prefill") == 2.0
    assert telemetry.counter_total(
        "tdt_serving_decode_sync_boundaries_total") == chunks - 3.0
    (hist,) = telemetry.snapshot()["histograms"]["tdt_serving_prefill_chunks"]
    assert (hist["sum"], hist["count"]) == (1.0 + 3.0 + 4.0, 3)
    srv.shutdown(drain=False)


def test_no_program_is_lowered_that_the_fenced_loop_does_not_lower(model1):
    """The same requests through a fresh engine a loop: the programs jax
    lowers are, name for name and count for count, those of the loop that
    waits for every chunk. The decode chunk is one executable fed from the
    host and from the device, and each (chunk, prompt) shape of the prefill
    one program, waited for or not."""
    def serve(fenced: bool):
        eng = make_engine(model1)
        with pytest.MonkeyPatch.context() as mp, _lowerings() as lowered:
            if fenced:
                _fence_every_chunk(mp, eng)
            srv = InferenceServer(eng, num_slots=3, chunk=2, prefill_chunk=4)
            if fenced:
                srv._sync_reason = lambda chunk: "other"
            reqs = [srv.submit(p, n) for p, n in
                    [([3, 17, 42], 21), (LONG12, 9), (LONG14, 7), (LONG9, 5)]]
            srv.run()
            srv.shutdown(drain=False)
        return [list(r.tokens) for r in reqs], sorted(lowered)

    tokens, lowered = serve(fenced=False)
    want_tokens, want = serve(fenced=True)
    assert tokens == want_tokens and lowered == want
    assert lowered.count("jit(decode_chunk_paged)") == 1
    assert lowered.count("jit(chunk_fn)") == 4  # prompts of 3, 9, 12 and 14


def test_unwaited_chunks_step_counters_sum_to_the_fenced_loops(hybrid_engine):
    """The step counters of a chunk nobody waits for are published at the
    next wait the loop makes: over a served script every model counter
    reads what it reads when each chunk is waited for as it is issued."""
    eng = hybrid_engine

    def serve(fenced: bool):
        telemetry.reset()
        with pytest.MonkeyPatch.context() as mp:
            if fenced:
                _fence_every_chunk(mp, eng)
            srv = InferenceServer(eng, num_slots=3, chunk=2, prefill_chunk=4)
            reqs = [srv.submit(p, n) for p, n, _ in CHUNKED_SCRIPT]
            srv.run()
            srv.shutdown(drain=False)
        assert not eng._unwaited_stats
        counters = {
            (name, tuple(sorted(e["labels"].items()))): e["value"]
            for name, es in telemetry.snapshot()["counters"].items()
            if name.startswith(("tdt_swa_", "tdt_ssm_", "tdt_shared_kv_"))
            for e in es
        }
        unfenced = telemetry.counter_value("tdt_serving_prefill_chunks_unfenced_total")
        return [list(r.tokens) for r in reqs], counters, unfenced

    tokens, counters, unfenced = serve(fenced=False)
    want_tokens, want, also_unfenced = serve(fenced=True)
    assert tokens == want_tokens
    # the loop counts the chunks it asked no wait for; the seam waited anyway
    assert unfenced == also_unfenced == 2 * (2 + 3) + 2
    assert counters == want and len(counters) == 10
    prefill_rows = counters[("tdt_ssm_tokens_total", (("phase", "prefill"),))]
    assert prefill_rows >= sum(len(p) for p, _, _ in CHUNKED_SCRIPT)


@pytest.mark.chaos
@pytest.mark.parametrize("surfaces_at", ["last_chunk", "landing"])
def test_chaos_fault_in_an_unwaited_chunk_surfaces_at_the_next_wait(
        engine1, surfaces_at):
    """A fault the device keeps for whoever waits next: the first chunk
    nobody waits for is poisoned, and the next fence the host enters raises.
    With no slot decoding yet that is the fence of a prompt's last chunk
    (under ``tdt_engine_prefill_chunk``); beside a decoding slot it is the
    landing of the chunk in flight (``tdt_engine_host_sync``). Either way
    recovery re-prefills from the history and every stream is the
    undisturbed serve's, each position once."""
    from triton_dist_tpu.runtime import tracing

    eng = engine1
    sizes = [(LONG12, 5), (LONG14, 6)]
    if surfaces_at == "landing":
        sizes.insert(0, ([3, 17, 42], 13))

    def serve(poison: bool):
        fired = []
        with pytest.MonkeyPatch.context() as mp:
            inner_chunk, inner_fence = eng.prefill_chunk_state, jax.block_until_ready
            armed = [False]

            def chunk(*args, wait=True):
                out = inner_chunk(*args, wait=wait)
                armed[0] = armed[0] or (poison and not wait and not fired)
                return out

            def fence(x):
                if armed[0]:
                    armed[0] = False
                    fired.append(tracing.current_span()["name"])
                    raise resilience.CollectiveAbortError("poisoned promise")
                return inner_fence(x)

            mp.setattr(eng, "prefill_chunk_state", chunk)
            mp.setattr(jax, "block_until_ready", fence)
            srv = InferenceServer(eng, num_slots=3, chunk=2, prefill_chunk=4)
            streams: dict[int, list] = {}
            reqs = [srv.submit(p, n, on_token=lambda r, t, i: streams.setdefault(
                r.req_id, []).append((i, t))) for p, n in sizes]
            srv.run()
            assert srv._in_flight is None and not srv._prefilling
            srv.shutdown(drain=False)
        for r in reqs:  # every position once, in order
            assert streams[r.req_id] == list(enumerate(r.tokens))
        return [list(r.tokens) for r in reqs], fired

    want, _ = serve(poison=False)
    recoveries = lambda: telemetry.counter_total("tdt_serving_recoveries_total")
    assert recoveries() == 0.0
    got, fired = serve(poison=True)
    assert got == want and [len(t) for t in got] == [n for _, n in sizes]
    assert fired == ["tdt_engine_prefill_chunk" if surfaces_at == "last_chunk"
                     else "tdt_engine_host_sync"]
    assert recoveries() == 1.0


def test_a_cancel_of_a_prefilling_slot_with_chunks_unwaited(engine1):
    """Four slots full, one decoding, three mid-prompt with a chunk each
    issued and not waited for, a decode chunk in flight: the cancel of a
    prefilling slot lands the chunk in flight (nothing of it is the
    cancelled slot's: it never decoded), drops the slot's buffers and frees
    its chain; the other streams are the undisturbed serve's."""
    eng = engine1
    sizes = [([3, 17, 42], 13), (LONG14, 6), (LONG12, 5), (LONG12, 9)]

    def serve(cancel: bool):
        srv = InferenceServer(eng, num_slots=4, chunk=2, prefill_chunk=4)
        landings = _landings(srv)
        reqs = [srv.submit(p, n) for p, n in sizes]
        assert srv.step()
        assert srv._in_flight is not None and sorted(srv._prefilling) == [1, 2, 3]
        if cancel:
            srv.cancel(reqs[1].req_id)
            free = srv.kv_ledger.stats()["blocks_free"]
            assert srv.step()
            assert 1 not in srv._prefilling and srv.scheduler.slots[1].request is None
            assert srv.kv_ledger.stats()["blocks_free"] > free
        srv.run()
        assert srv._in_flight is None and srv.scheduler.occupancy() == 0
        srv.shutdown(drain=False)
        return reqs, landings

    want, _ = serve(cancel=False)
    got, landings = serve(cancel=True)
    assert got[1].finish_reason == "cancelled" and got[1].tokens == []
    for i in (0, 2, 3):
        assert list(got[i].tokens) == list(want[i].tokens) and got[i].finish_reason == "ok"
    # the reap landed the chunk in flight and kept it from the slot it freed
    assert ("other", [1]) in landings
    assert telemetry.counter_value("tdt_serving_cancelled_total", where="running") == 1.0


# ========================= a request's timeline and the device's ledger
#
# The served script again (three slots at chunk 2, more clients than slots,
# a request of one token), with nothing cancelled: what the program says of
# its own time, against the requests' fields, the wall clock and the
# ledger's own running total taken at every ``step()``'s edges.


@pytest.fixture(scope="module")
def timeline(engine1):
    import time

    from triton_dist_tpu.runtime import tracing

    srv = InferenceServer(engine1, num_slots=3, chunk=2)
    for prompt, max_new, _ in SCRIPT[:3]:  # every program compiled and run once
        srv.submit(prompt, max_new)
    srv.run()
    telemetry.reset()
    tracing.reset()
    srv = InferenceServer(engine1, num_slots=3, chunk=2)
    observed: list = []
    inner = telemetry.observe

    def observe(name, value, /, **labels):
        observed.append((name, value))
        inner(name, value, **labels)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "observe", observe)
        reqs = [srv.submit(prompt, max_new) for prompt, max_new, _ in SCRIPT]
        t0 = time.perf_counter()
        in_steps = 0.0
        while True:
            at = tracing.device_starved_s()
            worked = srv.step()
            in_steps += tracing.device_starved_s() - at
            if not worked:
                break
        wall = time.perf_counter() - t0
        idle_at = tracing.device_starved_s()
        for _ in range(3):  # a server with nothing to serve
            time.sleep(0.01)
            srv.step()
    left = {
        "reqs": reqs, "wall": wall, "observed": observed, "in_steps": in_steps,
        "total": idle_at, "after_idle": tracing.device_starved_s(),
        "snap": telemetry.snapshot(),
    }
    srv.shutdown(drain=False)
    return left


def _hist(snap, name):
    (e,) = snap["histograms"][name]
    return e["sum"], e["count"]


def test_queue_wait_and_residence_are_the_time_to_the_first_token(timeline):
    reqs, snap = timeline["reqs"], timeline["snap"]
    assert all(r.done and r.finish_reason == "ok" for r in reqs)
    for r in reqs:
        wait, residence = r.admitted_at - r.arrived_at, r.first_token_at - r.admitted_at
        assert wait >= 0.0 and residence > 0.0
        assert wait + residence == pytest.approx(r.ttft_s, abs=1e-3)
    wait_s, n_wait = _hist(snap, "tdt_serving_queue_wait_seconds")
    res_s, n_res = _hist(snap, "tdt_serving_prefill_residence_seconds")
    ttft_s, n_ttft = _hist(snap, "tdt_serving_ttft_seconds")
    assert n_wait == n_res == n_ttft == len(reqs)
    # the histograms are the requests' own fields, and they add up
    assert wait_s == pytest.approx(sum(r.admitted_at - r.arrived_at for r in reqs), abs=1e-6)
    assert res_s == pytest.approx(sum(r.first_token_at - r.admitted_at for r in reqs), abs=1e-6)
    assert wait_s + res_s == pytest.approx(ttft_s, rel=1e-2, abs=1e-6)
    assert wait_s > 0.0  # four of the seven waited for a slot


def test_own_prefill_time_is_within_the_residence_request_by_request(timeline):
    seen = [(n, v) for n, v in timeline["observed"] if n.startswith("tdt_serving_prefill_")
            and n.endswith(("_residence_seconds", "_own_seconds"))]
    pairs = list(zip(seen[::2], seen[1::2]))
    assert len(pairs) == len(timeline["reqs"])
    for (res_name, residence), (own_name, own) in pairs:
        assert res_name == "tdt_serving_prefill_residence_seconds"
        assert own_name == "tdt_serving_prefill_own_seconds"
        assert 0.0 < own <= residence + 1e-9
    # requests that joined together took turns: someone's residence holds
    # another's prefill, so the sums differ
    own_s, _ = _hist(timeline["snap"], "tdt_serving_prefill_own_seconds")
    res_s, _ = _hist(timeline["snap"], "tdt_serving_prefill_residence_seconds")
    assert own_s < res_s


def test_chunk_walls_lie_within_the_served_time_and_boundaries_sum(timeline):
    snap = timeline["snap"]
    count = lambda name: sum(e["value"] for e in snap["counters"].get(name, []))
    chunks = count("tdt_serving_decode_chunks_total")
    assert chunks > 0 and chunks == (
        count("tdt_serving_decode_chunks_ahead_total")
        + count("tdt_serving_decode_sync_boundaries_total"))
    assert count("tdt_serving_decode_chunks_ahead_total") > 0
    wall_s, n = _hist(snap, "tdt_serving_decode_chunk_seconds")
    # one observation a chunk, disjoint stretches of the loop's time
    assert n == chunks and 0.0 < wall_s <= timeline["wall"]
    assert "tdt_serving_chunk_token_seconds" not in snap["histograms"]
    # the doc's batch-level TPOT: the histogram's sum over the tokens
    tokens = count("tdt_serving_tokens_total")
    assert tokens == sum(len(r.tokens) for r in timeline["reqs"]) - len(timeline["reqs"])


def test_starved_phases_and_the_time_outside_spans_are_the_counter(timeline):
    """Over the served window: the digest's sum over phases is what the
    ledger's total moved inside ``step()`` (every second of a step lies in a
    span), that plus the move outside is the total, and the counter less
    ``no_work`` is the total less the interval still open at the end."""
    snap = timeline["snap"]
    after = {e["labels"]["after"]: e["value"]
             for e in snap["counters"]["tdt_engine_device_starved_seconds_total"]}
    phases = {e["labels"]["phase"]: e["sum"]
              for e in snap["digests"]["tdt_span_starved_seconds"]}
    total, in_steps = timeline["total"], timeline["in_steps"]
    assert 0.0 < in_steps <= total <= timeline["wall"]
    assert sum(phases.values()) == pytest.approx(in_steps, rel=1e-2)
    counted = sum(v for k, v in after.items() if k != "no_work")
    # the idle steps closed the last interval, so nothing is open
    assert counted == pytest.approx(total, rel=1e-2)
    assert counted == pytest.approx(sum(phases.values()) + (total - in_steps), rel=1e-2)
    # the intervals are named after the waits that began them
    assert set(after) <= {"prefill_chunk", "cache_scatter", "no_work"} | {
        f"decode_land:{why}" for why in ("finish", "free_slot", "prefill")}
    assert {"prefill_chunk", "cache_scatter", "decode_land:finish"} <= set(after)
    # the device starved under the engine's calls and the loop's own spans alike
    assert {"tdt_engine_prefill_chunk", "tdt_serving_fetch"} <= set(phases)
    # a server with nothing to serve is not starving its device
    assert timeline["after_idle"] == pytest.approx(total, abs=1e-4)
