"""Tracing tests: span-model semantics, the serving-stack thread-through
(the chrome-chain acceptance bar), chaos recovery spans, and the live
introspection endpoint exercised against a real serving loop.

Same substrate rules as ``test_serving.py``: CPU world=1 (collectives
short-circuit to XLA), generic-interpreter fallback for the single-device
Pallas kernels. The span ring and the sampling accumulator are
process-global like the telemetry registry, so every test resets both.
"""

import json
import os
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from triton_dist_tpu.runtime import introspect, resilience, telemetry, tracing
from triton_dist_tpu.serving import InferenceServer

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    tracing.reset()
    resilience.reset_degradation()
    yield
    telemetry.reset()
    tracing.reset()
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


# ================================================================ span model


def test_span_nesting_and_ambient_parenting():
    t = tracing.start_trace("tdt_test_trace", req_id=1)
    assert t.sampled
    assert tracing.current_span() is None
    with t.span("tdt_test_outer") as outer:
        assert tracing.current_span() is outer
        assert tracing.current_correlation() == (t.trace_id, outer["span_id"])
        with t.span("tdt_test_inner") as inner:
            assert inner["parent_id"] == outer["span_id"]
    assert tracing.current_span() is None
    t.finish()
    spans = {s["name"]: s for s in tracing.spans(t.trace_id)}
    assert spans["tdt_test_outer"]["parent_id"] == t.root_id
    assert spans["tdt_test_inner"]["parent_id"] == spans["tdt_test_outer"]["span_id"]
    # Every span closed with end >= start, all in one trace.
    for s in spans.values():
        assert s["end_s"] >= s["start_s"]
        assert s["trace_id"] == t.trace_id


def test_retroactive_record_and_points():
    t = tracing.start_trace("tdt_test_trace")
    t0 = tracing.now_s()
    sid = t.record("tdt_test_retro", t0 - 0.5, t0 - 0.25, slot=3)
    assert isinstance(sid, int)
    # point_current outside any live span is a no-op, not an error.
    tracing.point_current("tdt_test_orphan", x=1)
    with t.span("tdt_test_live"):
        tracing.point_current("tdt_test_mark", peer=2)
    t.finish()
    spans = {s["name"]: s for s in tracing.spans(t.trace_id)}
    assert "tdt_test_orphan" not in spans
    retro = spans["tdt_test_retro"]
    assert retro["span_id"] == sid and retro["attrs"]["slot"] == 3
    assert abs((retro["end_s"] - retro["start_s"]) - 0.25) < 1e-6
    mark = spans["tdt_test_mark"]
    assert mark["parent_id"] == spans["tdt_test_live"]["span_id"]
    assert mark["end_s"] == mark["start_s"]  # zero-duration


def test_name_stays_usable_as_attribute_key():
    """Span names are positional-only, so ``name=...`` lands in attrs —
    the watchdog's timeout point labels which collective timed out."""
    t = tracing.start_trace("tdt_test_trace", name="outer")
    with t.span("tdt_test_live", name="inner"):
        tracing.point_current("tdt_test_mark", name="_ring_ag_kernel")
    t.point("tdt_test_point", name="p")
    t.finish()
    spans = {s["name"]: s for s in tracing.spans(t.trace_id)}
    assert spans["tdt_test_trace"]["attrs"]["name"] == "outer"
    assert spans["tdt_test_live"]["attrs"]["name"] == "inner"
    assert spans["tdt_test_mark"]["attrs"]["name"] == "_ring_ag_kernel"
    assert spans["tdt_test_point"]["attrs"]["name"] == "p"


def test_finish_emits_ring_event_and_is_idempotent():
    t = tracing.start_trace("tdt_test_trace")
    with t.span("tdt_test_child"):
        pass
    t.finish(status="ok")
    t.finish(status="ok")  # second finish: no-op, no duplicate event
    evs = telemetry.events("trace")
    assert len(evs) == 1
    assert evs[0]["trace_id"] == t.trace_id
    assert evs[0]["name"] == "tdt_test_trace"
    assert evs[0]["dur_s"] >= 0


def test_sampling_is_deterministic(monkeypatch):
    monkeypatch.setenv("TDT_TRACE_SAMPLE", "0.5")
    tracing.reset()  # restart the error-feedback accumulator
    traces = [tracing.start_trace("tdt_test_trace", i=i) for i in range(6)]
    sampled = [t.sampled for t in traces]
    assert sampled == [False, True, False, True, False, True]
    # Unsampled handles are the shared no-op: every method safe, no spans.
    t = traces[0]
    with t.span("tdt_test_child") as sp:
        assert sp is None
    assert t.record("tdt_test_retro", 0.0, 1.0) is None
    t.finish()
    assert len(tracing.trace_ids()) == 3


def test_disabled_telemetry_disables_tracing():
    telemetry.reset(enabled_override=False)
    t = tracing.start_trace("tdt_test_trace")
    assert t is tracing.NOOP_TRACE and not t.sampled
    with t.span("tdt_test_child"):
        pass
    t.finish()
    assert tracing.spans() == []
    assert not tracing.enabled()


def test_span_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("TDT_SPAN_RING", "8")
    tracing.reset()
    t = tracing.start_trace("tdt_test_trace")
    for i in range(30):
        t.record("tdt_test_retro", float(i), float(i) + 0.5, i=i)
    spans = tracing.spans()
    assert len(spans) == 8
    # Oldest evicted first: the survivors are the newest 8.
    assert [s["attrs"]["i"] for s in spans] == list(range(22, 30))


def test_chrome_export_shape(tmp_path):
    t = tracing.start_trace("tdt_serving_request", req_id=9)
    with t.span("tdt_test_child", slot=1):
        pass
    # Leave the trace OPEN: the root must export with a running duration.
    path = tracing.export_chrome(str(tmp_path / "trace.json"))
    doc = json.loads((tmp_path / "trace.json").read_text())
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert path.endswith("trace.json")
    assert meta and f"req=9" in meta[0]["args"]["name"]
    assert all(e["ts"] >= 0 for e in events)  # normalized to the earliest
    root = next(e for e in events if e["args"]["parent_id"] is None)
    assert root["args"].get("open") is True and root["dur"] > 0
    child = next(e for e in events if e["name"] == "tdt_test_child")
    assert child["args"]["parent_id"] == root["args"]["span_id"]
    assert child["pid"] == root["pid"] == t.trace_id


def test_snapshot_traces_and_dump_integration(tmp_path):
    t = tracing.start_trace("tdt_test_trace")
    with t.span("tdt_test_child"):
        snap = tracing.snapshot_traces()
        assert snap["n_open"] == 2  # root + live child
    t.finish()
    out = telemetry.dump(str(tmp_path / "snap.json"))
    doc = json.loads(open(out).read())
    assert doc["traces"]["n_spans"] == 2 and doc["traces"]["n_open"] == 0
    assert doc["traces"]["traces"][0]["trace_id"] == t.trace_id


# ===================================================== serving thread-through


def _span_names(trace_id):
    return [s["name"] for s in tracing.spans(trace_id)]


def test_engine_build_gets_a_trace(model1):
    make_engine(model1)
    builds = [
        tid for tid in tracing.trace_ids()
        if "tdt_engine_build" in _span_names(tid)
    ]
    assert len(builds) == 1
    (root,) = tracing.spans(builds[0])
    assert root["parent_id"] is None
    assert root["attrs"]["backend"] == "xla"
    assert root["end_s"] > root["start_s"]


def test_staggered_serving_chrome_chain(model1, tmp_path):
    """Acceptance: every request's trace carries the complete
    queue→prefill→decode→done chain, decode-chunk spans name the slot the
    request actually occupied, and the shared dispatch attribution points
    into the server trace."""
    eng = make_engine(model1)
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    handles = [
        srv.submit(p, g, arrival_time_s=i * 0.01)
        for i, (p, g) in enumerate(
            [([3, 17, 42], 5), ([8, 1], 4), ([5, 5, 5, 5], 3), ([9], 4)]
        )
    ]
    srv.run()
    assert all(h.done for h in handles)

    server_span_ids = {s["span_id"] for s in tracing.spans(srv._trace.trace_id)}
    for h in handles:
        spans = tracing.spans(h.trace.trace_id)
        by_name: dict[str, list[dict]] = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        # Complete chain, in timeline order.
        for name in ("tdt_serving_queue_wait", "tdt_serving_prefill",
                     "tdt_serving_decode_chunk", "tdt_serving_stream",
                     "tdt_serving_finish", "tdt_serving_request"):
            assert name in by_name, (h.req_id, sorted(by_name))
        root = by_name["tdt_serving_request"][0]
        assert root["parent_id"] is None
        assert root["attrs"]["req_id"] == h.req_id
        ids = {s["span_id"] for s in spans}
        assert all(s["parent_id"] in ids for s in spans if s is not root)
        # Slot attribution: every decode chunk ran in the slot this request
        # was prefilled into.
        slot = by_name["tdt_serving_prefill"][0]["attrs"]["slot"]
        chunks = by_name["tdt_serving_decode_chunk"]
        assert chunks and all(c["attrs"]["slot"] == slot for c in chunks)
        # Streamed token counts across chunks equal the post-TTFT tokens.
        assert sum(c["attrs"]["n_tokens"] for c in chunks) == len(h.tokens) - 1
        # Shared-dispatch attribution: each chunk references a span in the
        # SERVER trace (the one device dispatch it rode).
        assert all(c["attrs"]["dispatch"] in server_span_ids for c in chunks)
        # The chain is causally ordered.
        t_queue = by_name["tdt_serving_queue_wait"][0]["end_s"]
        t_prefill = by_name["tdt_serving_prefill"][0]["start_s"]
        assert t_prefill >= t_queue - 1e-6
        assert by_name["tdt_serving_finish"][0]["start_s"] >= t_prefill

    # The chrome export holds one process row per trace with the chain
    # machine-checkable via args.span_id/parent_id.
    doc = json.loads(
        open(tracing.export_chrome(str(tmp_path / "serve.json"))).read()
    )
    by_pid: dict[int, list[dict]] = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_pid.setdefault(e["pid"], []).append(e)
    for h in handles:
        names = {e["name"] for e in by_pid[h.trace.trace_id]}
        assert {"tdt_serving_request", "tdt_serving_prefill",
                "tdt_serving_decode_chunk", "tdt_serving_finish"} <= names

    # Queue-wait satellite: one histogram observation per admitted request.
    hist = telemetry.snapshot()["histograms"]["tdt_serving_queue_wait_seconds"]
    assert hist[0]["count"] == len(handles)


def test_rejected_request_trace_closes():
    from triton_dist_tpu.serving import Scheduler

    sched = Scheduler(num_slots=1, max_len=8)
    r = sched.submit([1] * 8, max_new=8)  # kv_budget reject
    assert r.reject_reason == "kv_budget"
    (root,) = tracing.spans(r.trace.trace_id)
    assert root["name"] == "tdt_serving_request"
    assert root["attrs"]["status"] == "rejected"
    assert root["attrs"]["reason"] == "kv_budget"
    assert root["end_s"] is not None


@pytest.mark.chaos
def test_chaos_recovery_span_parented_under_affected_traces(model1):
    """Acceptance: a mid-serving abort shows up in each affected request's
    trace as a recovery span parented at its root, covering the rebuild +
    re-prefill window."""
    eng = make_engine(model1, backend="dist_ar")
    srv = InferenceServer(eng, num_slots=2, chunk=2)

    orig = eng._decode_chunk_paged
    calls = {"n": 0}

    def boom(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            resilience.mark_degraded("collectives", "injected abort (test)")
            raise resilience.CollectiveAbortError("injected abort (test)")
        return orig(*args, **kwargs)

    eng._decode_chunk_paged = boom
    handles = [srv.submit([3, 17, 42], 6), srv.submit([8, 1], 5)]
    srv.run()
    assert calls["n"] == 2 and eng.backend == "xla"
    assert all(h.done for h in handles)

    affected = 0
    for h in handles:
        spans = {s["name"]: s for s in tracing.spans(h.trace.trace_id)}
        rec = spans.get("tdt_serving_recovery")
        if rec is None:
            continue  # finished before the abort — legitimately unaffected
        affected += 1
        assert rec["parent_id"] == h.trace.root_id
        assert rec["attrs"]["from_backend"] == "dist_ar"
        # The recovery window contains the re-prefill.
        re_prefills = [
            s for s in tracing.spans(h.trace.trace_id)
            if s["name"] == "tdt_serving_prefill" and s["attrs"]["recovery"]
        ]
        assert re_prefills
        assert all(
            rec["start_s"] - 1e-6 <= s["start_s"] and s["end_s"] <= rec["end_s"] + 1e-6
            for s in re_prefills
        )
    assert affected >= 1
    # The server trace carries the recovery too, and a second engine-build
    # trace exists (the degraded rebuild on xla).
    assert "tdt_serving_recovery" in _span_names(srv._trace.trace_id)
    builds = [
        s for tid in tracing.trace_ids() for s in tracing.spans(tid)
        if s["name"] == "tdt_engine_build"
    ]
    assert [b["attrs"]["backend"] for b in builds] == ["dist_ar", "xla"]


# ======================================================== live introspection


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_endpoint_live_against_serving_loop(model1, monkeypatch):
    """Acceptance: /metrics and /healthz answer correctly WHILE the serving
    loop is running — fetched from inside an on_token callback, i.e. with
    requests genuinely in flight."""
    monkeypatch.setenv("TDT_HTTP_PORT", "0")  # ephemeral port
    eng = make_engine(model1)
    srv = InferenceServer(eng, num_slots=2, chunk=2)
    assert srv._introspect is not None
    base = srv._introspect.url()
    live: dict[str, object] = {}

    def on_token(req, token, index):
        if live:
            return  # one mid-serve scrape is enough
        code, body = _get(base + "metrics")
        live["metrics"] = (code, body)
        live["healthz"] = _get(base + "healthz")
        live["snapshot"] = _get(base + "snapshot")

    handles = [srv.submit([3, 17, 42], 5, on_token=on_token),
               srv.submit([8, 1], 4, on_token=on_token)]
    try:
        srv.run()
        assert all(h.done for h in handles)

        code, body = live["metrics"]
        assert code == 200
        assert "# TYPE tdt_serving_requests_total counter" in body
        code, body = live["healthz"]
        assert code == 200
        health = json.loads(body)
        assert health["status"] == "ok" and health["uptime_s"] >= 0
        code, body = live["snapshot"]
        snap = json.loads(body)
        assert snap["traces"]["n_open"] >= 1  # requests were mid-flight

        # After the run: trace routes, 404s, and the degraded healthz.
        code, body = _get(base + "traces")
        ids = json.loads(body)["trace_ids"]
        assert handles[0].trace.trace_id in ids
        code, body = _get(base + f"traces/{handles[0].trace.trace_id}")
        names = {e["name"] for e in json.loads(body)["traceEvents"]}
        assert "tdt_serving_request" in names
        code, body = _get(base + "traces/last")
        assert code == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "traces/424242")
        assert ei.value.code == 404
        resilience.mark_degraded("collectives", "test degradation")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["status"] == "degraded"
    finally:
        srv._introspect.stop()


def test_maybe_start_disabled_by_default(monkeypatch):
    monkeypatch.delenv("TDT_HTTP_PORT", raising=False)
    assert introspect.maybe_start() is None
    monkeypatch.setenv("TDT_HTTP_PORT", "")
    assert introspect.maybe_start() is None
    monkeypatch.setenv("TDT_HTTP_PORT", "not-a-port")
    assert introspect.maybe_start() is None  # logged, never raises


# ============================================= cross-process propagation


def test_inject_extract_roundtrip():
    t = tracing.start_remote_trace("tdt_fleet_request", fleet_id=7)
    assert t.sampled
    car = tracing.inject(t)
    tp = car["traceparent"]
    # W3C-traceparent shape: version-traceid-spanid-flags, all lowercase hex.
    assert tp == f"00-{t.trace_id:032x}-{t.root_id:016x}-01"
    ctx = tracing.extract(car)
    assert ctx == (t.trace_id, t.root_id, True)
    # The raw string extracts too (a peer may flatten the carrier).
    assert tracing.extract(tp) == ctx
    # inject can pin a non-root parent span.
    with t.span("tdt_test_child") as sp:
        car2 = tracing.inject(t, span_id=sp["span_id"])
    assert tracing.extract(car2).span_id == sp["span_id"]


def test_extract_rejects_malformed_carriers():
    bad = [
        None, {}, {"traceparent": 42}, "nonsense",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",      # zero trace id
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",      # zero span id
        "ff-" + "1" * 32 + "-" + "1" * 16 + "-01",      # forbidden version
        "00-" + "1" * 31 + "-" + "1" * 16 + "-01",      # short trace id
    ]
    for carrier in bad:
        assert tracing.extract(carrier) is None, carrier


def test_continue_trace_parents_under_remote_span():
    t = tracing.start_remote_trace("tdt_fleet_request")
    with t.span("tdt_fleet_placement") as psp:
        car = tracing.inject(t, span_id=psp["span_id"])
    # "Remote" side: same process here, but only the carrier crosses.
    t2 = tracing.continue_trace(tracing.extract(car), "tdt_serving_request",
                                req_id=3)
    assert t2.trace_id == t.trace_id and t2.sampled
    with t2.span("tdt_serving_queue_wait"):
        pass
    t2.finish()
    t.finish()
    spans = {s["name"]: s for s in tracing.spans(t.trace_id)}
    assert spans["tdt_serving_request"]["parent_id"] == \
        spans["tdt_fleet_placement"]["span_id"]
    assert spans["tdt_serving_queue_wait"]["parent_id"] == \
        spans["tdt_serving_request"]["span_id"]


def test_continue_trace_honors_sender_sampling_and_none():
    # Unsampled sender: flags 00 -> the receiver no-ops regardless of its
    # own sampler (one fleet request is one trace everywhere or nowhere).
    car = tracing.inject(tracing.NOOP_TRACE)
    assert car["traceparent"].endswith("-00")
    ctx = tracing.extract(car)
    assert ctx is None  # zero ids: NOOP injects nothing usable
    t = tracing.continue_trace(
        tracing.SpanContext(123, 45, sampled=False), "tdt_serving_request"
    )
    assert t is tracing.NOOP_TRACE
    # No carrier at all: plain local trace, standalone serving unchanged.
    t2 = tracing.continue_trace(None, "tdt_serving_request")
    assert t2.sampled and tracing.spans(t2.trace_id, include_open=True)


def test_remote_trace_ids_do_not_collide_with_local():
    """Local ids count 1,2,3... per process; a propagated trace id must be
    drawn from a range that cannot collide across processes."""
    local = tracing.start_trace("tdt_test_trace")
    remote = tracing.start_remote_trace("tdt_fleet_request")
    assert remote.trace_id != local.trace_id
    assert remote.trace_id > 2**32  # 63-bit random, never a tiny counter
    assert tracing.parse_trace_id(f"{remote.trace_id:032x}") == remote.trace_id
    assert tracing.parse_trace_id(str(local.trace_id)) == local.trace_id
    assert tracing.parse_trace_id("zz") is None


def test_merge_chrome_builds_one_timeline_across_pids():
    t = tracing.start_remote_trace("tdt_fleet_request")
    with t.span("tdt_fleet_placement") as psp:
        car = tracing.inject(t, span_id=psp["span_id"])
    router_spans = tracing.spans(t.trace_id, include_open=True)
    # Fake the replica side: shift ids as a second process would have them.
    ctx = tracing.extract(car)
    replica_spans = [{
        "trace_id": ctx.trace_id, "span_id": 1, "parent_id": ctx.span_id,
        "name": "tdt_serving_request", "start_s": 5.0, "end_s": None,
        "attrs": {"req_id": 0},
    }]
    doc = tracing.merge_chrome([
        {"label": "router", "pid": 0, "spans": router_spans},
        {"label": "replica0 pid=999", "pid": 1, "spans": replica_spans},
        {"label": "empty", "pid": 2, "spans": []},
    ], trace_id=t.trace_id)
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [m["args"]["name"] for m in metas] == ["router", "replica0 pid=999"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    assert all(e["ts"] >= 0 for e in xs)     # normalized across segments
    serving = next(e for e in xs if e["name"] == "tdt_serving_request")
    placement = next(e for e in xs if e["name"] == "tdt_fleet_placement")
    # The cross-process parent link survives the merge machine-checkably.
    assert serving["args"]["parent_id"] == placement["args"]["span_id"]
    assert serving["args"]["open"] is True   # open spans render to t_end
    # A foreign trace filters out entirely.
    empty = tracing.merge_chrome(
        [{"label": "router", "pid": 0, "spans": router_spans}], trace_id=42
    )
    assert empty["traceEvents"] == []


# =========================================== two sinks, self time, jit misses


def _host_events(log_dir) -> dict[str, list]:
    """{name: [(start_ns, end_ns)]} of the ``tdt_*`` events the profiler
    wrote on host lines, read back with nothing but jax."""
    import glob

    (path,) = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    found: dict[str, list] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tdt_"):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return found


def _profile(log_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(log_dir)


def _self_seconds() -> dict[str, tuple[float, int]]:
    return {
        e["labels"]["phase"]: (e["sum"], e["n"])
        for e in telemetry.snapshot()["digests"].get("tdt_span_self_seconds", [])
    }


def test_spans_reach_the_profiler_nested_as_in_the_ring(tmp_path):
    """One span API, two sinks: a span opened while a profiler session runs
    is an event of its own name on a host line of the profiler's trace,
    inside its parent's — ring span, ring-less span and a lower layer's
    ``span_current`` alike. A retroactive ``record`` has no block and stays
    in the ring only."""
    t = tracing.start_trace("tdt_test_trace")

    def body():
        with t.span("tdt_test_outer"):
            with t.span("tdt_test_phase", ring=False):
                with tracing.span_current("tdt_test_lower"):
                    jax.block_until_ready(jax.numpy.ones((8,)) + 1)
            t.record("tdt_test_retro", tracing.now_s() - 0.001, tracing.now_s())

    ev = _profile(tmp_path, body)
    assert {"tdt_test_outer", "tdt_test_phase", "tdt_test_lower"} <= set(ev)
    assert "tdt_test_retro" not in ev
    (outer,), (phase,), (lower,) = (
        ev["tdt_test_outer"], ev["tdt_test_phase"], ev["tdt_test_lower"])
    assert outer[0] <= phase[0] <= lower[0] <= lower[1] <= phase[1] <= outer[1]
    # The ring holds what it held before, the ring span and the record;
    # the two ring-less spans are in neither table (the root is still open).
    names = {s["name"]: s for s in tracing.spans(t.trace_id, include_open=True)}
    names.pop("tdt_jit_lowering", None)  # where an engine was built before
    assert set(names) == {"tdt_test_trace", "tdt_test_outer", "tdt_test_retro"}
    assert names["tdt_test_outer"]["self_s"] <= (outer[1] - outer[0]) / 1e9


def test_self_time_on_a_nest_of_three():
    import time

    t = tracing.start_trace("tdt_test_trace")
    with t.span("tdt_test_a") as a:
        time.sleep(0.02)
        with t.span("tdt_test_b", ring=False) as b:
            time.sleep(0.03)
            with tracing.span_current("tdt_test_c") as c:
                time.sleep(0.04)
            with tracing.span_current("tdt_test_c"):
                time.sleep(0.01)
        t.record("tdt_test_retro", a["start_s"], tracing.now_s())  # covers nothing
    dur = {s["name"]: s["end_s"] - s["start_s"] for s in (a, b, c)}
    assert c["self_s"] == pytest.approx(dur["tdt_test_c"]) and 0.04 <= c["self_s"] < 0.06
    assert 0.03 <= b["self_s"] < 0.05
    assert b["self_s"] == pytest.approx(dur["tdt_test_b"] - dur["tdt_test_c"] - 0.01, abs=5e-3)
    assert 0.02 <= a["self_s"] < 0.04
    assert a["self_s"] == pytest.approx(dur["tdt_test_a"] - dur["tdt_test_b"], abs=1e-6)
    # The lower layer's spans hang off the nearest span the ring holds.
    assert c["parent_id"] == b["parent_id"] == a["span_id"]
    # One digest, a phase label a span name: a window reads as a difference.
    got = _self_seconds()
    assert got["tdt_test_a"] == (pytest.approx(a["self_s"]), 1)
    assert got["tdt_test_b"] == (pytest.approx(b["self_s"]), 1)
    assert got["tdt_test_c"][1] == 2
    assert sum(s for s, _ in got.values()) == pytest.approx(dur["tdt_test_a"], abs=1e-6)


@pytest.mark.parametrize("off", ["telemetry_off", "unsampled"])
def test_off_is_off_in_both_sinks(off, tmp_path, monkeypatch):
    """``TDT_TELEMETRY=0`` (the cached switch) and an unsampled trace open
    neither sink: no annotation in the profiler's trace, no digest moves."""
    if off == "telemetry_off":
        telemetry.reset(enabled_override=False)
    else:
        monkeypatch.setenv("TDT_TRACE_SAMPLE", "0")
    t = tracing.start_trace("tdt_test_trace")
    assert not t.sampled

    def body():
        with t.span("tdt_test_outer") as sp:
            assert sp is None and tracing.current_span() is None
            with t.span("tdt_test_phase", ring=False):
                with tracing.span_current("tdt_test_lower") as low:
                    assert low is None

    assert _profile(tmp_path, body) == {}
    assert _self_seconds() == {} and tracing.spans() == []


def test_request_span_keeps_its_tree_inside_the_servers_iteration():
    """A request's span opened while a span of the SERVER's trace is ambient
    (the loop's iteration) is parented in its own trace; the iteration
    still holds it: its time is not the iteration's self time."""
    import time

    server = tracing.start_trace("tdt_test_server")
    req = tracing.start_trace("tdt_test_request")
    with server.span("tdt_test_step", ring=False) as step:
        with req.span("tdt_test_prefill") as pf:
            time.sleep(0.02)
            with tracing.span_current("tdt_test_engine") as eng:
                pass
        pid = req.point("tdt_test_finish")
        with server.span("tdt_test_dispatch") as dsp:
            assert tracing.current_correlation() == (server.trace_id, dsp["span_id"])
    assert pf["parent_id"] == req.root_id and pf["trace_id"] == req.trace_id
    assert eng["trace_id"] == req.trace_id and eng["parent_id"] == pf["span_id"]
    assert dsp["parent_id"] == server.root_id
    assert {s["span_id"]: s for s in tracing.spans()}[pid]["parent_id"] == req.root_id
    assert step["self_s"] < 0.01 < pf["self_s"]


def test_jit_cache_misses_are_counted_and_pointed():
    tracing.watch_lowerings()
    tracing.watch_lowerings()  # idempotent: one listener however often asked
    t = tracing.start_trace("tdt_test_trace")

    def fresh(x):
        return x * 3 + 1

    f = jax.jit(fresh)
    vec = jax.numpy.ones((3,))  # its own little program lowers here
    n0 = telemetry.counter_total("tdt_jit_lowerings_total")
    with t.span("tdt_test_step", ring=False):
        with t.span("tdt_test_dispatch") as dsp:
            f(1.0)
            f(2.0)  # a cache hit lowers nothing
    assert telemetry.counter_total("tdt_jit_lowerings_total") == n0 + 1
    (pt,) = [s for s in tracing.spans(t.trace_id) if s["name"] == "tdt_jit_lowering"]
    assert pt["parent_id"] == dsp["span_id"] and "fresh" in pt["attrs"]["fun_name"]
    f(vec)  # a miss outside any span: counted, no point
    assert telemetry.counter_total("tdt_jit_lowerings_total") == n0 + 2
    assert len([s for s in tracing.spans() if s["name"] == "tdt_jit_lowering"]) == 1


# ============================================================ device ledger


class _ScriptedClock:
    """``tracing.now_s`` by hand: the test sets ``t``; every read counts."""

    def __init__(self, t=100.0):
        self.t, self.reads = t, 0

    def __call__(self):
        self.reads += 1
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _ScriptedClock()
    monkeypatch.setattr(tracing, "now_s", c)
    return c


def _starved_by(kind: str, name: str, label: str) -> dict[str, float]:
    field = "value" if kind == "counters" else "sum"
    return {e["labels"][label]: e[field]
            for e in telemetry.snapshot()[kind].get(name, [])}


def _starved_counter():
    return _starved_by("counters", "tdt_engine_device_starved_seconds_total", "after")


def _starved_phases():
    return _starved_by("digests", "tdt_span_starved_seconds", "phase")


def test_ledger_on_a_scripted_clock_through_a_nest_of_spans(clock):
    """Issue, wait, issue inside three nested spans (a ring span, a
    ring-less one, a lower layer's ``span_current``): each span's starved
    self time is its share of the ledger's total with its children's taken
    out, and the phases' sum plus the starved time outside any span is the
    counter."""
    t = tracing.start_trace("tdt_test_trace")
    first = tracing.device_issued()  # nothing was known: no interval ends
    assert first == 1 and tracing.device_starved_s() == 0.0
    clock.t = 101.0
    with t.span("tdt_test_a") as a:  # opens while the device is busy
        clock.t = 102.0
        tracing.device_waited(first, "prefill_chunk")  # starved from 102
        clock.t = 103.0
        with t.span("tdt_test_b", ring=False) as b:
            clock.t = 104.0
            with tracing.span_current("tdt_test_c") as c:
                clock.t = 105.0
                second = tracing.device_issued()  # 102-105 after the chunk's fence
                clock.t = 106.0
            clock.t = 107.0
            tracing.device_waited(second, "decode_land:finish")
            clock.t = 108.0
        clock.t = 109.0
    assert (a["starved_s"], b["starved_s"], c["starved_s"]) == (2.0, 2.0, 1.0)
    assert (a["self_s"], b["self_s"], c["self_s"]) == (3.0, 3.0, 2.0)
    clock.t = 110.0  # a second outside any span, the device still starved
    assert tracing.device_starved_s() == 6.0  # the open interval is in the total
    third = tracing.device_issued()
    assert _starved_counter() == {"prefill_chunk": 3.0, "decode_land:finish": 3.0}
    phases = _starved_phases()
    assert phases == {"tdt_test_a": 2.0, "tdt_test_b": 2.0, "tdt_test_c": 1.0}
    outside = 1.0  # 109-110
    assert sum(phases.values()) + outside == sum(_starved_counter().values())
    assert tracing.device_starved_s() == 6.0 and third == 3
    # the ring keeps the span's share beside its self time
    ring = {s["name"]: s for s in tracing.spans(t.trace_id)}
    assert ring["tdt_test_a"]["starved_s"] == 2.0 and "tdt_test_b" not in ring


def test_ledger_counts_no_work_apart_and_waits_for_the_newest_program(clock):
    """``no_work`` is no span's starved time and not in the total; a wait
    for a program that is not the newest issued changes nothing (the chunk
    landed behind the next one's issue), and costs no clock read; nor does
    a span's open or close beyond the two it always made."""
    t = tracing.start_trace("tdt_test_trace")
    one = tracing.device_issued()
    clock.t = 111.0
    tracing.device_waited(one, "cache_scatter")
    clock.t = 112.0
    tracing.device_no_work()  # 111-112 stays the scatter's; no_work from here
    reads = clock.reads
    tracing.device_no_work()  # nothing new to say, no clock read
    assert clock.reads == reads and tracing.device_starved_s() == 1.0
    reads = clock.reads
    clock.t = 113.0
    with t.span("tdt_test_idle", ring=False) as idle:
        clock.t = 115.0
    assert idle["starved_s"] == 0.0 and clock.reads == reads + 2  # open, close
    clock.t = 116.0
    two = tracing.device_issued()
    assert _starved_counter() == {"cache_scatter": 1.0, "no_work": 4.0}
    assert _starved_phases() == {}  # observed only where it is over zero
    clock.t = 117.0
    three = tracing.device_issued()  # issued behind ``two``, which is in flight
    clock.t = 118.0
    reads = clock.reads
    tracing.device_waited(two, "decode_land")  # not the newest: still busy
    assert clock.reads == reads and tracing.device_starved_s() == 1.0
    with t.span("tdt_test_busy", ring=False) as busy:
        clock.t = 119.0
    assert busy["starved_s"] == 0.0 and busy["self_s"] == 1.0
    clock.t = 120.0
    tracing.device_waited(three, "decode_land:drain")
    tracing.device_waited(three, "decode_land:drain")  # a second wait moves nothing
    clock.t = 122.0
    assert tracing.device_starved_s() == 3.0
    tracing.device_issued()
    assert _starved_counter()["decode_land:drain"] == 2.0
    tracing.reset()
    assert tracing.device_starved_s() == 0.0 and tracing.device_issued() == 1


def test_ledger_programs_nobody_waits_for_open_no_interval(clock):
    """A decode chunk in flight, two prefill chunks issued behind it and
    never waited for, the next decode chunk behind them: the landing of the
    first chunk, a wait for an older program, opens nothing; the device is
    known starved only from the wait for the newest program issued, and the
    interval is that wait's. The unwaited programs cost the ledger nothing
    but their tickets: its total stays a lower bound."""
    t = tracing.start_trace("tdt_test_trace")
    chunk = tracing.device_issued()
    clock.t = 101.0
    unwaited = [tracing.device_issued(), tracing.device_issued()]
    clock.t = 102.0
    nxt = tracing.device_issued()
    clock.t = 103.0
    reads = clock.reads
    with t.span("tdt_test_landing", ring=False) as landing:
        tracing.device_waited(chunk, "decode_land")  # three programs behind it
        clock.t = 104.0
    assert landing["starved_s"] == 0.0 and clock.reads == reads + 2  # open, close
    assert tracing.device_starved_s() == 0.0 and _starved_counter() == {}
    assert unwaited == [chunk + 1, chunk + 2] and nxt == chunk + 3
    # a prompt's last chunk, issued behind all of it and waited for: the
    # newest, so the device is starved from the fence's return; the landing
    # of the chunk in flight after it finds an interval open and moves nothing
    last = tracing.device_issued()
    clock.t = 107.0
    tracing.device_waited(last, "prefill_chunk")
    clock.t = 108.0
    tracing.device_waited(nxt, "decode_land:prefill")
    clock.t = 109.5
    tracing.device_issued()  # the pool scatter
    assert _starved_counter() == {"prefill_chunk": 2.5}
    assert tracing.device_starved_s() == 2.5


def test_ledger_is_off_with_telemetry_off(clock):
    telemetry.reset(enabled_override=False)
    reads = clock.reads
    ticket = tracing.device_issued()
    tracing.device_waited(ticket, "prefill_chunk")
    tracing.device_no_work()
    clock.t = 200.0
    assert ticket == 0 and tracing.device_issued() == 0
    assert clock.reads == reads and tracing.device_starved_s() == 0.0
    snap = telemetry.snapshot()
    assert "tdt_engine_device_starved_seconds_total" not in snap["counters"]
    assert "tdt_span_starved_seconds" not in snap["digests"]


def test_engine_tells_the_ledger_each_step_program_and_each_fence(model1):
    """A join and a decode chunk through the engine's own calls: each step
    program takes a ticket, each fence that leaves nothing in flight opens
    an interval named after it, and the next issue books it."""
    from paged_drive import alloc_chains, join

    eng = make_engine(model1)
    paged = alloc_chains(eng, 1)
    t = tracing.start_trace("tdt_test_trace")
    with t.span("tdt_test_step", ring=False):
        tok, paged = join(eng, paged, 0, [3, 17, 42])
        eng.decode_steps_paged(
            paged, jax.numpy.asarray([tok], jax.numpy.int32),
            jax.numpy.asarray([2], jax.numpy.int32), 2)
    after = _starved_counter()
    # the chunk's fence, then the scatter's; the landing's interval is open
    assert set(after) == {"prefill_chunk", "cache_scatter"}
    assert tracing.device_starved_s() > sum(after.values()) > 0.0
    phases = _starved_phases()
    assert {"tdt_engine_complete_paged_prefill", "tdt_engine_dispatch"} <= set(phases)



def test_engine_leaves_a_chunk_that_is_not_the_last_to_the_device(model1, monkeypatch):
    """``prefill_chunk_state(..., wait=False)`` takes a ticket, enters no
    fence, stamps no ``admission`` and opens no starved interval; the call
    for the prompt's last chunk is the fence it always was, and the two
    routes run one program on the same operands: the logits are the fenced
    calls' to the bit."""
    eng = make_engine(model1)
    ids = jax.numpy.asarray([[3, 17, 42, 7]], jax.numpy.int32)
    fences = []
    inner = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: fences.append(1) or inner(x))
    admissions = lambda: sum(
        e["n"] for e in telemetry.snapshot()["digests"].get("tdt_engine_phase_seconds", [])
        if e["labels"]["phase"] == "admission")
    _, kbuf, vbuf, state = eng.prefill_chunk_state(
        *eng.paged_kbuf_zeros(8), ids, 0, 3, (), wait=False)
    assert not fences and admissions() == 0 and _starved_counter() == {}
    assert tracing.device_starved_s() == 0.0 and tracing.device_issued() == 2
    assert eng._unwaited_stats == []  # a dense model's chunk has no counters
    logits, *_ = eng.prefill_chunk_state(kbuf, vbuf, ids, 4, 3, state)
    assert len(fences) == 1 and admissions() == 1 and tracing.device_starved_s() > 0.0
    _, kbuf, vbuf, state = eng.prefill_chunk_state(*eng.paged_kbuf_zeros(8), ids, 0, 3, ())
    want, *_ = eng.prefill_chunk_state(kbuf, vbuf, ids, 4, 3, state)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
