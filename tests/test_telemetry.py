"""Telemetry tests: registry semantics, no-op path, serve-path histograms,
chaos abort counters, the kernel-trace round trip, and the metric-name lint.

The registry is process-global (like the degradation registry), so every
test starts and ends from a clean reset; the kernel-trace test additionally
clears jit caches because ``TDT_KERNEL_TRACE`` is a trace-time flag that
does not participate in jit cache keys (the FaultPlan rule).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.runtime import resilience, telemetry

LINT = "scripts/check_metric_names.py"


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    yield
    telemetry.reset()
    resilience.reset_degradation()


def shard(ctx, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
    )


# ------------------------------------------------------------------ registry


def test_counter_labels_are_distinct_series():
    telemetry.inc("tdt_test_ops_total", backend="xla")
    telemetry.inc("tdt_test_ops_total", backend="xla")
    telemetry.inc("tdt_test_ops_total", backend="dist")
    assert telemetry.counter_value("tdt_test_ops_total", backend="xla") == 2.0
    assert telemetry.counter_value("tdt_test_ops_total", backend="dist") == 1.0
    # Label ORDER does not matter, label VALUES are str-coerced.
    telemetry.inc("tdt_test_pairs_total", a=1, b="x")
    assert telemetry.counter_value("tdt_test_pairs_total", b="x", a="1") == 1.0


def test_histogram_bucketing_and_snapshot():
    telemetry.observe("tdt_test_lat_seconds", 0.001)
    telemetry.observe("tdt_test_lat_seconds", 0.5)
    telemetry.observe("tdt_test_lat_seconds", 1e9)  # lands in +Inf
    snap = telemetry.snapshot()
    (entry,) = snap["histograms"]["tdt_test_lat_seconds"]
    assert entry["count"] == 3
    assert entry["sum"] == pytest.approx(0.501 + 1e9)
    buckets = entry["buckets"]
    # Cumulative: monotone nondecreasing, +Inf last covers everything.
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 3
    # 0.001 <= 2^-9; the finite buckets hold exactly two observations.
    finite_total = buckets[-2][1]
    assert finite_total == 2


def test_event_ring_bounded_and_filtered(monkeypatch):
    monkeypatch.setenv("TDT_EVENT_RING", "4")
    telemetry.reset()
    for i in range(10):
        telemetry.emit("tick", i=i)
    telemetry.emit("other", note="x")
    evs = telemetry.events()
    assert len(evs) == 4  # bounded ring
    assert telemetry.events(kind="other")[0]["note"] == "x"
    # seq keeps counting across evictions; fields are JSON-primitive.
    assert evs[-1]["seq"] == 11
    telemetry.emit("coerced", obj=object())
    assert isinstance(telemetry.events(kind="coerced")[0]["obj"], str)


def test_disabled_is_noop():
    telemetry.reset(enabled_override=False)
    assert not telemetry.enabled()
    telemetry.inc("tdt_test_ops_total")
    telemetry.observe("tdt_test_lat_seconds", 1.0)
    telemetry.set_gauge("tdt_test_level", 3.0)
    telemetry.observe_digest("tdt_test_lat2_seconds", 1.0)
    telemetry.emit("tick")
    assert telemetry.counter_value("tdt_test_ops_total") == 0.0
    assert telemetry.digest_quantile("tdt_test_lat2_seconds", 0.5) is None
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    assert snap["gauges"] == {} and snap["events"] == []
    assert snap["digests"] == {}
    assert telemetry.summary()["counters"] == {}


def test_env_flag_disables(monkeypatch):
    monkeypatch.setenv("TDT_TELEMETRY", "0")
    telemetry.reset()
    assert not telemetry.enabled()
    assert not telemetry.kernel_trace_enabled()  # master gate wins
    # Instrumented call sites (engine serve path gates its fences on this)
    # execute the early-return path.
    telemetry.inc("tdt_engine_serve_total", backend="xla")
    assert telemetry.snapshot()["counters"] == {}


def test_prometheus_exposition():
    telemetry.inc("tdt_test_ops_total", backend="xla")
    telemetry.set_gauge("tdt_test_level", 2.5)
    telemetry.observe("tdt_test_lat_seconds", 0.25)
    text = telemetry.to_prometheus()
    assert "# TYPE tdt_test_ops_total counter" in text
    assert 'tdt_test_ops_total{backend="xla"} 1' in text
    assert "# TYPE tdt_test_level gauge" in text
    assert "# TYPE tdt_test_lat_seconds histogram" in text
    assert 'tdt_test_lat_seconds_bucket{le="0.25"} 1' in text
    assert 'tdt_test_lat_seconds_bucket{le="+Inf"} 1' in text
    assert "tdt_test_lat_seconds_sum 0.25" in text
    assert "tdt_test_lat_seconds_count 1" in text
    # The exporter renders foreign (dumped) snapshots too — the CLI path.
    again = telemetry.to_prometheus(json.loads(json.dumps(telemetry.snapshot())))
    assert again == text


def test_dump_and_cli_show(tmp_path):
    telemetry.inc("tdt_test_ops_total", backend="xla")
    telemetry.observe("tdt_test_lat_seconds", 0.01)
    telemetry.emit("tick", i=1)
    path = telemetry.dump(str(tmp_path / "snap.json"))
    r = subprocess.run(
        [sys.executable, "scripts/tdt_metrics.py", "show", path],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tdt_test_ops_total{backend=xla} = 1" in r.stdout
    assert "tdt_test_lat_seconds" in r.stdout and "tick" in r.stdout


# ------------------------------------------------------------------- digests


def _oracle(samples, q):
    """The sorted-list oracle at the digest's rank convention."""
    s = sorted(samples)
    return s[int(q * (len(s) - 1))]


def test_digest_relative_error_bound_vs_oracle():
    """Acceptance: every documented quantile of a 10k+ heavy-tailed sample
    is within DIGEST_ALPHA relative error of the sorted-list oracle."""
    rng = np.random.default_rng(7)
    samples = [float(v) for v in rng.lognormal(-3.0, 1.0, size=12_000)]
    d = telemetry.Digest()
    for v in samples:
        d.add(v)
    assert d.n == len(samples)
    for q in telemetry.DIGEST_QUANTILES:
        oracle = _oracle(samples, q)
        est = d.quantile(q)
        assert abs(est - oracle) / oracle <= telemetry.DIGEST_ALPHA, (
            q, est, oracle)
    # Estimates are clamped into the observed range.
    assert min(samples) <= d.quantile(0.999) <= max(samples)


def test_digest_merge_associative_commutative():
    """Merging per-replica digests is order- and grouping-independent and
    equals the single-observer digest EXACTLY (bucket-for-bucket), so
    fleet-wide percentiles from /fleet/metrics equal the single-digest
    answer bit-for-bit."""
    rng = np.random.default_rng(11)
    samples = [float(v) for v in rng.lognormal(-3.5, 0.8, size=4_000)]
    single = telemetry.Digest()
    shards = [telemetry.Digest() for _ in range(4)]
    for i, v in enumerate(samples):
        single.add(v)
        shards[i % 4].add(v)

    def merged(order):
        out = telemetry.Digest()
        for k in order:
            out.merge(shards[k])
        return out

    a = merged([0, 1, 2, 3])                      # left fold
    b = merged([3, 1, 0, 2])                      # permuted: commutativity
    ab = telemetry.Digest()                       # pairwise: associativity
    ab.merge(shards[0]); ab.merge(shards[1])
    cd = telemetry.Digest()
    cd.merge(shards[2]); cd.merge(shards[3])
    ab.merge(cd)
    for m in (a, b, ab):
        assert m.buckets == single.buckets and m.zero == single.zero
        assert (m.n, m.min, m.max) == (single.n, single.min, single.max)
        for q in telemetry.DIGEST_QUANTILES:
            assert m.quantile(q) == single.quantile(q)
    # Mixed-alpha merges are refused: they would silently break the bound.
    with pytest.raises(ValueError):
        telemetry.Digest(alpha=0.05).merge(single)


def test_digest_registry_snapshot_and_prometheus():
    """observe_digest lands in the registry; digests ride snapshot() (JSON
    round-trip exact), render as Prometheus summary lines, and merge
    across label sets via digest_merged."""
    for v in (0.010, 0.020, 0.030, 0.040):
        telemetry.observe_digest("tdt_test_lat2_seconds", v, tenant="a")
    telemetry.observe_digest("tdt_test_lat2_seconds", 0.050, tenant="b")
    assert telemetry.digest_quantile(
        "tdt_test_lat2_seconds", 0.5, tenant="a") == pytest.approx(
            0.020, rel=telemetry.DIGEST_ALPHA)
    merged = telemetry.digest_merged("tdt_test_lat2_seconds")
    assert merged.n == 5

    snap = json.loads(json.dumps(telemetry.snapshot()))
    entries = snap["digests"]["tdt_test_lat2_seconds"]
    assert {e["labels"]["tenant"] for e in entries} == {"a", "b"}
    e_a = next(e for e in entries if e["labels"]["tenant"] == "a")
    d_a = telemetry.Digest.from_dict(e_a)
    assert d_a.quantile(0.5) == telemetry.digest_quantile(
        "tdt_test_lat2_seconds", 0.5, tenant="a")
    assert e_a["quantiles"]["p50"] == d_a.quantile(0.5)

    text = telemetry.to_prometheus()
    assert "# TYPE tdt_test_lat2_seconds summary" in text
    assert 'tdt_test_lat2_seconds{tenant="a",quantile="0.5"}' in text
    assert 'tdt_test_lat2_seconds_count{tenant="a"} 4' in text
    # Foreign (dumped) snapshots render identically — the CLI path.
    assert telemetry.to_prometheus(snap) == text


def test_digest_edge_values():
    d = telemetry.Digest()
    assert d.quantile(0.5) is None                 # empty: no answer
    d.add(0.0)                                     # zero bucket
    d.add(-1.0)                                    # clamped negative
    d.add(0.25)
    assert d.n == 3 and d.zero == 2
    # Ranks 0-1 land in the zero bucket (2 of 3 values), rank 2 in the
    # positive range — and estimates clamp into [min, max].
    assert d.quantile(0.0) <= 0.0 and d.quantile(0.5) <= 0.0
    assert d.quantile(1.0) == pytest.approx(0.25, rel=telemetry.DIGEST_ALPHA)


# ------------------------------------------------------------ wired-in sites


def test_auto_routing_counters():
    from triton_dist_tpu.kernels.allreduce import get_auto_all_reduce_method

    m = get_auto_all_reduce_method(1024, 4)
    assert telemetry.counter_value(
        "tdt_kernels_auto_route_total", collective="allreduce", method=m.value
    ) == 1.0


def test_degradation_and_fallback_counters():
    resilience.mark_degraded("gemm_ar", "test reason")
    assert telemetry.counter_value(
        "tdt_resilience_degradations_total", feature="gemm_ar"
    ) == 1.0
    assert telemetry.events(kind="degraded")[0]["feature"] == "gemm_ar"
    # note_fallback_once dedups the LOG line but counts every occurrence —
    # fallback traffic volume is the operational signal.
    resilience.note_fallback_once("site.a", "why")
    resilience.note_fallback_once("site.a", "why")
    assert telemetry.counter_value(
        "tdt_resilience_fallbacks_total", site="site.a"
    ) == 2.0
    assert len(telemetry.events(kind="fallback")) == 1


def test_record_status_abort_counter():
    words = [resilience.STATUS_ABORT, resilience.phase_id("ag_recv"), 3, 123]
    with pytest.raises(Exception):
        resilience.record_status(words, feature="allgather", kernel="_ring_ag_kernel")
    assert telemetry.counter_value(
        "tdt_resilience_aborts_total", feature="allgather", phase="ag_recv", peer=3
    ) == 1.0
    ev = telemetry.events(kind="collective_abort")[0]
    assert ev["phase"] == "ag_recv" and ev["peer"] == 3


@pytest.fixture(scope="module")
def dense_model(request):
    import tests.conftest  # ensure CPU devices

    from triton_dist_tpu.models import DenseLLM, PRESETS
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((4,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    cfg = PRESETS["test-dense"]
    return DenseLLM(cfg, ctx, key=jax.random.PRNGKey(1))


def test_serve_latency_histograms(dense_model):
    from triton_dist_tpu.models import Engine

    eng = Engine(dense_model, backend="xla", max_len=32)
    assert telemetry.counter_value("tdt_engine_rebuilds_total", backend="xla") == 1.0
    ids = jnp.asarray([[3, 17, 42, 7, 99, 5, 23, 11]], jnp.int32)
    out = eng.serve(ids, gen_len=6)
    assert out.shape == (1, 6)
    assert telemetry.counter_value("tdt_engine_serve_total", backend="xla") == 1.0
    snap = telemetry.snapshot()
    for name in ("tdt_engine_ttft_seconds", "tdt_engine_decode_token_seconds"):
        (entry,) = snap["histograms"][name]
        assert entry["labels"] == {"backend": "xla"}
        assert entry["count"] >= 1 and entry["sum"] > 0.0
    # The summary digest (what bench.py attaches) carries the same series.
    s = telemetry.summary()
    assert s["histograms"]['tdt_engine_ttft_seconds{backend="xla"}']["count"] >= 1


# ============================================================= chaos (device)

CHAOS_BOUND = 2_000
VICTIM = 1
W4 = 4


@pytest.mark.chaos
def test_chaos_abort_counter_labeled(ctx4, rng):
    """The acceptance scenario: after a dropped-peer abort, the snapshot
    shows ``tdt_resilience_aborts_total`` labeled with the stalled phase and
    observed peer."""
    from triton_dist_tpu.kernels import AllGatherMethod, all_gather_shard

    f = shard(
        ctx4,
        lambda xs: all_gather_shard(xs, axis="tp", method=AllGatherMethod.RING_1D)
        .reshape(-1, xs.shape[-1]),
        (P("tp"),),
        P(),
    )
    x = jnp.asarray(rng.standard_normal((W4 * 8, 64)), jnp.float32)
    with resilience.fault_plan("drop_peer", rank=VICTIM, wait_bound=CHAOS_BOUND):
        with pytest.raises(Exception):
            jax.block_until_ready(f(x))
    ab = resilience.last_abort()
    assert ab is not None
    assert telemetry.counter_value(
        "tdt_resilience_aborts_total",
        feature=ab.feature, phase=ab.phase, peer=ab.peer,
    ) >= 1.0
    entries = telemetry.snapshot()["counters"]["tdt_resilience_aborts_total"]
    assert any(e["labels"]["phase"] == ab.phase for e in entries)
    jax.clear_caches()  # a degraded trace must not leak into later tests


# ------------------------------------------------------- kernel trace (device)


@pytest.fixture
def kernel_trace_env(monkeypatch):
    """TDT_KERNEL_TRACE is a trace-time flag outside the jit cache key:
    clear caches around the flip so both this test and its successors
    compile with the setting they expect."""
    monkeypatch.setenv("TDT_KERNEL_TRACE", "1")
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_kernel_trace_roundtrip_allgather(ctx4, rng, kernel_trace_env, tmp_path):
    from triton_dist_tpu.kernels import AllGatherMethod, all_gather_shard
    from triton_dist_tpu.tools import profiler

    assert telemetry.kernel_trace_enabled()
    f = shard(
        ctx4,
        lambda xs: all_gather_shard(xs, axis="tp", method=AllGatherMethod.RING_1D)
        .reshape(-1, xs.shape[-1]),
        (P("tp"),),
        P(),
    )
    x = jnp.asarray(rng.standard_normal((W4 * 8, 64)), jnp.float32)
    out = jax.block_until_ready(f(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=0, atol=0)

    recs = telemetry.kernel_traces(kernel="_ring_ag_kernel")
    assert {r["rank"] for r in recs} == set(range(W4))  # one buffer per rank
    for r in recs:
        assert r["n_dropped"] == 0
        tags = [e["tag"] for e in r["events"]]
        # Entry barrier in/out, then per ring step: send, wait, recv.
        assert tags.count(profiler.TAG_BARRIER) >= 2
        assert tags.count(profiler.TAG_SEND) == W4 - 1
        assert tags.count(profiler.TAG_WAIT) == W4 - 1
        assert tags.count(profiler.TAG_RECV) == W4 - 1
        # Ordering, not wall time: each wait is satisfied before the next.
        seqs = [e["seq"] for e in r["events"]]
        assert seqs == sorted(seqs)

    ct = profiler.decode_to_chrome(recs)
    path = ct.save(str(tmp_path / "ktrace.json"))
    data = json.load(open(path))
    assert len(data["traceEvents"]) == sum(len(r["events"]) for r in recs)
    pids = {e["pid"] for e in data["traceEvents"]}
    assert pids == set(range(W4))  # one chrome row per rank


def test_kernel_trace_off_means_no_buffers(ctx4, rng):
    """Flag unset: maybe_kernel_trace returns None and kernels keep their
    exact pre-trace signature — nothing is collected."""
    from triton_dist_tpu.kernels import AllGatherMethod, all_gather_shard

    assert telemetry.maybe_kernel_trace() is None
    f = shard(
        ctx4,
        lambda xs: all_gather_shard(xs, axis="tp", method=AllGatherMethod.FULL_MESH_PUSH)
        .reshape(-1, xs.shape[-1]),
        (P("tp"),),
        P(),
    )
    x = jnp.asarray(rng.standard_normal((W4 * 8, 32)), jnp.float32)
    jax.block_until_ready(f(x))
    assert telemetry.kernel_traces() == []


# ------------------------------------------------------------------ name lint


def test_metric_name_lint_repo_is_clean():
    r = subprocess.run([sys.executable, LINT], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_metric_name_lint_flags_violations(tmp_path):
    bad = tmp_path / "bad_site.py"
    bad.write_text(
        "from triton_dist_tpu.runtime import telemetry\n"
        "def f(name, shape):\n"
        "    telemetry.inc(name)\n"  # dynamic metric name
        "    telemetry.inc(f'tdt_x_{shape}_total')\n"  # interpolated name
        "    telemetry.inc('my_counter')\n"  # missing tdt_ prefix
        "    telemetry.inc('tdt_ops')\n"  # too few segments
        "    telemetry.emit('Bad-Kind')\n"  # not snake_case
        "    telemetry.inc('tdt_good_ops_total', shape=shape)\n"  # OK: label
        "    telemetry.inc(name)  # metric-name-ok: test waiver\n"
    )
    r = subprocess.run([sys.executable, LINT, str(bad)], capture_output=True, text=True)
    assert r.returncode == 1
    for line in (3, 4, 5, 6, 7):
        assert f"bad_site.py:{line}" in r.stdout, r.stdout
    for line in (8, 9):
        assert f"bad_site.py:{line}" not in r.stdout, r.stdout


def test_span_name_lint_flags_violations(tmp_path):
    """Span names ride the same registry discipline as metric names: the
    lint recognizes tracing call shapes (module fns and req.trace.span)."""
    bad = tmp_path / "bad_spans.py"
    bad.write_text(
        "from triton_dist_tpu.runtime import tracing\n"
        "def f(req, name):\n"
        "    t = tracing.start_trace('serving_request')\n"  # no tdt_ prefix
        "    with req.trace.span(name):\n"  # dynamic span name
        "        pass\n"
        "    req.trace.record('tdt_ok_span_name', 0.0, 1.0)\n"  # OK
        "    tracing.point_current('tdt_bad')\n"  # too few segments
        "    t.finish()\n"  # not a span-name call: ignored
    )
    r = subprocess.run([sys.executable, LINT, str(bad)], capture_output=True, text=True)
    assert r.returncode == 1
    for line in (3, 4, 7):
        assert f"bad_spans.py:{line}" in r.stdout, r.stdout
    for line in (6, 8):
        assert f"bad_spans.py:{line}" not in r.stdout, r.stdout


# ------------------------------------------------------- concurrent readers


def test_snapshot_paths_survive_concurrent_writes():
    """The introspection endpoint reads the registry and the span rings from
    a second thread while the serving loop writes — every reader must see a
    consistent copy (the thread-safety contract in telemetry's module doc).
    Hammer all reader paths against parallel writers and require zero
    exceptions and parseable output throughout."""
    import threading

    from triton_dist_tpu.runtime import tracing

    stop = threading.Event()
    errors: list[BaseException] = []

    def writer(tag: str):
        i = 0
        try:
            while not stop.is_set():
                telemetry.inc("tdt_test_stress_total", worker=tag)
                telemetry.set_gauge("tdt_test_stress_depth", float(i % 5))
                telemetry.observe("tdt_test_stress_seconds", 1e-3 * (i % 7 + 1))
                telemetry.observe_digest(
                    "tdt_test_stress_lat_seconds", 1e-3 * (i % 7 + 1),
                    worker=tag,
                )
                telemetry.emit("stress_tick", worker=tag, i=i)
                t = tracing.start_trace("tdt_test_stress_trace", worker=tag)
                with t.span("tdt_test_stress_child"):
                    tracing.point_current("tdt_test_stress_mark")
                t.finish()
                i += 1
        except BaseException as e:  # noqa: BLE001 - collected for the assert
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                snap = telemetry.snapshot()
                json.dumps(snap)  # JSON-safe all the way down
                telemetry.to_prometheus(snap)
                telemetry.summary()
                telemetry.events("stress_tick")
                telemetry.counter_total("tdt_test_stress_total")
                telemetry.digest_quantile(
                    "tdt_test_stress_lat_seconds", 0.99, worker="w0")
                telemetry.digest_merged("tdt_test_stress_lat_seconds")
                json.dumps(tracing.snapshot_traces())
                tracing.to_chrome()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(f"w{k}",)) for k in range(2)]
    threads += [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    import time as _time

    _time.sleep(0.6)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    # The writers actually wrote (the stress was real).
    assert telemetry.counter_total("tdt_test_stress_total") > 0


def test_counter_total_sums_across_label_sets():
    telemetry.inc("tdt_test_multi_total", peer=0)
    telemetry.inc("tdt_test_multi_total", peer=1)
    telemetry.inc("tdt_test_multi_total", 3.0, peer=1)
    assert telemetry.counter_total("tdt_test_multi_total") == 5.0
    assert telemetry.counter_total("tdt_test_absent_total") == 0.0


# ------------------------------------------------------------ flight recorder


def test_flight_recorder_roundtrip_and_wraparound(tmp_path):
    path = tmp_path / "flight.bin"
    fr = telemetry.FlightRecorder(path, capacity=8)
    for i in range(3):
        fr.append({"kind": "event", "i": i})
    recs = telemetry.FlightRecorder.read(path)
    assert [r["i"] for r in recs] == [0, 1, 2]
    assert all(r["pid"] == os.getpid() for r in recs)
    assert recs[0]["flight_seq"] == 1 and recs[0]["t_mono_s"] > 0
    # Ring wraps: only the newest `capacity` records survive, in order.
    for i in range(3, 20):
        fr.append({"kind": "event", "i": i})
    recs = telemetry.FlightRecorder.read(path)
    assert [r["i"] for r in recs] == list(range(12, 20))
    fr.close()


def test_flight_recorder_survives_no_close(tmp_path):
    """The SIGKILL property, minus the SIGKILL: records written with no
    close()/flush/atexit are readable from the file by another process —
    the mmap'd pages belong to the kernel once written."""
    path = tmp_path / "flight.bin"
    code = (
        "import sys; sys.path.insert(0, %r);"
        "from triton_dist_tpu.runtime import telemetry;"
        "fr = telemetry.FlightRecorder(%r, capacity=16);"
        "[fr.append({'kind': 'k', 'i': i}) for i in range(5)];"
        "import os; os.kill(os.getpid(), 9)"  # no close, no atexit
    ) % (os.getcwd(), str(path))
    p = subprocess.run([sys.executable, "-c", code])
    assert p.returncode == -9
    recs = telemetry.FlightRecorder.read(path)
    assert [r["i"] for r in recs] == list(range(5))


def test_flight_recorder_drops_torn_record(tmp_path):
    path = tmp_path / "flight.bin"
    fr = telemetry.FlightRecorder(path, capacity=8)
    for i in range(4):
        fr.append({"kind": "event", "i": i})
    fr.close()
    # Tear the LAST record mid-payload (what a kill during the final
    # memcpy leaves behind): reader must drop it, keep the rest.
    hdr = telemetry.FLIGHT_HEADER_BYTES
    rec = telemetry.FLIGHT_RECORD_BYTES
    with open(path, "r+b") as f:
        f.seek(hdr + 3 * rec + 12)
        f.write(b"\x00" * 40)
    recs = telemetry.FlightRecorder.read(path)
    assert [r["i"] for r in recs] == [0, 1, 2]
    # A file that is not a flight ring reads as empty, never raises.
    junk = tmp_path / "junk.bin"
    junk.write_bytes(b"not a flight ring")
    assert telemetry.FlightRecorder.read(junk) == []
    assert telemetry.FlightRecorder.read(tmp_path / "absent.bin") == []


def test_flight_recorder_truncates_oversized_payload(tmp_path):
    path = tmp_path / "flight.bin"
    fr = telemetry.FlightRecorder(path, capacity=4)
    fr.append({"kind": "big", "blob": "x" * 4096})
    fr.append({"kind": "small"})
    recs = telemetry.FlightRecorder.read(path)
    assert recs[0]["kind"] == "big" and recs[0]["truncated"] is True
    assert "blob" not in recs[0]             # stub, not torn JSON
    assert recs[1]["kind"] == "small"
    fr.close()


def test_emit_feeds_flight_recorder_when_enabled(tmp_path, monkeypatch):
    monkeypatch.setenv("TDT_FLIGHT_RECORDER", str(tmp_path))
    monkeypatch.setenv("TDT_FLIGHT_RECORDS", "16")
    telemetry.reset()
    assert telemetry.flight_active()
    telemetry.emit("serving_started", slots=2)
    telemetry.flight("flight_only", req_id=5)    # flight ring only
    recs = telemetry.FlightRecorder.read(tmp_path / "flight.bin")
    assert [r["kind"] for r in recs] == ["serving_started", "flight_only"]
    assert recs[0]["slots"] == 2 and recs[1]["req_id"] == 5
    # flight() bypasses the in-memory event ring.
    assert telemetry.events("flight_only") == []
    assert telemetry.counter_value("tdt_flight_records_total") == 2.0
    # reset() re-resolves: recorder off once the env var is gone.
    monkeypatch.delenv("TDT_FLIGHT_RECORDER")
    telemetry.reset()
    assert not telemetry.flight_active()


def test_flight_postmortem_folds_open_spans(tmp_path):
    """The harvest view: span_start/span_end pairs fold away; what remains
    open at death names the active request/slot/span."""
    recs = [
        {"kind": "span_start", "trace_id": 9, "span_id": 1, "parent_id": None,
         "name": "tdt_serving_request", "req_id": 4},
        {"kind": "span_start", "trace_id": 9, "span_id": 2, "parent_id": 1,
         "name": "tdt_serving_prefill", "slot": 1},
        {"kind": "span_end", "trace_id": 9, "span_id": 2,
         "name": "tdt_serving_prefill"},
        {"kind": "span_start", "trace_id": 9, "span_id": 3, "parent_id": 1,
         "name": "tdt_serving_decode_chunk", "slot": 1},
        {"kind": "event", "i": 1},
    ]
    pm = telemetry.flight_postmortem(recs)
    assert pm["n_records"] == 5
    assert pm["last"]["i"] == 1
    names = pm["active_span_names"]
    assert "tdt_serving_request" in names
    assert "tdt_serving_decode_chunk" in names
    assert "tdt_serving_prefill" not in names  # closed before death
    assert 4 in pm["active_requests"] or "4" in map(str, pm["active_requests"])
    assert 1 in pm["active_slots"]
    assert len(pm["tail"]) == 5
    assert telemetry.flight_postmortem([])["n_records"] == 0
