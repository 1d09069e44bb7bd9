"""Fleet tier tests: placement policy, the replica wire protocol, and the
multi-process acceptance bars.

Three tiers, cheapest first:

* **host** — Router placement ranking (affinity > sticky > load,
  round-robin cold spread) against synthetic placement hints, and the
  read-only ``PrefixIndex.match_blocks`` probe. No model, no processes.
* **world-1 in-process** — a real ``InferenceServer`` behind
  :class:`ReplicaService` routes over the live introspection endpoint
  (submit → stream → placement → drain → journal), the ``resume()``
  mid-stream admission contract, and the ephemeral-port satellite fix.
* **multi-process** — the ISSUE acceptance bars: 2-replica
  prefix-affinity + byte parity + rolling rebuild with zero rejects, and
  kill -9 one of 3 replicas mid-burst with every stream completing
  byte-identical on a survivor (zero dropped / duplicated tokens).

Every replica subprocess shares the parent's model recipe (test-dense,
seed 1, xla, ``MAX_LEN=32``), which is the fleet determinism invariant
migration relies on.
"""

import json
import os
import time
import urllib.error
import urllib.request
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.fleet import FleetRequest, ReplicaService, Router
from triton_dist_tpu.runtime import introspect, resilience, telemetry
from triton_dist_tpu.serving import (
    InferenceServer,
    RequestJournal,
    RequestState,
)

MAX_LEN = 32
BLOCK = 16  # TDT_KV_BLOCK_SIZE default — one full block indexes at 16 tokens

#: Env for replica subprocesses: CPU devices, small serving shape for fast
#: boot/serve.
REPLICA_ENV = {
    "JAX_PLATFORMS": "cpu",
    "TDT_SERVE_SLOTS": "2",
    "TDT_SERVE_CHUNK": "2",
}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    introspect.set_requests_provider(None)
    introspect.set_health_provider(None)
    introspect.clear_json_routes()
    yield
    telemetry.reset()
    resilience.reset_degradation()
    introspect.set_requests_provider(None)
    introspect.set_health_provider(None)
    introspect.clear_json_routes()


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


@pytest.fixture(scope="module")
def engine(model1):
    from triton_dist_tpu.models import Engine

    return Engine(model1, backend="xla", max_len=MAX_LEN)


#: Engine -> {(prompt, gen_len): its one-shot stream}.
_ONE_SHOT = weakref.WeakKeyDictionary()


def _references(eng, requests):
    """The one-shot ``serve`` stream of each request, which its served
    stream must equal. Greedy serve is deterministic, so each (prompt,
    length) is served once an engine."""
    done = _ONE_SHOT.setdefault(eng, {})
    for p, g in requests:
        if (tuple(p), g) not in done:
            done[tuple(p), g] = list(
                np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0])
    return [list(done[tuple(p), g]) for p, g in requests]


def _post(url, body):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read().decode())


# ================================================== host tier: placement


def test_match_blocks_probe_is_readonly():
    from triton_dist_tpu.models.kv_cache import BlockAllocator
    from triton_dist_tpu.serving.scheduler import PrefixIndex

    alloc = BlockAllocator(8)
    idx = PrefixIndex(alloc, 4)
    prompt = list(range(10))                 # 2 full blocks + remainder
    idx.register(prompt, alloc.alloc(2))
    clock = idx._clock
    assert idx.match_blocks(prompt) == 2
    assert idx.match_blocks(prompt[:7]) == 1
    assert idx.match_blocks([9] * 10) == 0
    assert idx._clock == clock               # the probe never ticks the LRU


def _hint(warm=0, est=None, backlog=0, depth=0):
    return {"warm_blocks": warm, "est_wait_s": est,
            "backlog_tokens": backlog, "queue_depth": depth}


def test_rank_affinity_then_sticky_then_load(tmp_path):
    r = Router(3, tmp_path)
    for h in r.replicas:
        h.alive = True
    prompt_a = list(range(BLOCK + 2))
    fr = FleetRequest(0, prompt_a, 4, 1)

    # Warmest replica wins outright, regardless of load.
    infos = [(r.replicas[0], _hint(est=0.0)),
             (r.replicas[1], _hint(warm=2, est=9.0, backlog=100)),
             (r.replicas[2], _hint(warm=1))]
    ranked, reason, hit = r._rank(fr, infos)
    assert ranked[0] is r.replicas[1] and reason == "affinity" and hit
    assert set(ranked) == set(r.replicas)    # the rest stay as fallbacks

    # No warm prefix anywhere: the sticky home (recorded above) wins, so a
    # shared prefix co-locates before any replica's trie has seen it.
    cold = [(h, _hint()) for h in r.replicas]
    ranked, reason, hit = r._rank(fr, cold)
    assert ranked[0] is r.replicas[1] and reason == "sticky" and not hit

    # Unknown prefix, no warm: EWMA-projected load decides.
    fr2 = FleetRequest(1, [100 + i for i in range(BLOCK + 2)], 4, 1)
    infos = [(r.replicas[0], _hint(est=4.0)),
             (r.replicas[1], _hint(est=0.5)),
             (r.replicas[2], _hint(est=2.0))]
    ranked, reason, hit = r._rank(fr2, infos)
    assert ranked[0] is r.replicas[1] and reason == "load" and not hit


def test_rank_round_robin_spreads_cold_equal_load(tmp_path):
    r = Router(3, tmp_path, affinity=False)
    for h in r.replicas:
        h.alive = True
    heads = []
    for i in range(6):
        fr = FleetRequest(i, [200 * (i + 1) + j for j in range(BLOCK)], 4, 1)
        ranked, reason, _ = r._rank(fr, [(h, _hint()) for h in r.replicas])
        assert reason == "load"              # affinity=False: never affinity
        heads.append(ranked[0].idx)
    assert heads == [0, 1, 2, 0, 1, 2]       # cold equal load round-robins


def test_rank_affinity_off_ignores_warm(tmp_path):
    r = Router(2, tmp_path, affinity=False)
    for h in r.replicas:
        h.alive = True
    fr = FleetRequest(0, list(range(BLOCK)), 4, 1)
    infos = [(r.replicas[0], _hint(est=0.1)),
             (r.replicas[1], _hint(warm=3, est=5.0))]
    ranked, reason, _ = r._rank(fr, infos)
    assert ranked[0] is r.replicas[0] and reason == "load"


# =========================== world-1 in-process: replica service + resume


def test_port_file_reports_actual_ephemeral_port(monkeypatch, tmp_path):
    port_file = tmp_path / "port"
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    monkeypatch.setenv("TDT_HTTP_PORT_FILE", str(port_file))
    ep = introspect.maybe_start()
    assert ep is not None
    try:
        assert ep.port > 0                   # the kernel-assigned port
        assert str(ep.port) in ep.url()
        assert port_file.read_text() == str(ep.port)
        _get(ep.url() + "healthz")           # and it is reachable there
    finally:
        ep.stop()


def test_resume_admits_mid_stream_and_journals_seed(engine, tmp_path):
    prompt, max_new = [3, 17, 42, 7, 99], 6
    [ref] = _references(engine, [(prompt, max_new)])
    path = tmp_path / "j.jsonl"
    srv = InferenceServer(
        engine, num_slots=2, chunk=2,
        journal=RequestJournal(path, fsync_every=1),
    )
    streamed: list[int] = []
    req = srv.resume(prompt, max_new, ref[:3],
                     on_token=lambda r, t, i: streamed.append(t))
    assert req.state is RequestState.QUEUED
    srv.run()
    assert req.done and list(req.tokens) == ref
    # Seeded tokens are NOT re-streamed; the suffix regenerates exactly.
    assert streamed == ref[3:]
    # The seed is journaled (position-0 chunk), so THIS journal alone can
    # resume the request again — self-contained for the next migration.
    state = RequestJournal.replay(RequestJournal.read(path))
    assert state[req.req_id].tokens == ref and state[req.req_id].done
    assert telemetry.counter_value("tdt_serving_resumed_total") == 1.0

    # Resuming with the FULL history completes without new tokens.
    streamed2: list[int] = []
    req2 = srv.resume(prompt, max_new, ref,
                      on_token=lambda r, t, i: streamed2.append(t))
    srv.run()
    assert req2.done and list(req2.tokens) == ref and streamed2 == []
    srv.shutdown(drain=True)


def test_replica_service_routes_end_to_end(engine, monkeypatch, tmp_path):
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    reqs = [(list(range(BLOCK)) + [7], 4), ([8, 1, 13], 4)]
    refs = _references(engine, reqs)
    srv = InferenceServer(
        engine, num_slots=2, chunk=2,
        journal=RequestJournal(tmp_path / "j.jsonl", fsync_every=1),
    )
    svc = ReplicaService(srv)
    base = srv._introspect.url().rstrip("/")
    try:
        # Cold placement hint: nothing warm, not draining, ready.
        hint = _post(base + "/fleet/placement", {"prompt": reqs[0][0]})
        assert hint["warm_blocks"] == 0 and hint["ready"]
        assert hint["block_size"] == BLOCK

        rids = []
        for p, g in reqs:
            resp = _post(base + "/fleet/submit", {"prompt": p, "max_new": g})
            assert resp["state"] == "queued"
            rids.append(resp["req_id"])
        srv.run()

        # Positional streaming: full fetch, then an offset fetch.
        out = _post(base + "/fleet/stream",
                    {"reqs": [[rid, 0] for rid in rids]})
        for rid, ref in zip(rids, refs):
            st = out["streams"][str(rid)]
            assert st["tokens"] == ref and st["done"]
            assert st["reason"] == "ok"
        out = _post(base + "/fleet/stream", {"reqs": [[rids[0], 2]]})
        assert out["streams"][str(rids[0])]["tokens"] == refs[0][2:]
        unknown = _post(base + "/fleet/stream", {"reqs": [[999, 0]]})
        assert unknown["streams"]["999"].get("unknown")

        # The served 16-token block is now warm for a sharing prompt.
        hint = _post(base + "/fleet/placement",
                     {"prompt": list(range(BLOCK)) + [9, 9]})
        assert hint["warm_blocks"] >= 1

        # Cancel: unknown id is a no-op, not an error.
        assert _post(base + "/fleet/cancel", {"req_id": 12345}) == {
            "cancelled": False
        }

        # Drain: status flips, new admits bounce with shutting_down.
        st = _post(base + "/fleet/drain", {})
        assert st["draining"] and not st["ready"] and st["drained"]
        late = _post(base + "/fleet/submit", {"prompt": [1, 2], "max_new": 2})
        assert late["state"] == "rejected"
        assert late["reject_reason"] == "shutting_down"

        # Journal export: flushed records, replayable.
        j = _post(base + "/fleet/journal", {})
        state = RequestJournal.replay(j["records"])
        assert [state[rid].tokens for rid in rids] == refs
        assert j["path"].endswith("j.jsonl")

        svc.close()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/fleet/status")     # routes unmounted with close()
        assert ei.value.code == 404
    finally:
        svc.close()                          # idempotent
        srv.shutdown(drain=True)


# ============================================= multi-process acceptance


def _collect(streams):
    def on_token(fr, t, i):
        streams.setdefault(fr.fleet_id, []).append(t)
    return on_token


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_fleet_affinity_parity_and_rolling_rebuild(engine, tmp_path):
    """2 replicas: shared-prefix waves route to the warm replica and every
    stream matches the one-shot reference; then a rolling rebuild with
    fresh work in flight completes with zero rejects and zero downtime."""
    pa, pb = [11] * BLOCK, [22] * BLOCK
    reqs = [(pa + [1], 4), (pb + [2], 4),
            (pa + [3], 4), (pa + [4], 4), (pb + [5], 4), (pb + [6], 4),
            (pa + [7], 4), (pb + [8], 4), (pa + [9], 4), (pb + [10], 4)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        # Wave 1 registers each prefix family on some replica (sticky
        # keeps each family together even before the tries are warm).
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs[:2]]
        router.serve_all(timeout_s=180)
        # Wave 2 must find the warm tries and follow them.
        frs += [router.submit(p, g, on_token=_collect(streams))
                for p, g in reqs[2:6]]
        router.serve_all(timeout_s=180)
        assert router._prefix_hits >= 1
        assert telemetry.counter_value(
            "tdt_fleet_placements_total", reason="affinity"
        ) >= 1.0
        hit_rate = telemetry.gauge_value("tdt_fleet_prefix_hit_rate")
        assert hit_rate is not None and hit_rate > 0

        # Rolling rebuild with work in flight: nothing rejected, nothing
        # dropped, both replicas end up on a fresh generation.
        frs += [router.submit(p, g, on_token=_collect(streams))
                for p, g in reqs[6:]]
        rebuilt = router.rolling_rebuild()
        assert rebuilt == 2
        router.serve_all(timeout_s=180)
        assert all(h.gen == 2 and h.alive for h in router.replicas)
        assert telemetry.counter_value("tdt_fleet_rebuilds_total") == 2.0

        for fr, ref in zip(frs, refs):
            assert fr.done and fr.finish_reason == "ok"
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
            assert streams[fr.fleet_id] == ref   # zero drop / zero dup
        # Zero rejects is structural (the router parks rather than
        # rejecting) — every submitted request reached done above.
        assert len(router._pending) == 0

        # One more request against the REBUILT generation (the rebuild
        # recycled the earlier replicas' span rings and counters — this
        # gives the fresh fleet served work to observe).
        fr = router.submit(pa + [99], 4)
        router.serve_all(timeout_s=180)
        assert fr.done

        # Federation: the merged /fleet/metrics counter sums must equal
        # what each replica reports when scraped directly.
        merged = router.federated_metrics()
        direct = 0.0
        for h in router.replicas:
            snap = _get(h.url("/snapshot?limit=1"))
            for e in snap["counters"].get("tdt_serving_tokens_total", []):
                direct += e["value"]
        tok = merged["counters"]["tdt_serving_tokens_total"]
        assert "replica" not in tok[0]["labels"]
        assert tok[0]["value"] == direct > 0
        assert sum(e["value"] for e in tok[1:]) == direct
        # Router-local family rides along labeled, never summed in.
        reqs_series = merged["counters"]["tdt_fleet_requests_total"]
        assert {e["labels"].get("replica") for e in reqs_series} == {"router"}

        # Topology reflects the rebuilt fleet and the placement tallies.
        topo = router.topology()
        assert all(r["gen"] == 2 and r["alive"] for r in topo["replicas"])
        assert sum(r["placements"] for r in topo["replicas"]) \
            == router._placements
        live_loads = [r["load"] for r in topo["replicas"]]
        assert all(ld is not None and "est_wait_s" in ld for ld in live_loads)
        # Every placement decision left an audit record with candidates.
        ring = router.placements()
        assert ring and all(rec["candidates"] for rec in ring)
        assert telemetry.counter_value("tdt_fleet_trace_propagated_total") > 0

        # One trace per fleet request, spanning processes: the merged
        # timeline holds the router span AND the replica's serving chain
        # under one trace id, cross-process parent link intact.
        doc = router.fleet_trace(fr.trace.trace_id)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len({e["pid"] for e in xs}) >= 2
        placement_ids = {e["args"]["span_id"] for e in xs
                         if e["name"] == "tdt_fleet_placement"}
        serving_roots = [e for e in xs if e["name"] == "tdt_serving_request"]
        assert serving_roots
        assert all(e["args"]["parent_id"] in placement_ids
                   for e in serving_roots)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_kill_one_of_three_mid_burst(engine, tmp_path):
    """Acceptance: SIGKILL one of 3 replicas mid-burst. Every in-flight
    stream completes on a survivor byte-identical to the unkilled run —
    zero dropped, zero duplicated tokens — via journal-replay migration.
    Requests alternate tenants with distinct QoS (priority + WFQ weight):
    identity must survive the replay byte-identically — the survivor's
    journal submit records and per-tenant accounting both carry it."""
    reqs = [([3 + i, 17, (42 & (i + 1)) + 1, 7, 9 * i + 1], 12)
            for i in range(9)]
    qos = [("acme", 0, 2.5), ("beta", 1, 1.0)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(3, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        frs = [router.submit(p, g, on_token=_collect(streams),
                             priority=qos[i % 2][1], tenant=qos[i % 2][0],
                             weight=qos[i % 2][2])
               for i, (p, g) in enumerate(reqs)]
        # Let the burst get genuinely mid-flight before the kill.
        deadline = time.monotonic() + 120
        while sum(len(s) for s in streams.values()) < 5:
            assert time.monotonic() < deadline, "burst never started"
            if not router.pump():
                time.sleep(0.01)
        victim = max(router.replicas, key=lambda h: len(h.inflight))
        assert victim.inflight                # the kill lands on live work
        pre_kill_rids = set(victim.inflight)  # remote ids executing at death
        router.kill(victim.idx)

        router.serve_all(timeout_s=300)
        assert not victim.alive
        assert telemetry.counter_total("tdt_fleet_migrations_total") >= 1.0
        assert telemetry.gauge_value("tdt_fleet_replicas_alive") == 2.0
        for fr, ref in zip(frs, refs):
            assert fr.done
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
            assert streams[fr.fleet_id] == ref   # zero drop / zero dup

        # Postmortem: the dead replica's flight record (read off disk, no
        # atexit hook — the process died by SIGKILL) names the requests it
        # was executing at death.
        pm = router.postmortem(victim.idx)
        assert pm is not None and pm["reason"] == "death"
        assert pm["n_records"] > 0 and pm["tail"]
        assert set(pm["active_requests"]) & pre_kill_rids
        assert telemetry.counter_value(
            "tdt_fleet_postmortems_total", reason="death") == 1.0

        # One trace id across the kill: a migrated request's merged
        # timeline continues on the SURVIVOR — router spans plus the
        # survivor's serving chain under the same trace, with the
        # migration marker in between.
        migrated = [fr for fr in frs if fr.migrations >= 1]
        assert migrated
        fr = migrated[0]
        doc = router.fleet_trace(fr.trace.trace_id)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len({e["pid"] for e in xs}) >= 2   # router + survivor
        names = {e["name"] for e in xs}
        assert {"tdt_fleet_request", "tdt_fleet_migration",
                "tdt_serving_request"} <= names
        placement_ids = {e["args"]["span_id"] for e in xs
                         if e["name"] == "tdt_fleet_placement"}
        survivor_roots = [e for e in xs if e["name"] == "tdt_serving_request"]
        assert all(e["args"]["parent_id"] in placement_ids
                   for e in survivor_roots)
        assert all(e["pid"] != 1 + victim.idx for e in xs)

        # QoS identity through migration: every survivor journal submit
        # record carries the original tenant / weight / priority
        # byte-identically (prompts are unique, so they key the match).
        by_prompt = {tuple(fr.prompt): fr for fr in frs}
        seen_prompts = set()
        for h in router.replicas:
            if not h.alive:
                continue
            for rec in router._http(h, "/fleet/journal")["records"]:
                if rec.get("kind") != "submit":
                    continue
                fr = by_prompt.get(tuple(rec["prompt"]))
                assert fr is not None
                assert rec["tenant"] == fr.tenant
                assert rec["weight"] == fr.weight
                assert rec["priority"] == fr.priority
                seen_prompts.add(tuple(rec["prompt"]))
        migrated_prompts = {tuple(fr.prompt) for fr in migrated}
        assert migrated_prompts <= seen_prompts  # resumed WITH identity

        # ...and lands in the survivors' per-tenant accounting: the merged
        # fleet scrape shows replica-side (not router-local) counts for
        # both tenants.
        merged = router.federated_metrics()
        per_tenant: dict[str, float] = {}
        for e in merged["counters"].get("tdt_tenant_requests_total", []):
            if e["labels"].get("replica") not in (None, "router"):
                t = e["labels"]["tenant"]
                per_tenant[t] = per_tenant.get(t, 0.0) + e["value"]
        assert per_tenant.get("acme", 0.0) >= 1.0
        assert per_tenant.get("beta", 0.0) >= 1.0


# ===================================== wire hardening + observability (fast)


def _get_raw(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read().decode()


def test_fleet_wire_errors_are_structured(engine, monkeypatch, tmp_path):
    """Malformed JSON, unknown paths, wrong verbs, and bad fields all get
    structured JSON errors — never a stack trace, never a hung socket."""
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    srv = InferenceServer(engine, num_slots=2, chunk=2)
    svc = ReplicaService(srv)
    base = srv._introspect.url().rstrip("/")
    try:
        def post_raw(path, payload: bytes):
            req = urllib.request.Request(
                base + path, data=payload,
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            return ei.value.code, json.loads(ei.value.read().decode())

        # Malformed JSON: 400 with a structured error, even on a path that
        # does not exist (the body gate runs first).
        code, err = post_raw("/fleet/submit", b"{not json")
        assert code == 400 and "error" in err
        code, err = post_raw("/fleet/no-such-route", b"{not json")
        assert code == 400 and "error" in err
        # Unknown route: 404.
        code, err = post_raw("/fleet/no-such-route", b"{}")
        assert code == 404 and "error" in err
        # Wrong verb: 405 names the allowed methods without running the
        # handler.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/fleet/submit")
        assert ei.value.code == 405
        err = json.loads(ei.value.read().decode())
        assert err["allow"] == ["POST"] and "error" in err
        code, err = post_raw("/fleet/trace/1", b"{}")
        assert code == 405 and err["allow"] == ["GET"]
        # Missing / bad fields: 400 with the field named.
        code, err = post_raw("/fleet/submit", b'{"prompt": [1]}')
        assert code == 400 and "max_new" in err["error"]
        code, err = post_raw(
            "/fleet/submit", b'{"prompt": [1], "max_new": "lots"}')
        assert code == 400 and "bad field value" in err["error"]
        code, err = post_raw("/fleet/stream", b'{"reqs": [[1]]}')
        assert code == 400
        # Trace route input gate: junk id 400, unknown trace 404.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/fleet/trace/zzz")
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/fleet/trace/424242")
        assert ei.value.code == 404
    finally:
        svc.close()
        srv.shutdown(drain=True)


def test_replica_continues_router_trace_in_process(engine, monkeypatch,
                                                  tmp_path):
    """A submit body carrying a traceparent makes the replica-side serving
    span chain a CHILD of the router's placement span — one trace id across
    the admission boundary, fetchable over ``/fleet/trace/<32-hex>``."""
    from triton_dist_tpu.runtime import tracing

    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    srv = InferenceServer(engine, num_slots=2, chunk=2)
    svc = ReplicaService(srv)
    base = srv._introspect.url().rstrip("/")
    try:
        t = tracing.start_remote_trace("tdt_fleet_request", fleet_id=0)
        with t.span("tdt_fleet_placement") as psp:
            carrier = tracing.inject(t, span_id=psp["span_id"])
            resp = _post(base + "/fleet/submit", {
                "prompt": [3, 17, 42], "max_new": 4, "trace": carrier,
            })
        assert resp["state"] == "queued"
        srv.run()
        t.finish()
        doc = _get(base + f"/fleet/trace/{t.trace_id:032x}")
        assert doc["trace_id_hex"] == f"{t.trace_id:032x}"
        spans = {s["name"]: s for s in doc["spans"]}
        assert spans["tdt_serving_request"]["parent_id"] == psp["span_id"]
        assert spans["tdt_serving_request"]["trace_id"] == t.trace_id
        # The whole serving chain rode along into the same trace.
        for name in ("tdt_serving_queue_wait", "tdt_serving_stream"):
            assert spans[name]["trace_id"] == t.trace_id
        # Decimal id form fetches the same trace.
        doc2 = _get(base + f"/fleet/trace/{t.trace_id}")
        assert len(doc2["spans"]) == len(doc["spans"])
    finally:
        svc.close()
        srv.shutdown(drain=True)


def test_stream_polls_are_idempotent_across_resume(engine, monkeypatch,
                                                   tmp_path):
    """Positional ``/fleet/stream`` polling: duplicate and overlapping
    polls never duplicate or drop tokens — including after the request
    migrates (resume on a second server seeded mid-stream)."""
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    prompt, max_new = [3, 17, 42, 7, 99], 8
    [ref] = _references(engine, [(prompt, max_new)])
    ref = [int(t) for t in ref]              # JSON-able resume seeds
    srv = InferenceServer(
        engine, num_slots=2, chunk=2,
        journal=RequestJournal(tmp_path / "j.jsonl", fsync_every=1),
    )
    svc = ReplicaService(srv)
    base = srv._introspect.url().rstrip("/")
    try:
        rid = _post(base + "/fleet/submit",
                    {"prompt": prompt, "max_new": max_new})["req_id"]
        srv.run()
        full = _post(base + "/fleet/stream",
                     {"reqs": [[rid, 0]]})["streams"][str(rid)]
        assert full["tokens"] == ref and full["done"]
        # Duplicate poll: byte-identical, nothing consumed.
        again = _post(base + "/fleet/stream",
                      {"reqs": [[rid, 0]]})["streams"][str(rid)]
        assert again["tokens"] == ref
        # Overlapping offsets slice the same stream consistently.
        for frm in (0, 2, 5, len(ref), len(ref) + 3):
            st = _post(base + "/fleet/stream",
                       {"reqs": [[rid, frm]]})["streams"][str(rid)]
            assert st["tokens"] == ref[frm:] and st["done"]
        # Same req polled twice in ONE call: both entries full and equal.
        st = _post(base + "/fleet/stream",
                   {"reqs": [[rid, 0], [rid, 3]]})["streams"]
        assert st[str(rid)]["tokens"] in (ref, ref[3:])
    finally:
        svc.close()
        srv.shutdown(drain=True)

    # "Migration": a second server resumes from the journal seed; polls
    # against the NEW replica stay positional from the router's delivered
    # count, so the client stream never duplicates the seed.
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    srv2 = InferenceServer(engine, num_slots=2, chunk=2)
    svc2 = ReplicaService(srv2)
    base2 = srv2._introspect.url().rstrip("/")
    try:
        delivered = ref[:3]                  # what the router already has
        rid2 = _post(base2 + "/fleet/resume", {
            "prompt": prompt, "max_new": max_new, "tokens": ref[:5],
        })["req_id"]                         # journal ahead of delivery
        srv2.run()
        st = _post(base2 + "/fleet/stream",
                   {"reqs": [[rid2, len(delivered)]]})["streams"][str(rid2)]
        assert delivered + st["tokens"] == ref  # zero dup, zero drop
        st2 = _post(base2 + "/fleet/stream",
                    {"reqs": [[rid2, len(delivered)]]})["streams"][str(rid2)]
        assert st2["tokens"] == st["tokens"]    # re-poll: same answer
    finally:
        svc2.close()
        srv2.shutdown(drain=True)


def test_router_federation_routes(monkeypatch, tmp_path):
    """The router-process federation endpoint: topology/metrics/placements
    serve with zero live replicas, postmortem and trace 404/400 correctly,
    and verbs are enforced. No replica subprocesses involved."""
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    ep = introspect.maybe_start()
    assert ep is not None
    base = ep.url().rstrip("/")
    router = Router(2, tmp_path / "fleet")
    router.mount_routes()
    try:
        router.submit([1, 2, 3], 4)          # parks: no replica is alive
        topo = _get(base + "/fleet/topology")
        assert [r["idx"] for r in topo["replicas"]] == [0, 1]
        assert not any(r["alive"] for r in topo["replicas"])
        assert topo["pending"] == 1 and topo["requests"] == 1
        for r in topo["replicas"]:               # health fields ride along
            assert r["health"] == "live"
            assert r["consecutive_failures"] == 0
            assert r["probe_ewma_ms"] == 0.0
            assert r["stall_age_s"] is None      # not alive: no stall clock
            assert r["respawn_failures"] == 0
            assert not r["breaker_tripped"]

        status, text = _get_raw(base + "/fleet/metrics")
        assert status == 200
        assert 'tdt_fleet_requests_total{replica="router"} 1' in text
        merged = _get(base + "/fleet/metrics?format=json")
        assert merged["federated"] and merged["replicas"] == []

        assert _get(base + "/fleet/placements") == {"placements": []}
        for path, code in [("/fleet/postmortem/0", 404),
                           ("/fleet/postmortem/xyz", 400),
                           ("/fleet/trace/zzz", 400),
                           ("/fleet/trace/424242", 404)]:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + path)
            assert ei.value.code == code, path
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base + "/fleet/metrics", {})
        assert ei.value.code == 405

        # The router's own live trace IS fetchable fleet-wide (router pid 0).
        tid = router._requests[0].trace.trace_id
        doc = _get(base + f"/fleet/trace/{tid:032x}")
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert "tdt_fleet_request" in names

        router.shutdown()                    # unmounts
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/fleet/topology")
        assert ei.value.code == 404
    finally:
        router.shutdown()
        ep.stop()


def test_merge_scrapes_sums_counters_and_histograms():
    """Federation merge semantics on synthetic snapshots: counters and
    histograms sum per label set with per-replica series alongside; gauges
    stay per-replica (a summed gauge would be a lie)."""
    def snap(tok, wait_count):
        return {
            "counters": {
                "tdt_serving_tokens_total": [
                    {"labels": {}, "value": float(tok)}],
                "tdt_serving_requests_total": [
                    {"labels": {"priority": "1"}, "value": 2.0}],
            },
            "gauges": {"tdt_serving_queue_depth": [
                {"labels": {}, "value": 3.0}]},
            "histograms": {"tdt_serving_queue_wait_seconds": [{
                "labels": {}, "count": wait_count, "sum": 0.5 * wait_count,
                "buckets": [[0.1, wait_count], ["+Inf", wait_count]],
            }]},
        }

    m = Router._merge_scrapes([(0, snap(10, 2)), (2, snap(32, 3))])
    assert m["replicas"] == [0, 2]
    tok = m["counters"]["tdt_serving_tokens_total"]
    assert tok[0] == {"labels": {}, "value": 42.0}          # the sum
    assert {e["labels"].get("replica"): e["value"] for e in tok[1:]} == \
        {"0": 10.0, "2": 32.0}
    # Labeled counter series sum per label set.
    pri = m["counters"]["tdt_serving_requests_total"]
    assert pri[0] == {"labels": {"priority": "1"}, "value": 4.0}
    # Gauges: per-replica only, no summed series.
    depth = m["gauges"]["tdt_serving_queue_depth"]
    assert all("replica" in e["labels"] for e in depth) and len(depth) == 2
    # Histograms: counts, sums, and cumulative buckets sum positionally.
    hist = m["histograms"]["tdt_serving_queue_wait_seconds"]
    assert hist[0]["count"] == 5 and hist[0]["sum"] == pytest.approx(2.5)
    assert hist[0]["buckets"] == [[0.1, 5], ["+Inf", 5]]
    assert len(hist) == 3
    # The merged dict renders as Prometheus text directly.
    text = telemetry.to_prometheus(m)
    assert "tdt_serving_tokens_total 42" in text
    assert 'tdt_serving_tokens_total{replica="0"} 10' in text


def test_merge_scrapes_digest_federation_is_exact():
    """ISSUE 18 acceptance: the fleet-wide quantiles from merged
    per-replica digests EQUAL the single-digest answer over the union
    stream (merge invariance — log-γ bucket counts sum per key), and
    both stay within DIGEST_ALPHA of the sorted-list oracle."""
    rng = np.random.default_rng(3)
    samples = [float(v) for v in rng.lognormal(-3.0, 0.9, size=6_000)]
    single = telemetry.Digest()
    shards = [telemetry.Digest() for _ in range(3)]
    for i, v in enumerate(samples):
        single.add(v)
        shards[i % 3].add(v)
    scrapes = [
        (idx, {"digests": {"tdt_slo_ttft_seconds": [
            telemetry.digest_entry({"tenant": "vip", "tier": "0"}, d)]}})
        for idx, d in enumerate(shards)
    ]
    m = Router._merge_scrapes(scrapes)
    entries = m["digests"]["tdt_slo_ttft_seconds"]
    fleet = entries[0]                       # the merged (fleet-wide) series
    assert "replica" not in fleet["labels"] and fleet["count"] == len(samples)
    merged_d = telemetry.Digest.from_dict(fleet)
    s = sorted(samples)
    for q in telemetry.DIGEST_QUANTILES:
        assert merged_d.quantile(q) == single.quantile(q)    # bit-exact
        oracle = s[int(q * (len(s) - 1))]
        assert (abs(merged_d.quantile(q) - oracle) / oracle
                <= telemetry.DIGEST_ALPHA)
    # digest_entry precomputed the same quantiles into the payload...
    assert fleet["quantiles"]["p99"] == single.quantile(0.99)
    # ...the per-replica series ride alongside, replica-labeled...
    assert sum("replica" in e["labels"] for e in entries) == 3
    # ...and the merged dict renders as Prometheus summary text.
    text = telemetry.to_prometheus(m)
    assert "# TYPE tdt_slo_ttft_seconds summary" in text
    assert 'quantile="0.99"' in text


@pytest.mark.chaos
def test_slo_burn_alert_fires_and_clears_once(monkeypatch, tmp_path):
    """Chaos acceptance (the ``slo-burn-alert`` suite row): an aggressor
    tenant's burst into a bounded router queue burns its error budget —
    the pump's burn-rate monitor fires EXACTLY one ``slo_alert``, holds
    while the fast window is hot (hysteresis), and clears EXACTLY once
    after recovery. Deterministic: no replica processes (every replica
    retired, so the burst parks then sheds ``queue_full``), pinned tiny
    windows, pump driven by hand."""
    monkeypatch.setenv("TDT_FLEET_PENDING_MAX", "2")
    monkeypatch.setenv("TDT_SLO_FAST_WINDOW_S", "0.4")
    monkeypatch.setenv("TDT_SLO_SLOW_WINDOW_S", "0.8")
    monkeypatch.setenv("TDT_SLO_MIN_EVENTS", "5")
    router = Router(1, tmp_path)
    try:
        for h in router.replicas:
            h.retired = True                 # no eligible replica: park
        burst = [router.submit([40 + i, 7], 2, tenant="agg")
                 for i in range(10)]
        shed = [fr for fr in burst if fr.done]
        assert len(shed) == 8
        assert all(fr.finish_reason == "queue_full" for fr in shed)

        # The burst's sheds are in the monitor; the NEXT pump tick fires.
        router.pump()
        alerts = telemetry.events("slo_alert")
        assert len(alerts) == 1
        assert alerts[0]["tenant"] == "agg" and alerts[0]["state"] == "fire"
        assert telemetry.counter_value(
            "tdt_slo_alerts_total", tenant="agg", state="fire") == 1.0
        # Hysteresis: pumping while the fast window is hot re-fires NOTHING.
        router.pump()
        router.pump()
        assert len(telemetry.events("slo_alert")) == 1
        slo_view = router.fleet_slo()
        assert slo_view["burn"]["agg"]["firing"] is True
        assert slo_view["burn"]["agg"]["fast_burn"] >= 14.0

        # Recovery: the fast window drains past the burst -> one clear.
        time.sleep(0.45)
        router.pump()
        alerts = telemetry.events("slo_alert")
        assert [a["state"] for a in alerts] == ["fire", "clear"]
        assert telemetry.counter_value(
            "tdt_slo_alerts_total", tenant="agg", state="clear") == 1.0
        router.pump()                        # quiet: no flapping
        assert len(telemetry.events("slo_alert")) == 2
        slo_view = router.fleet_slo()
        agg = slo_view["burn"]["agg"]
        # Fast window drained (burn 0); the slow window may still hold the
        # burst — clearing is the FAST window's call, by design.
        assert agg["firing"] is False
        assert (agg["fires"], agg["clears"]) == (1, 1)
        assert agg["fast_burn"] == 0.0
        assert [a["state"] for a in slo_view["alerts"]] == ["fire", "clear"]
    finally:
        router.shutdown()


def test_placement_audit_ring_records_why_and_is_bounded(monkeypatch,
                                                         tmp_path):
    monkeypatch.setenv("TDT_FLEET_PLACEMENT_RING", "4")
    r = Router(2, tmp_path)
    for h in r.replicas:
        h.alive = True
    infos = [(r.replicas[0], _hint(warm=2, est=1.0)),
             (r.replicas[1], _hint(est=0.2))]
    for i in range(6):
        fr = FleetRequest(i, list(range(BLOCK)), 4, 1)
        ranked, reason, hit = r._rank(fr, infos)
        r._audit_placement(fr, infos, ranked, ranked[0], reason, hit)
    ring = r.placements()
    assert len(ring) == 4                    # bounded: oldest evicted
    assert [rec["fleet_id"] for rec in ring] == [2, 3, 4, 5]
    rec = ring[-1]
    assert rec["chosen"] == 0 and rec["reason"] == "affinity"
    assert rec["prefix_hit"] and rec["ranked"][0] == 0
    cands = {c["replica"]: c for c in rec["candidates"]}
    assert cands[0]["warm_blocks"] == 2
    assert cands[1]["est_wait_s"] == pytest.approx(0.2)


# ========================================== gray-failure tolerance (fast)
# Pure units: the health state machine, the wire-chaos grammar, error
# classification, retry accounting, deadline stamping — deterministic
# clocks, monkeypatched wires, no subprocesses.


def test_replica_health_thresholds_and_heal():
    from triton_dist_tpu.fleet.router import ReplicaHealth

    hp = ReplicaHealth(suspect_after=2, dead_after=4, now=0.0)
    hp.note_failure(1.0)
    assert hp.state == "live" and hp.failures == 1   # one blip is free
    hp.note_failure(2.0)
    assert hp.state == "suspect"                     # leaves placement
    hp.note_ok(3.0, 0.01)
    assert hp.state == "live" and hp.failures == 0   # one success heals
    for t in range(4):
        hp.note_failure(4.0 + t)
    assert hp.state == "dead"                        # migration verdict
    hp.note_ok(9.0, 0.01)
    assert hp.state == "dead"                        # only reset() revives
    hp.reset(10.0)
    assert hp.state == "live" and hp.failures == 0

    # Heartbeat staleness and the progress-watchdog predicate.
    hp2 = ReplicaHealth(heartbeat_s=1.0, now=0.0)
    assert not hp2.stale(2.9) and hp2.stale(3.0)     # 3 missed intervals
    hp2.note_progress(5.0)
    assert hp2.stall_age_s(6.5) == pytest.approx(1.5)
    assert not hp2.stalled(6.0, 2.0) and hp2.stalled(7.0, 2.0)
    assert not hp2.stalled(1e9, 0.0)                 # 0 disables the watchdog


def test_replica_health_straggler_ewma_marks_suspect():
    from triton_dist_tpu.fleet.router import ReplicaHealth

    hp = ReplicaHealth(slow_ms=50.0, now=0.0)
    hp.note_ok(1.0, 0.001)
    assert hp.state == "live"
    for i in range(20):                              # 200ms calls: straggler
        hp.note_ok(2.0 + i, 0.2)
    assert hp.ewma_ms > 50.0 and hp.state == "suspect"
    for i in range(60):                              # fast again: heals
        hp.note_ok(30.0 + i, 0.001)
    assert hp.ewma_ms < 50.0 and hp.state == "live"


def test_replica_health_respawn_backoff_doubles_and_breaker_trips():
    from triton_dist_tpu.fleet.router import ReplicaHealth

    hp = ReplicaHealth(respawn_s=0.5, respawn_cap_s=2.0, crash_loop_n=3,
                       now=0.0)
    assert hp.schedule_respawn(10.0) == 0.5
    assert not hp.respawn_due(10.4) and hp.respawn_due(10.5)
    assert hp.respawn_result(False, 11.0) == 1.0     # 0.5 × 2^1
    assert hp.next_respawn_at == pytest.approx(12.0)
    assert hp.respawn_result(False, 13.0) == 2.0     # 0.5 × 2^2, capped
    assert hp.respawn_result(False, 16.0) is None    # 3rd death: breaker
    assert hp.breaker_tripped and hp.state == "quarantined"
    assert not hp.respawn_due(1e9)                   # pinned down for good

    hp2 = ReplicaHealth(respawn_s=0.5, crash_loop_n=3, now=0.0)
    hp2.respawn_result(False, 1.0)
    assert hp2.respawn_result(True, 2.0) == 0.0      # success resets
    assert hp2.respawn_failures == 0 and hp2.state == "live"

    hp3 = ReplicaHealth(now=0.0)                     # supervision off
    assert hp3.respawn_delay() == 0.0 and not hp3.respawn_due(1e9)


def test_classify_oserror_codes():
    from triton_dist_tpu.fleet.router import _classify_oserror

    assert _classify_oserror(ConnectionRefusedError()) == "refused"
    assert _classify_oserror(ConnectionResetError()) == "reset"
    assert _classify_oserror(ConnectionAbortedError()) == "reset"
    assert _classify_oserror(BrokenPipeError()) == "reset"
    assert _classify_oserror(TimeoutError()) == "timeout"
    assert _classify_oserror(OSError("misc")) == "conn"
    # urllib wraps the socket error in URLError: unwrap what it carries.
    assert _classify_oserror(
        urllib.error.URLError(ConnectionRefusedError())) == "refused"
    assert _classify_oserror(urllib.error.URLError("just a string")) == "conn"


def test_wire_chaos_schedule_parse_take_and_sticky_hang():
    s = resilience.WireChaosSchedule(
        "delay@/fleet/stream:50ms, reset@/fleet/stream#1:1,"
        "hang@/fleet/status,heal"
    )
    ev = s.take("/fleet/stream", 0)
    assert ev.action == "delay" and ev.delay_s == pytest.approx(0.05)
    # The head now targets replica 1: replica 0 neither fires it nor
    # consumes its skip; replica 1's first matching call burns the skip,
    # its second fires.
    assert s.take("/fleet/stream", 0) is None
    assert s.take("/fleet/stream", 1) is None        # skip=1 consumed
    ev = s.take("/fleet/stream", 1)
    assert ev is not None and ev.action == "reset"
    # hang is STICKY: fires on its first match and every one after.
    assert s.take("/fleet/stream", 1) is None        # path mismatch
    assert s.take("/fleet/status", 2).action == "hang"
    assert s.take("/fleet/status", 0).action == "hang"
    assert not s.exhausted                           # sticky keeps it armed
    # Duration forms: 0.5s and bare seconds.
    s2 = resilience.WireChaosSchedule("delay@/x:0.5s,delay@/x:2")
    assert s2.take("/x").delay_s == 0.5
    assert s2.take("/x").delay_s == 2.0
    assert s2.exhausted


@pytest.mark.parametrize("spec", [
    "heal,reset@/fleet/stream",      # heal must be last
    "explode@/fleet/stream",         # unknown action
    "reset@stream",                  # path must start with /
    "delay@/fleet/stream",           # delay needs a duration arg
    "delay@/fleet/stream:fast",      # bad duration
    "reset@/fleet/stream#x",         # bad replica index
    "reset@/fleet/stream:1.5",       # bad skip
    "reset",                         # missing @
])
def test_wire_chaos_schedule_rejects_bad_specs(spec):
    with pytest.raises(ValueError):
        resilience.WireChaosSchedule(spec)


def test_router_http_retry_absorbs_reset_then_accounts_health(
        monkeypatch, tmp_path):
    """One reset costs one retry (replica stays LIVE); exhausting the
    retry budget costs ONE health failure (SUSPECT, not migration); the
    next clean call heals back to LIVE — with every step visible in
    ``tdt_fleet_wire_retries_total`` / ``tdt_fleet_health_state``."""
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    monkeypatch.setenv("TDT_FLEET_RETRY_BACKOFF_S", "0")
    ep = introspect.maybe_start()
    assert ep is not None
    introspect.register_json_route(
        "/fleet/status", lambda m, q, b: (200, {"ready": True}),
        methods=("GET",))
    try:
        router = Router(1, tmp_path, wire_chaos="reset@/fleet/status,heal")
        h = router.replicas[0]
        h.port, h.alive = ep.port, True

        assert router._http(h, "/fleet/status")["ready"]  # retry absorbed it
        assert telemetry.counter_value(
            "tdt_fleet_wire_retries_total",
            path="/fleet/status", code="reset") == 1.0
        assert h.health.state == "live" and h.health.failures == 0
        assert telemetry.gauge_value(
            "tdt_fleet_health_state", replica="0") == 0.0

        # Three resets exhaust retries=2: one OSError, one health failure.
        router._wire_chaos = resilience.WireChaosSchedule(
            "reset@/fleet/status,reset@/fleet/status,reset@/fleet/status,heal"
        )
        with pytest.raises(OSError):
            router._http(h, "/fleet/status")
        assert h.health.state == "suspect" and h.health.failures == 1
        assert telemetry.gauge_value(
            "tdt_fleet_health_state", replica="0") == 1.0

        assert router._http(h, "/fleet/status")["ready"]  # clean call heals
        assert h.health.state == "live"
        assert telemetry.gauge_value(
            "tdt_fleet_health_state", replica="0") == 0.0

        # Non-idempotent route + reset: NO retry (a duplicate admit could
        # double-serve) — the error surfaces on the first attempt.
        router._wire_chaos = resilience.WireChaosSchedule(
            "reset@/fleet/submit,heal")
        with pytest.raises(ConnectionResetError):
            router._http(h, "/fleet/submit", {"prompt": [1]})
        assert telemetry.counter_value(
            "tdt_fleet_wire_retries_total",
            path="/fleet/submit", code="reset") == 0.0
    finally:
        ep.stop()


def test_router_http_refused_retries_even_submit(monkeypatch, tmp_path):
    """``refused`` means the connection never reached a server, so even
    ``/fleet/submit`` retries safely — and the exhausted run is one
    health failure."""
    import socket

    monkeypatch.setenv("TDT_FLEET_RETRY_BACKOFF_S", "0")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]       # bound then closed: refuses
    router = Router(1, tmp_path, wire_chaos="")
    h = router.replicas[0]
    h.port, h.alive = dead_port, True
    with pytest.raises(OSError):
        router._http(h, "/fleet/submit", {"prompt": [1], "max_new": 2})
    assert telemetry.counter_value(
        "tdt_fleet_wire_retries_total",
        path="/fleet/submit", code="refused") == 2.0
    assert telemetry.counter_value(
        "tdt_fleet_http_errors_total",
        path="/fleet/submit", code="refused") == 3.0
    assert h.health.state == "suspect" and h.health.failures == 1


def test_deadline_stamps_remaining_budget_and_migration_shrinks(
        monkeypatch, tmp_path):
    router = Router(1, tmp_path)
    h = router.replicas[0]
    h.alive = True
    calls = []

    def fake_http(handle, path, body=None, **kw):
        calls.append((path, body))
        if path == "/fleet/placement":
            return _hint()
        return {"state": "queued", "req_id": 7}

    monkeypatch.setattr(router, "_http", fake_http)
    fr = router.submit([1, 2, 3], 8, ttft_deadline_s=5.0, deadline_s=10.0)
    sub = next(b for p, b in calls if p == "/fleet/submit")
    assert 9.5 < sub["deadline_s"] <= 10.0           # remaining, not total
    assert 4.5 < sub["ttft_deadline_s"] <= 5.0

    # Migration re-stamp 3s later: the residual SHRANK, and a seeded
    # resume carries no TTFT budget (first token already happened).
    fr.arrived_at -= 3.0
    fr._seed = [101, 102]
    h.inflight.clear()
    assert router._send(fr, h)
    res = next(b for p, b in calls if p == "/fleet/resume")
    assert 6.5 < res["deadline_s"] <= 7.0
    assert "ttft_deadline_s" not in res
    assert res["tokens"] == [101, 102]

    # No deadlines: nothing stamped on the wire.
    fr2 = router.submit([4, 5], 4)
    sub2 = [b for p, b in calls if p == "/fleet/submit"][-1]
    assert fr2.done is False
    assert "deadline_s" not in sub2 and "ttft_deadline_s" not in sub2


def test_parked_deadline_expires_router_side(tmp_path):
    router = Router(1, tmp_path)                     # no replica alive
    fr = router.submit([1, 2], 4, deadline_s=5.0)
    assert not fr.done and router._pending
    fr.arrived_at -= 10.0                            # budget long gone
    assert router.pump()
    assert fr.done and fr.finish_reason == "deadline"
    assert not router._pending
    assert telemetry.gauge_value("tdt_fleet_pending_requests") == 0.0


def test_serve_all_idle_backoff_doubles_to_cap(monkeypatch, tmp_path):
    router = Router(1, tmp_path)
    fr = router.submit([1, 2, 3], 4)                 # parks: nothing alive
    sleeps = []

    def fake_sleep(s):
        sleeps.append(s)
        if len(sleeps) >= 6:
            fr.done = True                           # let serve_all exit

    monkeypatch.setattr(time, "sleep", fake_sleep)
    router.serve_all(timeout_s=30, poll_s=0.01, idle_cap_s=0.1)
    assert sleeps == [0.01, 0.02, 0.04, 0.08, 0.1, 0.1]


def test_wait_ready_failure_includes_log_tail(tmp_path):
    import types

    router = Router(1, tmp_path)
    h = router.replicas[0]
    h.log_path = str(tmp_path / "replica.log")
    with open(h.log_path, "w", encoding="utf-8") as f:
        f.write("\n".join(f"boot line {i}" for i in range(30)))
    h.port_file = str(tmp_path / "never-written-port")

    h.proc = types.SimpleNamespace(poll=lambda: None, returncode=None)
    with pytest.raises(TimeoutError) as ei:
        router._wait_ready(h, 0.01)
    msg = str(ei.value)
    assert "last 20 log lines" in msg
    assert "boot line 29" in msg and "boot line 5" not in msg

    h.proc = types.SimpleNamespace(poll=lambda: 3, returncode=3)
    with pytest.raises(RuntimeError) as ei:
        router._wait_ready(h, 0.01)
    assert "rc=3" in str(ei.value) and "boot line 29" in str(ei.value)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_postmortem_flight_record_after_kill(engine, tmp_path):
    """Flight-recorder acceptance in isolation: kill -9 a replica mid-work
    and recover which request/slot/span it was executing at death from the
    mmap ring next to its journal — no exit hook ran, the file alone tells
    the story."""
    reqs = [([5 + i, 3, 2 * i + 1], 10) for i in range(4)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs]
        deadline = time.monotonic() + 120
        while sum(len(s) for s in streams.values()) < 2:
            assert time.monotonic() < deadline, "burst never started"
            if not router.pump():
                time.sleep(0.01)
        victim = max(router.replicas, key=lambda h: len(h.inflight))
        assert victim.inflight
        pre_kill_rids = set(victim.inflight)
        flight_path = victim.flight_path
        router.kill(victim.idx)
        router.serve_all(timeout_s=300)

        for fr, ref in zip(frs, refs):
            assert fr.done and fr.tokens == ref

        # The raw ring on disk is readable and ordered.
        records = telemetry.FlightRecorder.read(flight_path)
        assert records
        seqs = [r["flight_seq"] for r in records]
        assert seqs == sorted(seqs)
        assert {"span_start", "span_end"} & {r.get("kind") for r in records}

        # The router's harvested postmortem pins the work at death.
        pm = router.postmortem(victim.idx)
        assert pm is not None
        assert pm["replica"] == victim.idx and pm["reason"] == "death"
        assert pm["flight_path"] == flight_path
        assert set(pm["active_requests"]) & pre_kill_rids
        assert any(n.startswith("tdt_serving_")
                   for n in pm["active_span_names"])
        assert pm["last"]["flight_seq"] == seqs[-1]


# =============================== gray-failure acceptance (multi-process)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_flaky_wire_reset_absorbed_without_migration(
        engine, monkeypatch, tmp_path):
    """Acceptance: a flaky wire costs retries, never migrations. A burst
    of stream-poll resets — including one run long enough to exhaust the
    retry budget and flip the victim SUSPECT — ends with every stream
    byte-identical, ZERO migrations, and every replica back to LIVE."""
    monkeypatch.setenv("TDT_FLEET_RETRY_BACKOFF_S", "0.005")
    reqs = [([7 + i, 3, 2 * i + 1], 8) for i in range(6)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    # First poll anywhere eats 3 resets (attempt + 2 retries → one health
    # failure → SUSPECT); the 4th reset is absorbed by a later poll's
    # retry; then the wire runs clean.
    chaos = ",".join(["reset@/fleet/stream"] * 4) + ",heal"
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV,
                wire_chaos=chaos) as router:
        router.start()
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs]
        router.serve_all(timeout_s=300)

        for fr, ref in zip(frs, refs):
            assert fr.done and fr.finish_reason == "ok"
            assert fr.tokens == ref
            assert streams[fr.fleet_id] == ref
            assert fr.migrations == 0            # absorbed, not migrated
        assert telemetry.counter_total("tdt_fleet_migrations_total") == 0.0
        assert telemetry.counter_total("tdt_fleet_replica_failures_total") \
            == 0.0
        assert telemetry.counter_value(
            "tdt_fleet_wire_retries_total",
            path="/fleet/stream", code="reset") >= 3.0
        # SUSPECT → LIVE: whoever ate the exhausted run healed on the next
        # clean poll; nobody is dead, nobody quarantined.
        assert all(h.alive and h.health.state == "live"
                   for h in router.replicas)
        assert telemetry.gauge_value("tdt_fleet_replicas_alive") == 2.0
        assert router._wire_chaos.exhausted


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_hang_watchdog_quarantines_and_migrates(
        engine, monkeypatch, tmp_path):
    """Acceptance: wedge one replica's wire (sticky ``hang@/fleet/stream``
    — the process stays alive and boots fine, its stream polls never
    answer). The progress watchdog quarantines it within
    ``TDT_FLEET_STALL_S``, kills it, and journal-replay-migrates its
    streams to survivors byte-identically. The threshold must sit ABOVE
    the healthy replicas' worst first-chunk latency (cold compile on a
    contended CPU) or the watchdog would reap legitimately busy peers."""
    monkeypatch.setenv("TDT_FLEET_STALL_S", "30.0")
    monkeypatch.setenv("TDT_FLEET_DEAD_AFTER", "100000")  # watchdog, not wire
    monkeypatch.setenv("TDT_FLEET_RETRIES", "0")
    reqs = [([3 + i, 17, (42 & (i + 1)) + 1, 7, 9 * i + 1], 10)
            for i in range(9)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(3, tmp_path / "fleet", env=REPLICA_ENV,
                wire_chaos="hang@/fleet/stream#0") as router:
        router.start()
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs]
        victim = router.replicas[0]
        assert victim.inflight                   # the wedge lands on work
        t0 = time.monotonic()
        router.serve_all(timeout_s=300)

        for fr, ref in zip(frs, refs):
            assert fr.done and fr.tokens == ref
            assert streams[fr.fleet_id] == ref   # zero drop / zero dup
        # The stall arc fired (quarantine → drain → kill → migrate), off
        # the watchdog — not the wire-death path. Depending on how far the
        # wedged-WIRE replica's (healthy) serving loop got before the
        # SIGKILL, each of its streams either resumed on a survivor or
        # completed straight from the final journal — both byte-exact,
        # both stall-triggered.
        assert telemetry.counter_value(
            "tdt_fleet_replica_failures_total", reason="stall") == 1.0
        resumed = telemetry.counter_value(
            "tdt_fleet_migrations_total", reason="stall")
        completed = telemetry.counter_value(
            "tdt_fleet_migrations_total", reason="stall_journal_complete")
        assert resumed + completed >= 1.0
        assert telemetry.counter_value(
            "tdt_fleet_stall_migrations_total") == resumed
        assert not victim.alive and victim.health.state == "dead"
        assert telemetry.gauge_value(
            "tdt_fleet_health_state", replica="0") == 3.0
        assert telemetry.gauge_value("tdt_fleet_replicas_alive") == 2.0
        assert router.topology()["replicas"][0]["health"] == "dead"
        # Detection + full drain of the burst happened promptly — the
        # watchdog did not wait out some larger timeout.
        assert time.monotonic() - t0 < 120


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_serving_loop_stall_watchdog_migrates(
        engine, monkeypatch, tmp_path):
    """Acceptance, gray-failure shape #2: the replica's SERVING LOOP wedges
    (``stall@decode`` chaos inside the subprocess) while its HTTP endpoint
    keeps answering status and stream polls — so wire health stays green
    and only the token-progress watchdog can see the problem. (30s
    threshold: comfortably above cold-compile first-chunk latency on a
    contended CPU, far below the suite timeout.)"""
    monkeypatch.setenv("TDT_FLEET_STALL_S", "30.0")
    reqs = [([5 + i, 3, 2 * i + 1], 8) for i in range(6)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV,
                per_replica_env={1: {
                    "TDT_CHAOS_SCHEDULE": "stall@decode:2",
                    "TDT_CHAOS_STALL_S": "600",
                }}) as router:
        router.start()
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs]
        assert router.replicas[1].inflight       # the wedge lands on work
        router.serve_all(timeout_s=300)

        for fr, ref in zip(frs, refs):
            assert fr.done and fr.tokens == ref
            assert streams[fr.fleet_id] == ref
        assert telemetry.counter_value(
            "tdt_fleet_replica_failures_total", reason="stall") == 1.0
        assert telemetry.counter_value(
            "tdt_fleet_stall_migrations_total") >= 1.0
        victim = router.replicas[1]
        assert not victim.alive and victim.health.state == "dead"
        assert telemetry.gauge_value("tdt_fleet_replicas_alive") == 1.0


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_deadline_expires_against_original_budget_mid_migration(
        engine, monkeypatch, tmp_path):
    """Acceptance: a deadline request whose sole replica dies mid-stream
    parks for migration with nowhere to go — and finishes router-side with
    ``finish_reason="deadline"`` against the ORIGINAL submit-time budget,
    not a clock reset by the migration."""
    [ref] = _references(engine, [([3, 17, 42, 7, 99], 24)])
    with Router(1, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        t0 = time.monotonic()
        fr = router.submit([3, 17, 42, 7, 99], 24, deadline_s=3.0)
        deadline = time.monotonic() + 120
        while not fr.tokens:                     # stream genuinely started
            assert time.monotonic() < deadline, "stream never started"
            if not router.pump():
                time.sleep(0.01)
        router.kill(0)                           # sole replica: no survivor
        router.serve_all(timeout_s=120)
        elapsed = time.monotonic() - t0

        assert fr.done and fr.finish_reason == "deadline"
        assert fr.migrations == 1                # it DID migrate (to park)
        assert fr.tokens == ref[:len(fr.tokens)]  # partial stream is exact
        # Expired against the original 3s budget: not early, and not
        # stretched by the migration (generous ceiling for slow CI).
        assert 3.0 <= elapsed < 30.0
        assert telemetry.gauge_value("tdt_fleet_replicas_alive") == 0.0
        assert not router._pending


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_crash_loop_breaker_contains_respawn_storm(
        engine, monkeypatch, tmp_path):
    """Acceptance: supervised respawn brings a killed replica back through
    capped-doubling backoff — but when every respawn dies at boot (bad
    preset injected post-start), the crash-loop breaker trips after
    ``TDT_FLEET_CRASH_LOOP_N`` startup deaths and the slot stays
    QUARANTINED while the surviving peer serves the whole burst."""
    monkeypatch.setenv("TDT_FLEET_RESPAWN_S", "0.1")
    monkeypatch.setenv("TDT_FLEET_RESPAWN_CAP_S", "5.0")
    monkeypatch.setenv("TDT_FLEET_CRASH_LOOP_N", "3")
    reqs = [([9 + i, 4, i + 1], 8) for i in range(4)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()                           # first boot: healthy env
        victim = router.replicas[1]
        # Every respawn from here boots with a nonexistent preset → the
        # subprocess dies during startup, every time.
        router.per_replica_env[1] = {"TDT_REPLICA_PRESET": "no-such-preset"}
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs]
        router.kill(1)
        router.serve_all(timeout_s=300)          # peer serves everything
        for fr, ref in zip(frs, refs):
            assert fr.done and fr.finish_reason == "ok"
            assert fr.tokens == ref
            assert streams[fr.fleet_id] == ref

        # Keep pumping until the breaker trips (3 boot deaths with 0.1 →
        # 0.2 → 0.4s backoffs between attempts).
        deadline = time.monotonic() + 240
        while not victim.health.breaker_tripped:
            assert time.monotonic() < deadline, "breaker never tripped"
            router.pump()
            time.sleep(0.05)

        assert victim.health.state == "quarantined"
        assert not victim.respawning and not victim.alive
        assert victim.health.respawn_failures == 3
        assert telemetry.counter_value(
            "tdt_fleet_respawns_total", outcome="crash") == 3.0
        assert telemetry.counter_value(
            "tdt_fleet_respawns_total", outcome="ok") == 0.0
        assert telemetry.gauge_value(
            "tdt_fleet_health_state", replica="1") == 2.0
        topo = router.topology()["replicas"][1]
        assert topo["breaker_tripped"] and topo["respawn_failures"] == 3
        # The peer kept serving throughout; one more request still lands.
        fr = router.submit([44, 45], 4)
        router.serve_all(timeout_s=120)
        assert fr.done and fr.finish_reason == "ok"
        assert router.replicas[0].alive


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_supervised_respawn_brings_replica_back(
        engine, monkeypatch, tmp_path):
    """The happy respawn path: with supervision on and a healthy env, a
    SIGKILLed replica migrates its work away and then REJOINS the fleet
    (fresh generation, health reset, respawns_total{outcome=ok})."""
    monkeypatch.setenv("TDT_FLEET_RESPAWN_S", "0.1")
    reqs = [([6 + i, 2, i + 1], 8) for i in range(4)]
    refs = _references(engine, reqs)
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        victim = router.replicas[0]
        gen0 = victim.gen
        frs = [router.submit(p, g) for p, g in reqs]
        router.kill(0)
        router.serve_all(timeout_s=300)
        for fr, ref in zip(frs, refs):
            assert fr.done and fr.tokens == ref

        deadline = time.monotonic() + 240
        while not victim.alive:                  # pump the boot to ready
            assert time.monotonic() < deadline, "respawn never completed"
            router.pump()
            time.sleep(0.05)
        assert victim.gen == gen0 + 1            # fresh journal generation
        assert victim.health.state == "live"
        assert telemetry.counter_value(
            "tdt_fleet_respawns_total", outcome="ok") == 1.0
        assert telemetry.gauge_value("tdt_fleet_replicas_alive") == 2.0
        # And the reborn replica takes work again.
        fr = router.submit([77, 78], 4)
        router.serve_all(timeout_s=120)
        assert fr.done and fr.finish_reason == "ok"


# =========================== host tier: elasticity + multi-tenant QoS


def test_scheduler_wfq_orders_pending_by_weight():
    """Weighted-fair tags: tenant a (weight 2) advances its virtual time
    half as fast as tenant b (weight 1), so the tag-order walk interleaves
    2:1 in a's favor; within one tenant tags are monotone (FCFS)."""
    from triton_dist_tpu.serving.scheduler import Scheduler

    s = Scheduler(1, 32)
    for i in range(4):
        s.submit([1, i], 4, tenant="a", weight=2.0)
    for i in range(4):
        s.submit([2, i], 4, tenant="b", weight=1.0)
    order = [r.tenant for r in sorted(s._pending, key=lambda r: r.wfq_tag)]
    assert order == ["a", "a", "b", "a", "a", "b", "b", "b"]

    # Single tenant: tags are monotone in submission order, so the WFQ
    # walk degrades to exactly the old FCFS.
    s2 = Scheduler(1, 32)
    rs = [s2.submit([3, i], 4) for i in range(3)]
    tags = [r.wfq_tag for r in rs]
    assert tags == sorted(tags) and len(set(tags)) == 3


def test_prefix_index_tenant_isolation_and_quota():
    """Tenant-scoped tries: cross-tenant lookups/probes see nothing; a
    tenant at its quota recycles its OWN LRU leaves, never a neighbor's."""
    from triton_dist_tpu.models.kv_cache import BlockAllocator
    from triton_dist_tpu.serving.scheduler import PrefixIndex

    alloc = BlockAllocator(16)
    idx = PrefixIndex(alloc, 4)
    idx.tenant_quota = 2

    def reg(prompt, n, tenant):
        # The donor pattern: the request's chain registers, then the
        # request finishes and frees its own refs — the index keeps one
        # ref per node, so dropped leaves actually free their blocks.
        blocks = alloc.alloc(n)
        idx.register(prompt, blocks, tenant=tenant)
        alloc.free(blocks)

    pa = list(range(8))                      # 2 full blocks
    reg(pa, 2, "a")
    assert idx.tenant_blocks("a") == 2
    # Tenant b can neither reuse nor OBSERVE a's warm prefix.
    assert idx.match_blocks(pa, tenant="b") == 0
    assert idx.match_blocks(pa, tenant="a") == 2
    assert idx.lookup(pa, tenant="b") == []

    pb = [100 + i for i in range(4)]
    reg(pb, 1, "b")
    # a registers past its quota: its own pa leaves recycle, b untouched.
    pa2 = [50 + i for i in range(8)]
    reg(pa2, 2, "a")
    assert idx.tenant_blocks("a") <= 2
    assert idx.match_blocks(pa2, tenant="a") == 2
    assert idx.match_blocks(pa, tenant="a") == 0
    assert idx.tenant_blocks("b") == 1 and idx.match_blocks(
        pb, tenant="b") == 1
    assert telemetry.counter_value(
        "tdt_tenant_prefix_evictions_total", tenant="a", cause="self") >= 1.0

    # Pool-pressure eviction prefers over-quota tenants before the global
    # LRU: push b over quota, then evict on behalf of a fresh tenant.
    idx.tenant_quota = 0                     # lift the cap to overfill b
    for j in range(3):
        reg([200 + 10 * j + i for i in range(4)], 1, "b")
    idx.tenant_quota = 2
    assert idx.tenant_blocks("b") > idx.tenant_quota
    idx.evict(alloc.num_free + 1, tenant="c")
    assert telemetry.counter_value(
        "tdt_tenant_prefix_evictions_total",
        tenant="b", cause="over_quota") >= 1.0
    assert idx.match_blocks(pa2, tenant="a") == 2    # a stayed warm


def test_router_wfq_tags_interleave_tenants(monkeypatch, tmp_path):
    """Router-side WFQ mirrors the scheduler: TDT_TENANT_WEIGHTS supplies
    default weights and the pending walk is tag-ordered."""
    monkeypatch.setenv("TDT_TENANT_WEIGHTS", "gold=2.0")
    router = Router(1, tmp_path)             # no replica alive: all park
    for i in range(4):
        router.submit([i], 4, tenant="gold")
    for i in range(4):
        router.submit([10 + i], 4, tenant="econ")
    order = [fr.tenant
             for fr in sorted(router._pending, key=lambda r: r.wfq_tag)]
    assert order == ["gold", "gold", "econ",
                     "gold", "gold", "econ", "econ", "econ"]
    assert router.autoscale()["tenant_weights"] == {"gold": 2.0}
    assert telemetry.gauge_value(
        "tdt_tenant_pending_requests", tenant="gold") == 4.0


def test_pending_queue_bound_sheds_lowest_tier_aggressor(
        monkeypatch, tmp_path):
    """TDT_FLEET_PENDING_MAX bounds the park queue priority-aware: the
    victim is the least important parked request, ties broken toward the
    tenant with the most parked work — the aggressor sheds itself while
    the high-tier tenant's requests survive, and every gauge stays exact
    through the mutation."""
    monkeypatch.setenv("TDT_FLEET_PENDING_MAX", "2")
    router = Router(1, tmp_path)
    v1 = router.submit([1], 2, priority=0, tenant="vip")
    a1 = router.submit([2], 2, priority=2, tenant="agg")
    assert telemetry.gauge_value("tdt_fleet_pending_requests") == 2.0
    a2 = router.submit([3], 2, priority=2, tenant="agg")
    # Overflow: the aggressor's newest request sheds, never the vip.
    assert a2.done and a2.finish_reason == "queue_full"
    assert not v1.done and not a1.done
    assert telemetry.counter_value(
        "tdt_tenant_shed_total", tenant="agg", reason="queue_full") == 1.0
    v2 = router.submit([4], 2, priority=0, tenant="vip")
    # Overflow again: the remaining priority-2 request pays, not v2.
    assert a1.done and a1.finish_reason == "queue_full"
    assert not v1.done and not v2.done
    assert telemetry.gauge_value("tdt_fleet_pending_requests") == 2.0
    assert telemetry.gauge_value(
        "tdt_tenant_pending_requests", tenant="vip") == 2.0
    assert telemetry.gauge_value(
        "tdt_tenant_pending_requests", tenant="agg") == 0.0


def test_parked_ttft_deadline_expires_router_side(tmp_path):
    """A parked request whose TTFT budget lapses while EVERY replica is
    non-LIVE expires router-side with finish_reason="deadline" instead of
    bouncing between park and placement forever."""
    router = Router(1, tmp_path)             # the only replica never boots
    fr = router.submit([1, 2], 4, ttft_deadline_s=5.0)
    assert not fr.done and router._pending
    fr.arrived_at -= 10.0                    # budget long gone
    assert router.pump()
    assert fr.done and fr.finish_reason == "deadline"
    assert telemetry.gauge_value("tdt_fleet_pending_requests") == 0.0


def test_autoscaler_policy_hysteresis_and_bounds(monkeypatch, tmp_path):
    """The control loop's decision layer, wire-free: scale-up on EWMA
    demand past up_at (bounded by SCALE_MAX, one event per cooldown, never
    while a boot is in progress), scale-down on demand under down_at
    (bounded by SCALE_MIN, never with parked work)."""
    monkeypatch.setenv("TDT_FLEET_SCALE_MAX", "2")
    monkeypatch.setenv("TDT_FLEET_SCALE_MIN", "1")
    monkeypatch.setenv("TDT_FLEET_SCALE_UP_AT", "2.0")
    monkeypatch.setenv("TDT_FLEET_SCALE_DOWN_AT", "1.0")
    monkeypatch.setenv("TDT_FLEET_SCALE_COOLDOWN_S", "100.0")
    monkeypatch.setenv("TDT_FLEET_SCALE_ALPHA", "1.0")
    spawned = []
    monkeypatch.setattr(Router, "_spawn",
                        lambda self, h: spawned.append(h.idx))
    down_calls = []
    monkeypatch.setattr(Router, "scale_down",
                        lambda self, idx: down_calls.append(idx))
    router = Router(1, tmp_path)
    h0 = router.replicas[0]
    h0.alive = True
    h0.health.state = "suspect"              # not eligible: submits park
    for i in range(6):
        router.submit([i], 4)
    assert len(router._pending) == 6

    now = time.monotonic()
    assert router._autoscale(now)            # 6 demand / 1 live > 2.0
    assert spawned == [1] and router.replicas[1].booting
    assert router.autoscale()["events"][-1]["direction"] == "up"
    assert telemetry.counter_value(
        "tdt_fleet_scale_events_total", direction="up") == 1.0
    assert telemetry.gauge_value("tdt_fleet_scale_demand") == 6.0

    # A boot in progress gates further scale events.
    assert not router._autoscale(now)
    assert spawned == [1]

    h1 = router.replicas[1]
    h1.booting = False
    h1.alive = True
    h1.health.state = "suspect"
    router._scale_last_event_at = 0.0        # bypass cooldown for bounds
    # At SCALE_MAX: demand stays hot but no third replica appears.
    assert not router._autoscale(now)
    assert spawned == [1] and not down_calls

    # Demand collapses: the least-loaded highest-idx live replica drains.
    router._pending.clear()
    router._pending_gauges()
    assert router._autoscale(now)
    assert down_calls == [1]

    # Cooldown: the next tick may not start another event.
    down_calls.clear()
    assert not router._autoscale(now + 1.0)
    assert not down_calls

    # At SCALE_MIN: a one-replica fleet never scales below the floor.
    h1.alive = False
    h1.retired = True
    router._scale_last_event_at = 0.0
    assert not router._autoscale(now)
    assert not down_calls


def test_scale_down_state_clears_when_target_dies(tmp_path):
    """The scale-down machine tolerates its target dying (or being retired
    by the failure path) at any phase: the slot retires, the state clears,
    and pump never blocks on a corpse. A scale_down() aimed at an
    already-dead slot retires it immediately."""
    router = Router(2, tmp_path)
    h = router.replicas[1]                   # never alive
    router._scale_down_state = {"idx": 1, "phase": "migrate",
                                "deadline": time.monotonic() + 60.0}
    assert router._pump_scale_down(time.monotonic())
    assert h.retired and router._scale_down_state is None

    router2 = Router(2, tmp_path / "b")
    router2.scale_down(1)                    # dead target: retire in place
    assert router2.replicas[1].retired
    assert router2._scale_down_state is None
    assert telemetry.counter_value(
        "tdt_fleet_scale_events_total", direction="down") == 1.0
    # Retired slots are tombstones: pump skips them, status names them.
    router2.pump()
    assert router2.status()["replicas"][1]["retired"]
    assert not router2.replicas[1].respawning


def test_journal_replays_tenant_and_weight():
    """Tenant identity and QoS weight round-trip the write-ahead journal
    byte-identically — and records written BEFORE the tenant fields
    existed replay with the defaults."""
    recs = [
        {"kind": "submit", "req_id": 1, "prompt": [1, 2], "max_new": 4,
         "priority": 0, "tenant": "acme", "weight": 2.5},
        {"kind": "submit", "req_id": 2, "prompt": [3, 4], "max_new": 4},
    ]
    state = RequestJournal.replay(recs)
    assert state[1].tenant == "acme" and state[1].weight == 2.5
    assert state[1].priority == 0
    assert state[2].tenant == "default" and state[2].weight == 1.0


# ============================== chaos: elastic scale + tenant brown-out


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_scale_down_kill_mid_drain_zero_loss(
        engine, monkeypatch, tmp_path):
    """Chaos acceptance: begin a scale-down (drain flipped, state machine
    armed), then SIGKILL the draining replica before the router can
    migrate a single request. The death path replays the journal FILE onto
    survivors — every stream byte-identical, zero drop/dup — and the slot
    RETIRES instead of respawning, even with supervision enabled."""
    monkeypatch.setenv("TDT_FLEET_RESPAWN_S", "0.1")  # retire must win
    reqs = [([3 + i, 17, (42 & (i + 1)) + 1, 7, 9 * i + 1], 12)
            for i in range(6)]
    refs = _references(engine, reqs)
    streams: dict[int, list[int]] = {}
    with Router(3, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        frs = [router.submit(p, g, on_token=_collect(streams))
               for p, g in reqs]
        deadline = time.monotonic() + 120
        while sum(len(s) for s in streams.values()) < 5:
            assert time.monotonic() < deadline, "burst never started"
            if not router.pump():
                time.sleep(0.01)
        victim = max(router.replicas, key=lambda h: len(h.inflight))
        assert victim.inflight                # the drain has live work
        router.scale_down(victim.idx)
        sd = router._scale_down_state
        assert sd is not None and sd["idx"] == victim.idx
        assert sd["phase"] == "migrate"       # nothing migrated yet
        router.kill(victim.idx)               # kill -9 MID-drain

        router.serve_all(timeout_s=300)
        for fr, ref in zip(frs, refs):
            assert fr.done
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
            assert streams[fr.fleet_id] == ref   # zero drop / zero dup
        assert victim.retired and not victim.alive
        assert router._scale_down_state is None
        assert telemetry.counter_total("tdt_fleet_migrations_total") >= 1.0
        assert telemetry.counter_value(
            "tdt_fleet_scale_events_total", direction="down") == 1.0

        # Supervision never resurrects a retired slot: pump well past the
        # respawn backoff and the tombstone holds.
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            router.pump()
            time.sleep(0.05)
        assert victim.retired and not victim.alive and not victim.respawning
        st = router.status()
        assert st["replicas"][victim.idx]["retired"]
        # The retired slot is out of the placement set but the fleet still
        # takes work.
        fr = router.submit([91, 92], 4)
        router.serve_all(timeout_s=120)
        assert fr.done and fr.finish_reason == "ok"


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_fleet_tenant_burst_sheds_only_aggressor(
        engine, monkeypatch, tmp_path):
    """Chaos acceptance (brown-out isolation): an aggressor tenant floods
    a bounded router queue during a placement stall (every replica
    momentarily ineligible — the overload moment a bounded queue exists
    for). Only aggressor requests shed (``queue_full``, newest-first
    within the aggressor's own backlog); every victim stream completes
    byte-identical with warm WITHIN-tenant prefix hits, and the
    aggressor's probes never see the victim's warm prefix."""
    monkeypatch.setenv("TDT_FLEET_PENDING_MAX", "3")
    pv = [11] * BLOCK                        # victim's shared prefix family
    vip_reqs = [(pv + [i + 1], 4) for i in range(4)]
    vip_refs = _references(engine, vip_reqs)
    with Router(2, tmp_path / "fleet", env=REPLICA_ENV) as router:
        router.start()
        warm = router.submit(pv + [99], 4, priority=0, tenant="vip")
        router.serve_all(timeout_s=180)
        assert warm.done and warm.finish_reason == "ok"

        # Placement stall: both replicas drop out of the eligible set
        # (router-side view only — the processes keep serving), so the
        # flood lands in the bounded pending queue.
        for h in router.replicas:
            h.draining = True
        agg = [router.submit([50 + i, 7, i], 12, priority=2, tenant="agg",
                             weight=0.5)
               for i in range(12)]
        # Deterministic brown-out: the bound (3) holds the aggressor's 3
        # OLDEST requests; the other 9 shed newest-first — the aggressor
        # pays for its own burst while it is the only tenant queued.
        shed = [fr for fr in agg if fr.done]
        assert len(shed) == 9
        assert all(fr.finish_reason == "queue_full" for fr in shed)
        assert telemetry.counter_value(
            "tdt_tenant_shed_total",
            tenant="agg", reason="queue_full") == 9.0
        for h in router.replicas:
            h.draining = False

        vip = [router.submit(p, g, priority=0, tenant="vip", weight=4.0)
               for p, g in vip_reqs]
        router.serve_all(timeout_s=300)

        # Brown-out isolation: every shed landed on the aggressor; the
        # victim tier saw none and its streams are byte-exact.
        assert all(fr.done for fr in agg + vip)
        assert all(fr.tenant == "agg" for fr in agg + vip
                   if fr.finish_reason == "queue_full")
        for fr, ref in zip(vip, vip_refs):
            assert fr.finish_reason == "ok"
            assert fr.tokens == ref, f"fleet_id={fr.fleet_id} diverged"
        assert telemetry.counter_value(
            "tdt_tenant_shed_total",
            tenant="vip", reason="queue_full") == 0.0

        # Warm within-tenant affinity did its job for the victim...
        assert router.status()["prefix_hits"] >= 1
        # ...and the warm prefix is invisible across the tenant boundary:
        # the same prompt probes warm for vip, cold for agg, fleet-wide.
        warm_vip = warm_agg = 0
        for h in router.replicas:
            if not h.alive:
                continue
            body = {"prompt": pv + [0]}
            warm_vip += router._http(
                h, "/fleet/placement",
                dict(body, tenant="vip"))["warm_blocks"]
            warm_agg += router._http(
                h, "/fleet/placement",
                dict(body, tenant="agg"))["warm_blocks"]
        assert warm_vip >= 1 and warm_agg == 0
