"""Write-ahead journal tests: durability mechanics, replay idempotence,
crash-resumable serving, and graceful shutdown.

Host tier for the journal file mechanics (append/fsync/torn-tail/rotate)
and the replay fold; world=1 xla-backend serving (same harness as
``tests/test_serving.py``) for the recovery acceptance:

* kill-and-recover — a journaled server is abandoned mid-serve; a fresh
  server pointed at the same journal replays it and every stream completes
  with zero dropped and zero duplicated tokens, byte-identical to one-shot
  ``Engine.serve``;
* the crash-at-every-record-boundary sweep — recovery from EVERY prefix of
  the journal converges to the same final tokens, making zero-drop/zero-dup
  a property of the record format rather than of one lucky crash point.
"""

import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.runtime import introspect, resilience, telemetry
from triton_dist_tpu.serving import (
    InferenceServer,
    RequestJournal,
    RequestState,
)

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    introspect.set_requests_provider(None)
    introspect.set_health_provider(None)
    yield
    telemetry.reset()
    resilience.reset_degradation()
    introspect.set_requests_provider(None)
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


@pytest.fixture(scope="module")
def engine(model1):
    from triton_dist_tpu.models import Engine

    return Engine(model1, backend="xla", max_len=MAX_LEN)


# Staggered 8-request workload: mixed prompt/gen lengths, arrivals landing
# mid-decode (same shape as the serving acceptance bar).
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


def _references(eng):
    return [
        list(np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0])
        for p, g in REQUESTS
    ]


@pytest.fixture(scope="module")
def refs(engine):
    """The one-shot streams of ``REQUESTS``, which every served or recovered
    stream below must equal. Greedy serve is deterministic, so the module
    computes them once."""
    return _references(engine)


# =========================================================== file mechanics


def test_append_read_roundtrip_and_torn_tail(tmp_path):
    path = tmp_path / "j.jsonl"
    j = RequestJournal(path, fsync_every=1)
    j.append("submit", req_id=1, prompt=[1, 2], max_new=4)
    j.append("prefill", req_id=1, start=0, tokens=[9])
    j.append("chunk", req_id=1, start=1, tokens=[8, 7])
    j.close()

    recs = RequestJournal.read(path)
    assert [r["kind"] for r in recs] == ["submit", "prefill", "chunk"]

    # A crash mid-append tears only the FINAL line: it must be dropped.
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"kind":"finish","req_id":1,"rea')
    recs = RequestJournal.read(path)
    assert [r["kind"] for r in recs] == ["submit", "prefill", "chunk"]

    # Unknown kinds and non-dict lines are skipped, not fatal.
    with open(path, "a", encoding="utf-8") as f:
        f.write('\n{"kind":"bogus"}\n[1,2]\n{"kind":"finish","req_id":1,"reason":"ok"}\n')
    recs = RequestJournal.read(path)
    assert [r["kind"] for r in recs] == ["submit", "prefill", "chunk", "finish"]
    # Missing file: empty, not an error.
    assert RequestJournal.read(tmp_path / "absent.jsonl") == []


def test_append_rejects_unknown_kind(tmp_path):
    j = RequestJournal(tmp_path / "j.jsonl")
    with pytest.raises(ValueError):
        j.append("frobnicate", req_id=1)
    j.close()


def test_fsync_batching_and_finish_forces(tmp_path):
    j = RequestJournal(tmp_path / "j.jsonl", fsync_every=3)
    j.append("submit", req_id=1, prompt=[1], max_new=2)
    j.append("prefill", req_id=1, start=0, tokens=[5])
    assert j.lag_records == 2               # below the batch threshold
    j.append("chunk", req_id=1, start=1, tokens=[6])
    assert j.lag_records == 0               # 3rd append forced the fsync
    j.append("submit", req_id=2, prompt=[2], max_new=2)
    assert j.lag_records == 1
    j.append("finish", req_id=1, reason="ok", n_tokens=2)
    assert j.lag_records == 0               # finish ALWAYS forces
    fsyncs = telemetry.counter_value("tdt_serving_journal_fsyncs_total")
    assert fsyncs == 2.0
    assert telemetry.counter_value(
        "tdt_serving_journal_records_total", kind="submit"
    ) == 2.0
    j.flush()
    j.close()
    assert j.stats()["closed"] is True
    j.close()                               # idempotent
    j.append("cancel", req_id=2)            # post-close append is a no-op
    assert [r["kind"] for r in RequestJournal.read(j.path)].count("cancel") == 0


def test_rotate_compacts_terminal_requests(tmp_path):
    j = RequestJournal(tmp_path / "j.jsonl", fsync_every=1)
    j.append("submit", req_id=1, prompt=[1, 2], max_new=3)
    j.append("prefill", req_id=1, start=0, tokens=[4])
    j.append("finish", req_id=1, reason="ok", n_tokens=3)
    j.append("submit", req_id=2, prompt=[9], max_new=2)
    j.append("prefill", req_id=2, start=0, tokens=[7])
    dropped = j.rotate()
    assert dropped == 3                     # request 1's records compacted
    recs = RequestJournal.read(j.path)
    assert [(r["kind"], r["req_id"]) for r in recs] == [
        ("submit", 2), ("prefill", 2),
    ]
    # The rotated file is still appendable and replayable.
    j.append("finish", req_id=2, reason="ok", n_tokens=2)
    state = RequestJournal.replay(RequestJournal.read(j.path))
    assert state[2].terminal and state[2].tokens == [7]
    j.close()
    assert telemetry.counter_value("tdt_serving_journal_rotations_total") == 1.0
    assert any(e["kind"] == "journal_rotate" for e in telemetry.events())


# ================================================================== replay


def test_replay_is_idempotent_and_positional():
    recs = [
        {"kind": "submit", "req_id": 1, "prompt": [1, 2], "max_new": 4,
         "priority": 2, "deadline_s": 9.0},
        {"kind": "prefill", "req_id": 1, "start": 0, "tokens": [10]},
        {"kind": "chunk", "req_id": 1, "start": 1, "tokens": [11, 12]},
        # Overlapping re-delivery (e.g. a re-prefill after recovery): the
        # absolute positions make it a no-op.
        {"kind": "prefill", "req_id": 1, "start": 0, "tokens": [10]},
        {"kind": "chunk", "req_id": 1, "start": 2, "tokens": [12, 13]},
        {"kind": "submit", "req_id": 2, "prompt": [5], "max_new": 2},
        {"kind": "cancel", "req_id": 2},
        # Records for a request whose submit was rotated away: skipped.
        {"kind": "chunk", "req_id": 77, "start": 0, "tokens": [1]},
    ]
    once = RequestJournal.replay(recs)
    twice = RequestJournal.replay(recs + recs)
    assert once[1].tokens == [10, 11, 12, 13] == twice[1].tokens
    assert once[1].priority == 2 and once[1].deadline_s == 9.0
    assert not once[1].terminal
    assert once[2].cancelled and once[2].terminal
    assert 77 not in once
    assert set(once) == set(twice)
    for rid in once:
        assert once[rid] == twice[rid]


def test_replay_refuses_token_gaps():
    recs = [
        {"kind": "submit", "req_id": 1, "prompt": [1], "max_new": 6},
        {"kind": "prefill", "req_id": 1, "start": 0, "tokens": [10]},
        # Lost chunk: next record starts past the known prefix. Applying it
        # would fabricate tokens 1..2, so it must be ignored.
        {"kind": "chunk", "req_id": 1, "start": 3, "tokens": [40, 50]},
        {"kind": "finish", "req_id": 1, "reason": "ok", "n_tokens": 6},
    ]
    st = RequestJournal.replay(recs)
    assert st[1].tokens == [10]             # durable prefix only
    assert st[1].done and st[1].finish_reason == "ok"


# =========================================== serving writes + kill/recover


def _serve_journaled(engine, path, *, partial=False):
    """Run (or, with ``partial=True``, abandon mid-serve) the staggered
    workload under a fsync-every journal; returns (server, handles,
    streams). The partial stop point is adaptive: at least one request has
    finished and at least one is still in flight — a genuine mid-serve
    crash regardless of chunk/slot timing."""
    journal = RequestJournal(path, fsync_every=1)
    srv = InferenceServer(engine, num_slots=3, chunk=2, journal=journal)
    streams: dict[int, list[int]] = {}

    def on_token(req, token, index):
        streams.setdefault(req.req_id, []).append(token)

    handles = [
        srv.submit(p, g, on_token=on_token) for p, g in REQUESTS[:4]
    ]
    if not partial:
        srv.step()
        handles += [
            srv.submit(p, g, on_token=on_token) for p, g in REQUESTS[4:]
        ]
        srv.run()
        return srv, handles, streams
    while not any(h.done for h in handles):
        srv.step()
    handles += [
        srv.submit(p, g, on_token=on_token) for p, g in REQUESTS[4:]
    ]
    # The last request wants 6 tokens; two steps can produce at most
    # join-prefill + 2 chunks of 2 = 5, so something is ALWAYS in flight.
    srv.step()
    srv.step()
    return srv, handles, streams


def test_server_journals_full_lifecycle(engine, refs, tmp_path):
    path = tmp_path / "journal.jsonl"
    srv, handles, streams = _serve_journaled(engine, path)
    assert all(h.done for h in handles)

    recs = RequestJournal.read(path)
    kinds_by_req: dict[int, list[str]] = {}
    for r in recs:
        kinds_by_req.setdefault(r["req_id"], []).append(r["kind"])
    assert len(kinds_by_req) == len(REQUESTS)
    state = RequestJournal.replay(recs)
    for h, ref in zip(handles, refs):
        ks = kinds_by_req[h.req_id]
        # Lifecycle order: submit, then the stream, then exactly one finish.
        assert ks[0] == "submit" and ks[-1] == "finish"
        assert ks.count("submit") == 1 and ks.count("finish") == 1
        assert ks[1] == "prefill"
        # The journaled token history IS the stream, byte for byte.
        assert state[h.req_id].tokens == list(h.tokens) == ref
        assert state[h.req_id].terminal
    # Everything terminal -> a recovery from this journal restores nothing.
    srv2 = InferenceServer(engine, num_slots=3, chunk=2)
    assert srv2.recover(path) == []
    assert telemetry.counter_value(
        "tdt_serving_journal_replayed_total", outcome="skipped_terminal"
    ) == float(len(REQUESTS))
    # ... and rotate() compacts it to empty.
    j = RequestJournal(path, fsync_every=1)
    assert j.rotate() == len(recs)
    assert RequestJournal.read(path) == []
    j.close()


@pytest.mark.chaos
def test_kill_and_recover_zero_drop_zero_dup(engine, refs, tmp_path):
    """Acceptance: abandon a journaled server mid-serve (process "crash" —
    no shutdown, no flush beyond the per-record fsync), point a fresh
    server at the journal, and every surviving stream completes
    byte-identically with zero dropped and zero duplicated tokens."""
    path = tmp_path / "journal.jsonl"
    srv1, handles1, streams1 = _serve_journaled(engine, path, partial=True)
    # The crash must land mid-serve: some requests done, some in flight.
    assert any(h.done for h in handles1)
    assert not all(h.done for h in handles1)

    pre = RequestJournal.replay(RequestJournal.read(path))
    live = {rid for rid, rr in pre.items() if not rr.terminal}
    assert live                              # in-flight work survived on disk

    # Fresh process: new server, same journal. recover() BEFORE run().
    streams2: dict[int, list[int]] = {}
    srv2 = InferenceServer(engine, num_slots=3, chunk=2)
    restored = srv2.recover(
        path, on_token=lambda r, t, i: streams2.setdefault(r.req_id, []).append(t)
    )
    assert sorted(r.req_id for r in restored) == sorted(live)
    srv2.run()

    by_id = {h.req_id: (h, ref) for h, ref in zip(handles1, refs)}
    for r in restored:
        _, ref = by_id[r.req_id]
        assert r.done
        # Zero drop, zero dup: journaled prefix + newly streamed suffix is
        # exactly the one-shot reference; journaled tokens are NOT re-sent.
        assert list(r.tokens) == ref
        assert streams2.get(r.req_id, []) == ref[len(pre[r.req_id].tokens):]
    # Requests that finished before the crash were skipped idempotently.
    done_before = {h.req_id for h in handles1 if h.done}
    assert done_before == set(pre) - live
    for rid in done_before:
        h, ref = by_id[rid]
        assert list(h.tokens) == ref
    # Replaying the same journal again on the same server is a no-op.
    assert srv2.recover(path) == []
    assert telemetry.counter_value(
        "tdt_serving_journal_replayed_total", outcome="skipped_duplicate"
    ) == float(len(live))
    assert any(e["kind"] == "serving_journal_replay" for e in telemetry.events())


@pytest.mark.timeout(360)
def test_crash_at_every_record_boundary(engine, refs, tmp_path):
    """The sweep: truncate the full journal at EVERY record boundary and
    recover from the prefix. Whatever the crash point, every request whose
    submit survived must finish with byte-identical tokens — zero drops,
    zero dups, no fabricated suffixes."""
    path = tmp_path / "journal.jsonl"
    srv, handles, _ = _serve_journaled(engine, path)
    assert all(h.done for h in handles)
    records = RequestJournal.read(path)
    ref_by_id = {h.req_id: ref for h, ref in zip(handles, refs)}
    assert len(records) > 3 * len(REQUESTS)  # submits + streams + finishes

    for cut in range(len(records) + 1):
        prefix_path = tmp_path / "prefix.jsonl"
        with open(prefix_path, "w", encoding="utf-8") as f:
            for rec in records[:cut]:
                f.write(json.dumps(rec, separators=(",", ":")) + "\n")
        pre = RequestJournal.replay(records[:cut])
        live = {rid for rid, rr in pre.items() if not rr.terminal}

        srv_b = InferenceServer(engine, num_slots=3, chunk=2)
        restored = srv_b.recover(prefix_path)
        assert sorted(r.req_id for r in restored) == sorted(live), f"cut={cut}"
        srv_b.run()
        for r in restored:
            assert r.done, f"cut={cut} req={r.req_id}"
            assert list(r.tokens) == ref_by_id[r.req_id], (
                f"cut={cut} req={r.req_id}: recovery diverged"
            )
        # Pool hygiene: after the drain only the prefix index may hold
        # blocks — any extra used block is a chain the recovery path
        # reserved but never released.
        st = srv_b.kv_ledger.stats()
        assert st["blocks_used"] == st["blocks_indexed"], f"cut={cut}"


@pytest.mark.chaos
def test_journal_portability_across_server_shapes(engine, refs, tmp_path, monkeypatch):
    """A journal is a portable request ledger, not a dump of one server's
    internals: records written by a 3-slot server over the default paged
    pool replay into a fresh server with a different slot count AND a
    different KV block size, and every surviving stream still completes
    byte-identically with zero dropped / duplicated tokens. This is the
    invariant the fleet router leans on when it migrates work between
    replicas that need not share serving-shape knobs."""
    path = tmp_path / "journal.jsonl"
    srv1, handles1, _ = _serve_journaled(engine, path, partial=True)
    pre = RequestJournal.replay(RequestJournal.read(path))
    live = {rid for rid, rr in pre.items() if not rr.terminal}
    assert live

    # Fresh "replica" with a deliberately different shape: more slots and
    # half-size KV blocks (a different paged pool geometry entirely).
    monkeypatch.setenv("TDT_KV_BLOCK_SIZE", "8")
    streams2: dict[int, list[int]] = {}
    srv2 = InferenceServer(engine, num_slots=5, chunk=2)
    assert srv2.kv_ledger.block_size == 8
    restored = srv2.recover(
        path, on_token=lambda r, t, i: streams2.setdefault(r.req_id, []).append(t)
    )
    assert sorted(r.req_id for r in restored) == sorted(live)
    srv2.run()
    by_id = {h.req_id: ref for h, ref in zip(handles1, refs)}
    for r in restored:
        assert r.done
        assert list(r.tokens) == by_id[r.req_id]
        # The journaled prefix is seeded, not re-streamed; the regenerated
        # suffix lands exactly once.
        assert streams2.get(r.req_id, []) == by_id[r.req_id][len(pre[r.req_id].tokens):]


def test_recover_drops_oversized_requests(engine, tmp_path):
    """A journal from a server with a bigger KV row must not abort the
    survivors: the oversized request is dropped loudly, the rest resume."""
    path = tmp_path / "journal.jsonl"
    j = RequestJournal(path, fsync_every=1)
    j.append("submit", req_id=0, prompt=list(range(30)), max_new=10)  # > max_len
    j.append("submit", req_id=1, prompt=[3, 1], max_new=2)
    j.close()
    srv = InferenceServer(engine, num_slots=2, chunk=2)
    restored = srv.recover(path)
    assert [r.req_id for r in restored] == [1]
    assert telemetry.counter_value(
        "tdt_serving_journal_replayed_total", outcome="dropped_kv_budget"
    ) == 1.0
    srv.run()
    assert restored[0].done


# ======================================================= graceful shutdown


def test_shutdown_drains_then_rejects(engine, refs, tmp_path):
    journal = RequestJournal(tmp_path / "j.jsonl", fsync_every=1)
    srv = InferenceServer(engine, num_slots=3, chunk=2, journal=journal)
    handles = [srv.submit(p, g) for p, g in REQUESTS[:3]]
    srv.step()                              # some work in flight
    srv.shutdown(drain=True)
    # Drain completed every admitted request, byte-identically.
    for h, ref in zip(handles, refs[:3]):
        assert h.done and list(h.tokens) == ref
    assert srv.scheduler.occupancy() == 0 and srv.scheduler.queue_depth() == 0
    # New work is refused while (and after) shutting down.
    late = srv.submit([1, 2, 3], 4)
    assert late.state is RequestState.REJECTED
    assert late.reject_reason == "shutting_down"
    # Journal flushed + closed; drain time observed; lifecycle events out.
    assert journal.stats()["closed"] is True
    snap = telemetry.snapshot()
    assert snap["histograms"]["tdt_serving_drain_seconds"]
    kinds = [e["kind"] for e in telemetry.events()]
    assert "serving_shutdown" in kinds and "serving_shutdown_done" in kinds
    srv.shutdown()                          # idempotent


def test_shutdown_without_drain_leaves_recoverable_journal(engine, refs, tmp_path):
    path = tmp_path / "j.jsonl"
    journal = RequestJournal(path, fsync_every=1)
    srv = InferenceServer(engine, num_slots=2, chunk=2, journal=journal)
    handles = [srv.submit(p, g) for p, g in REQUESTS[:3]]
    srv.step()
    srv.shutdown(drain=False)               # Ctrl-C semantics
    assert not all(h.done for h in handles)
    # The journal holds everything a fresh server needs.
    srv2 = InferenceServer(engine, num_slots=2, chunk=2)
    restored = srv2.recover(path)
    assert restored
    srv2.run()
    by_id = {h.req_id: ref for h, ref in zip(handles, refs[:3])}
    for r in restored:
        assert r.done and list(r.tokens) == by_id[r.req_id]


def test_sigterm_flag_converts_run_into_drain(engine):
    srv = InferenceServer(engine, num_slots=2, chunk=2)
    h = srv.submit([3, 17, 42], 4)
    srv.step()
    srv._on_signal(15, None)                # what the SIGTERM handler does
    srv.run()                               # notices the flag -> drains
    assert srv._shutdown and h.done
    assert any(e["kind"] == "serving_shutdown" for e in telemetry.events())


# ========================================================== /requests route


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_requests_route_live_and_404(engine, monkeypatch, tmp_path):
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    # No provider registered: the route 404s (an endpoint without a server).
    ep = introspect.maybe_start()
    assert ep is not None
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(ep.url() + "requests")
    assert ei.value.code == 404
    ep.stop()

    journal = RequestJournal(tmp_path / "j.jsonl", fsync_every=1)
    srv = InferenceServer(engine, num_slots=2, chunk=2, journal=journal)
    assert srv._introspect is not None
    base = srv._introspect.url()
    live: dict[str, object] = {}

    def on_token(req, token, index):
        if not live:
            live["requests"] = _get(base + "requests")
            live["healthz"] = _get(base + "healthz")

    handles = [srv.submit([3, 17, 42], 5, on_token=on_token),
               srv.submit([8, 1], 4, on_token=on_token),
               srv.submit([9, 9, 9], 3, on_token=on_token)]
    try:
        srv.run()
        assert all(h.done for h in handles)

        code, body = live["requests"]
        assert code == 200
        req_view = json.loads(body)
        assert req_view["backend"] == "xla"
        assert req_view["mesh_epoch"] == 0
        assert req_view["shutting_down"] is False
        # Scraped mid-serve: 2 slots busy, 1 request queued behind them.
        busy = [s for s in req_view["slots"] if "req_id" in s]
        assert busy and any(s["n_tokens"] >= 1 for s in busy)
        assert req_view["queue_depth"] + len(busy) >= 2
        assert req_view["journal"]["fsync_every"] == 1
        assert req_view["journal"]["path"].endswith("j.jsonl")

        code, body = live["healthz"]
        assert code == 200
        health = json.loads(body)
        assert health["mesh"]["epoch"] == 0
        assert health["mesh"]["dead_ranks"] == {}
    finally:
        srv.shutdown(drain=True)
    # Shutdown cleared the provider and stopped the endpoint.
    assert srv._introspect is None


def test_healthz_reports_dead_ranks(engine, monkeypatch):
    monkeypatch.setenv("TDT_HTTP_PORT", "0")
    srv = InferenceServer(engine, num_slots=1, chunk=2)
    base = srv._introspect.url()
    try:
        resilience.declare_rank_dead(1, reason="lease expired")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "healthz")          # dead rank -> degraded -> 503
        assert ei.value.code == 503
        health = json.loads(ei.value.read().decode())
        assert health["mesh"]["epoch"] == 1
        assert "lease expired" in health["mesh"]["dead_ranks"]["1"]
    finally:
        srv.shutdown(drain=True)
