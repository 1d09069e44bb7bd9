"""Speculative decoding acceptance bar (``models/drafter.py`` +
``Engine.spec_decode_steps_paged`` + the serving integration).

The contract under test (docs/speculative.md): greedy speculative decode
is **byte-identical** to plain greedy decode — the k-wide verify step
scores every draft with the target's own decode program, emitted tokens
are the target's argmaxes, and rejection rolls the paged pool back by a
pure length rewind. Anchored here:

* engine-level parity on the block pool (truncated AND GDN drafters —
  parity is drafter-independent by construction);
* serving-loop parity on both verify lowerings (xla: the contiguous bounce
  with accepted rows scattered back; mega: the pool in place) with
  staggered joins, plus the
  zero-recompile guarantee: one jit cache entry per (chunk, k) no matter
  how batch composition, kcap, or acceptance patterns move;
* the rollback invariant, forced acceptance pattern by acceptance pattern
  with a ``ScriptedDrafter``: pool free list, refcounts, block-table
  mirror, and device lengths stay byte-identical to a never-speculated
  run at every aligned stream position and after teardown;
* the ``chaos``-marked arc: abort mid-verify -> degraded xla recovery
  (zero dropped/duplicated tokens) -> probe restores mega, with
  speculation still armed and accepting afterwards.

Runs on CPU with world=1 under the generic-interpreter fallback, same as
the serving tests.
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
def model1():
    from triton_dist_tpu.models import PRESETS, DenseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    return DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(1))


# =============================================== engine-level k-wide verify


def _joined(eng, prompts):
    """A pool with every prompt joined into its slot: (paged, token0s)."""
    from paged_drive import alloc_chains, join

    paged = alloc_chains(eng, len(prompts))
    toks = []
    for i, p in enumerate(prompts):
        t0, paged = join(eng, paged, i, p)
        toks.append(t0)
    return paged, toks


def _engine_reference(eng, prompts, gens):
    """Plain batched ``decode_steps_paged`` streams, one list per slot."""
    paged, toks = _joined(eng, prompts)
    last = jnp.asarray(toks, jnp.int32)
    remaining = jnp.asarray([g - 1 for g in gens], jnp.int32)
    ref = [[t] for t in toks]
    while int(jnp.max(remaining)) > 0:
        out, last, paged, remaining = eng.decode_steps_paged(paged, last, remaining, 3)
        o = np.asarray(out)
        for b in range(len(prompts)):
            ref[b].extend(int(x) for x in o[b] if x >= 0)
    return ref, toks


def _engine_spec_run(eng, drafter, prompts, gens, token0s, kcaps):
    """Drive ``spec_decode_steps_paged`` to completion; returns (streams,
    stats, the spec program's cache size after every chunk)."""
    B = len(prompts)
    paged, toks = _joined(eng, prompts)
    assert toks == token0s
    dstate = drafter.init_state(B)
    for i, p in enumerate(prompts):
        dstate = drafter.prefill_state(dstate, i, p)
    last = jnp.asarray(token0s, jnp.int32)
    remaining = jnp.asarray([g - 1 for g in gens], jnp.int32)
    spec = [[t] for t in token0s]
    stats_tot = np.zeros((B, 3), np.int64)
    sizes = []
    it = 0
    while int(jnp.max(remaining)) > 0:
        # Vary the adaptive width mid-run: kcap is DATA, not a jit key.
        kcap = jnp.asarray(kcaps[min(it, len(kcaps) - 1)], jnp.int32)
        out, last, paged, remaining, dstate, stats = eng.spec_decode_steps_paged(
            paged, dstate, last, remaining, kcap, 2, 3
        )
        o = np.asarray(out)
        stats_tot += np.asarray(stats)
        for b in range(B):
            spec[b].extend(int(x) for x in o[b] if x >= 0)
        sizes.append(eng._spec_chunk._cache_size())
        it += 1
    return spec, stats_tot, sizes


def test_spec_engine_parity_paged(model1):
    """Byte parity of the k-wide verify against plain greedy decode on the
    block pool — truncated AND GDN drafters, with kcap moving mid-run and a
    single jit cache entry at the end (zero recompiles)."""
    from triton_dist_tpu.models import Engine, GDNDrafter, TruncatedDrafter

    prompts = [[3, 5, 7, 2], [11, 4, 9], [1, 2]]
    gens = [8, 6, 7]
    eng = Engine(model1, backend="xla", max_len=MAX_LEN)
    ref, token0s = _engine_reference(eng, prompts, gens)
    for p, g, r in zip(prompts, gens, ref):
        np.testing.assert_array_equal(
            r, np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0])

    eng2 = Engine(model1, backend="xla", max_len=MAX_LEN)
    dr = TruncatedDrafter(model1, num_layers=2, max_len=MAX_LEN, block_size=4)
    eng2.attach_drafter(dr)
    kcaps = [[3, 3, 3], [3, 2, 1], [1, 3, 2]]
    spec, stats, sizes = _engine_spec_run(eng2, dr, prompts, gens, token0s, kcaps)
    assert spec == ref
    # The truncated drafter shares the target's front layers: it proposes
    # well enough that rounds accept > 1 token on average.
    assert stats[:, 1].sum() > stats[:, 2].sum()
    # Zero recompiles: (chunk, k) are the only static keys. The jit cache
    # picks up one extra entry when the call-1 arguments switch from
    # freshly-built host arrays to committed jit outputs (same trace, same
    # executable) — after that it must never grow again, no matter how
    # kcap or acceptance move.
    assert sizes[-1] <= 2 and all(s == sizes[1] for s in sizes[1:])

    # Drafter-independence: a weak (untrained GDN) drafter accepts less
    # but must emit the exact same stream — acceptance only gates HOW MANY
    # of the target's own argmaxes ship per round, never WHICH.
    gdn = GDNDrafter(model1, key=jax.random.PRNGKey(3))
    eng2.attach_drafter(gdn)
    spec_g, stats_g, _ = _engine_spec_run(eng2, gdn, prompts, gens, token0s,
                                          [[3, 3, 3]])
    assert spec_g == ref
    assert stats_g[:, 1].sum() >= stats_g[:, 2].sum()  # >= 1 token/round


# ================================================= serving-loop byte parity

REQUESTS = [
    ([3, 5, 7, 2], 8),
    ([11, 4, 9], 6),
    ([1, 2], 7),
    ([8, 8, 1], 5),
    ([2, 9, 9, 9, 4], 6),
]


def _one_shot_refs(eng):
    return [
        np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0]
        for p, g in REQUESTS
    ]


@pytest.mark.parametrize("backend", ["xla", "mega"])
def test_spec_serving_parity_staggered(model1, backend):
    """The acceptance bar: a spec-enabled InferenceServer streams
    byte-identical tokens to one-shot non-speculative greedy serve, with
    staggered joins, on both verify lowerings — and the whole run compiles
    the spec chunk exactly once."""
    from triton_dist_tpu.models import Engine

    eng = Engine(model1, backend=backend, max_len=MAX_LEN)
    refs = _one_shot_refs(eng)
    telemetry.reset()

    eng2 = Engine(model1, backend=backend, max_len=MAX_LEN)
    srv = InferenceServer(eng2, num_slots=3, chunk=2, spec_k=3)
    assert srv.spec_k == 3
    streams: dict[int, list[int]] = {}

    def on_token(req, token, index):
        streams.setdefault(req.req_id, []).append(token)
        assert index == len(streams[req.req_id]) - 1

    handles = [
        srv.submit(p, g, on_token=on_token) for p, g in REQUESTS[:4]
    ]
    assert srv.step()
    assert srv.step()
    # Late arrival joins MID-decode: batch composition changes, no retrace.
    handles += [srv.submit(p, g, on_token=on_token) for p, g in REQUESTS[4:]]
    srv.run()

    for h, (_, g), ref in zip(handles, REQUESTS, refs):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert streams[h.req_id] == list(h.tokens)
        assert len(h.tokens) == g

    proposed = telemetry.counter_total("tdt_spec_proposed_total")
    accepted = telemetry.counter_total("tdt_spec_accepted_total")
    assert proposed > 0 and 0 < accepted <= proposed
    # tokens_total counts streamed-after-prefill tokens; every one of them
    # came through accept (journal/stream never see a rejected draft).
    assert telemetry.counter_value("tdt_serving_tokens_total") == float(
        sum(g for _, g in REQUESTS) - len(REQUESTS)
    )
    snap = telemetry.snapshot()
    assert any(name == "tdt_spec_accept_len" and entries
               for name, entries in snap["histograms"].items())

    # Zero-recompile in steady state: a SECOND wave of the same requests in
    # reversed arrival order (different batch composition, different
    # join/finish interleaving, fresh kcap/EWMA trajectories, prefix-cache
    # HITS this time) must not grow the spec program's cache —
    # (chunk, k) are the only static keys. Captured AFTER wave 1 because the
    # C++ fast-path cache key-splits on argument committed-ness (same single
    # trace — see the engine-level test), and all variants appear in wave 1.
    jfn = eng2._spec_chunk_paged if backend == "mega" else eng2._spec_chunk
    steady = jfn._cache_size()
    wave2 = list(reversed(REQUESTS))
    handles2 = [srv.submit(p, g, on_token=on_token) for p, g in wave2]
    srv.run()
    assert jfn._cache_size() == steady
    for h, (_, g), ref in zip(handles2, wave2, reversed(refs)):
        assert h.done
        np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
        assert len(h.tokens) == g


def test_spec_serving_non_greedy_refuses(model1):
    """Speculation is greedy-only: a sampling engine turns it OFF at
    construction (with an emitted event), never half-arms."""
    from triton_dist_tpu.models import Engine

    eng = Engine(model1, backend="xla", max_len=MAX_LEN,
                 sample="top_p", temperature=0.8, top_p=0.9)
    srv = InferenceServer(eng, num_slots=2, chunk=2, spec_k=3)
    assert srv.spec_k == 0
    assert any(e["kind"] == "serving_spec_disabled"
               for e in telemetry.events())


# ========================================= rollback invariants on the pool


def _scripted_rows(ref, k, schedule):
    """Draft table forcing the exact per-round accept counts ``schedule``.

    Position p streams next; a round accepting ``a`` needs drafts
    ``ref[p..p+a-2]`` (verified matches) then a poisoned cell at a-1 —
    ``tok ^ 1`` can never equal the target argmax, so the match run stops
    exactly there. Returns (rows, accepts) with accepts clipped to the
    engine's own per-round width ec = min(k, remaining)."""
    rows, accepts = [], []
    p, si = 1, 0
    while p < len(ref):
        ec = min(k, len(ref) - p)
        a = min(schedule[si % len(schedule)], ec)
        si += 1
        row = []
        for j in range(k):
            if j < a - 1:
                row.append(int(ref[p + j]))
            else:
                row.append(int(ref[min(p + j, len(ref) - 1)]) ^ 1)
        rows.append([row])  # B == 1
        accepts.append(a)
        p += a
    return rows, accepts


def _pool_state(srv):
    a = srv.kv_ledger.allocator
    return {
        "free": a.num_free,
        "ref": tuple(a.refcount(b) for b in range(a.num_blocks)),
        "tables": np.asarray(srv.cache.tables).tolist(),
        "lengths": np.asarray(srv.cache.lengths).tolist(),
        "ledger": srv.kv_ledger.stats(),
    }


@pytest.mark.parametrize(
    "schedule", [[1], [2], [3], [1, 2, 3], [3, 1, 2]],
    ids=["ones", "twos", "max", "cycle123", "cycle312"],
)
def test_spec_rollback_pool_invariants(model1, monkeypatch, schedule):
    """Acceptance-pattern sweep: force every accept count 1..k at every
    stream boundary with a ScriptedDrafter and assert the paged pool —
    free list, refcounts, block-table mirror, device lengths — is
    byte-identical to a never-speculated server at every aligned stream
    position, and fully freed after teardown. Rejected drafts leave ZERO
    trace: rollback is a pure length rewind on CoW-exclusive blocks."""
    from triton_dist_tpu.models import Engine, ScriptedDrafter

    prompt, max_new = [3, 5, 7, 2], 10
    # Pin kcap at spec_k: the EWMA can never fall below 0.0, so adaptive
    # backoff stays out of the way of the forced schedule.
    monkeypatch.setenv("TDT_SPEC_MIN_ACCEPT", "0.0")

    ref = list(
        np.asarray(
            Engine(model1, backend="xla", max_len=MAX_LEN).serve(
                jnp.asarray([prompt], jnp.int32), gen_len=max_new
            )
        )[0]
    )
    rows, accepts = _scripted_rows(ref, 3, schedule)
    assert set(accepts) <= {1, 2, 3} and sum(accepts) == max_new - 1

    # Never-speculated twin: same request, same pool geometry, chunk=1 so
    # its stream position advances one token per step (exact alignment).
    base_eng = Engine(model1, backend="xla", max_len=MAX_LEN)
    base = InferenceServer(base_eng, num_slots=1, chunk=1, spec_k=0)
    base_stream: list[int] = []
    bh = base.submit(prompt, max_new,
                     on_token=lambda r, t, i: base_stream.append(t))

    spec_eng = Engine(model1, backend="xla", max_len=MAX_LEN)
    srv = InferenceServer(spec_eng, num_slots=1, chunk=1, spec_k=3,
                          drafter=ScriptedDrafter(rows))
    stream: list[int] = []
    h = srv.submit(prompt, max_new, on_token=lambda r, t, i: stream.append(t))

    expect = 1  # token0 from prefill
    for a in accepts:
        assert srv.step()
        expect += a
        # The forced schedule really happened: each round accepted
        # exactly its scripted count.
        assert len(stream) == expect
        while len(base_stream) < len(stream):
            assert base.step()
            base._land_in_flight()  # a token a step, and the pool as of it
        state, base_state = _pool_state(srv), _pool_state(base)
        assert state == base_state, (
            f"pool state diverged at stream position {len(stream)}"
        )
    assert h.done
    base.run()
    assert h.done and bh.done
    assert stream == ref and base_stream == ref
    assert list(h.tokens) == ref

    # Teardown: every block freed, zero dangling refcounts, identical
    # mirrors — speculation left the pool exactly as plain decode did.
    final, base_final = _pool_state(srv), _pool_state(base)
    assert final == base_final
    assert final["ledger"]["blocks_used"] == final["ledger"]["blocks_shared"] == 0
    assert srv.kv_ledger.allocator.num_free == srv.num_blocks - 1

    assert telemetry.counter_total("tdt_spec_accepted_total") == float(
        max_new - 1
    )
    # kcap stayed pinned: the gauge never left spec_k under min_accept=0.
    assert srv._kcap[0] == 3
    # One trace, plus at most the committed-argument second cache entry.
    assert spec_eng._spec_chunk._cache_size() <= 2


# ============================================== chaos: abort mid-verify arc


@pytest.mark.chaos
def test_spec_chaos_abort_mid_verify_restores_mega(model1, monkeypatch):
    """Chaos abort lands INSIDE the spec decode dispatch: the breaker
    degrades mega -> xla with zero dropped/duplicated tokens (speculative
    state is rebuilt, only accepted tokens were ever journaled/streamed),
    the half-open probe restores mega in-process, and speculation is still
    armed and accepting on the restored backend."""
    from triton_dist_tpu.models import Engine

    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    telemetry.reset()
    resilience.reset_degradation()
    requests = [
        ([3, 17, 4, 7, 9], 6),
        ([8, 1, 13], 4),
        ([100, 200, 30], 5),
    ]
    ref_eng = Engine(model1, backend="xla", max_len=MAX_LEN)
    refs = [
        np.asarray(ref_eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0]
        for p, g in requests
    ]
    try:
        eng = Engine(model1, backend="mega", max_len=MAX_LEN)
        srv = InferenceServer(eng, num_slots=2, chunk=2, spec_k=3)
        streams: dict[int, list[int]] = {}
        with resilience.chaos_schedule("abort@decode:1,heal"):
            handles = [
                srv.submit(p, g, on_token=lambda r, t, i: streams.setdefault(
                    r.req_id, []).append(t))
                for p, g in requests
            ]
            srv.run()
            deadline = time.monotonic() + 30.0
            while eng.backend != "mega":
                assert time.monotonic() < deadline, "probe never restored mega"
                if not srv.step():
                    time.sleep(0.005)

        for h, ref in zip(handles, refs):
            assert h.done
            np.testing.assert_array_equal(np.asarray(h.tokens, np.int32), ref)
            assert streams[h.req_id] == list(h.tokens)
        assert eng.backend == "mega"
        assert not resilience.any_degraded()
        assert telemetry.counter_value(
            "tdt_serving_restores_total", to_backend="mega") == 1.0
        assert telemetry.counter_value(
            "tdt_serving_recoveries_total", from_backend="mega") == 1.0

        # Speculation survived the whole arc AND is live on restored mega:
        # a post-restore request still proposes/accepts.
        accepted0 = telemetry.counter_total("tdt_spec_accepted_total")
        assert accepted0 > 0
        post: list[int] = []
        ph = srv.submit([5, 6, 7], 5, on_token=lambda r, t, i: post.append(t))
        srv.run()
        assert ph.done and eng.backend == "mega"
        ref_post = np.asarray(
            ref_eng.serve(jnp.asarray([[5, 6, 7]], jnp.int32), gen_len=5)
        )[0]
        np.testing.assert_array_equal(np.asarray(ph.tokens, np.int32), ref_post)
        assert post == list(ph.tokens)
        assert telemetry.counter_total("tdt_spec_accepted_total") > accepted0
    finally:
        telemetry.reset()
        resilience.reset_degradation()
