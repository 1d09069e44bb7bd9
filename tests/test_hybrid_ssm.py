"""The Mamba / sliding-window / shared-K/V decoder (``HybridSSMLLM``)
against its plain reference (``tests/hybrid_ssm_ref.py``), on the CPU at the
tiny preset: 8 layers with every kind present (Mamba x3, window x2, full,
gated memory unit, cross), a window of 8 under prompts of 5-40.

Tolerance: program and reference both compute in float32 here and differ in
the order of their sums (a chunk's scan from a carried state, a ring in
place of a mask, the softmax over a table's extent), which moves a logit of
size ~1 by some 1e-6; ``TOL`` leaves two orders of room, and the bfloat16
case shows that a lower precision than stated fails it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_ssm_ref as ref
import paged_drive
from triton_dist_tpu.layers import hybrid_ssm as hs
from triton_dist_tpu.models import (
    HYBRID_SSM_PRESETS, PRESETS, DenseLLM, Engine, HybridSSMConfig, HybridSSMLLM,
    LatentSparseConfig, LatentSparseLLM)
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import initialize_distributed
from triton_dist_tpu.serving import InferenceServer

TOL = 2e-4
CFG = HybridSSMConfig()
W = CFG.sliding_window
T_REF = 64


@pytest.fixture(scope="module")
def ctx():
    return initialize_distributed(
        devices=jax.devices()[:1], axis_names=("tp",), set_default=False)


@pytest.fixture(scope="module")
def model(ctx):
    return HybridSSMLLM(CFG, ctx, key=jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def engine(model):
    return Engine(model, backend="dist", max_len=T_REF)


@pytest.fixture(scope="module")
def ref_forward():
    run = jax.jit(lambda params, tokens: ref.forward(CFG, params, tokens))

    def padded(params, tokens):
        seq = np.zeros((T_REF,), np.int32)
        seq[: len(tokens)] = tokens  # padding sits in every row's future
        return np.asarray(run(params, seq))[: len(tokens)]

    return padded


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n).tolist()


def _join(eng, paged, slot, ids, chunk):
    """Prefill ``ids`` into ``slot`` in chunks of ``chunk`` (the last one
    padded), as the server's join does. Returns (last row's logits, every
    chunk's logits, paged')."""
    p_len = len(ids)
    c = min(chunk, p_len)
    kbuf, vbuf = eng.paged_kbuf_zeros(p_len)
    state = eng.prompt_state()
    every = []
    for off in range(0, p_len, c):
        take = ids[off:off + c]
        rows = np.zeros((1, c), np.int32)
        rows[0, : len(take)] = take
        final = off + len(take) >= p_len
        logits, kbuf, vbuf, state = eng.prefill_chunk_state(
            kbuf, vbuf, jnp.asarray(rows), off, (p_len - 1 - off) if final else c - 1, state)
        every.append(np.asarray(logits[0]))
    paged = eng.complete_paged_prefill(paged, kbuf, vbuf, paged.tables[slot], 0, slot, state)
    paged = dataclasses.replace(paged, lengths=paged.lengths.at[slot].set(p_len))
    return every[-1], every, paged


def _decode(eng, paged, tokens, remaining, chunk=1):
    """(next-token logits before the chunk, paged after it)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = np.asarray(eng.decode_logits_paged(paged, tokens))
    _, _, paged, _ = eng.decode_steps_paged(paged, tokens, jnp.asarray(remaining, jnp.int32), chunk)
    return logits, paged


@pytest.mark.parametrize("p_len", [5, W, 29], ids=["under_the_window", "the_window", "past_it"])
def test_whole_prompt_in_one_chunk_matches_the_forward_pass(model, engine, ref_forward, p_len):
    ids = _ids(p_len, seed=p_len)
    got, _, _ = _join(engine, paged_drive.alloc_chains(engine, 1), 0, ids, chunk=T_REF)
    assert np.abs(got - ref_forward(model.params, ids)[-1]).max() <= TOL


@pytest.mark.parametrize("chunk", [5, W, 12], ids=["under_the_window", "the_window", "over_it"])
def test_chunked_prefill_then_decode_matches_the_forward_pass(model, engine, ref_forward, chunk):
    """A prompt of 29 (no whole number of chunks of 5, 8 or 12) prefilled in
    chunks carrying the conv tail, the state and the rings, then 11 decode
    steps through the pool, the rings (past their first wrap and their
    third) and the state: every step's logits are the full forward's. A
    chunk that is not the prompt's last computes no logits (zeros): the
    layers above the full layer run for the last row alone."""
    ids = _ids(40, seed=1)
    want = ref_forward(model.params, ids)
    paged = paged_drive.alloc_chains(engine, 3)
    last, every, paged = _join(engine, paged, 1, ids[:29], chunk)
    assert np.abs(last - want[28]).max() <= TOL
    assert all(not e.any() for e in every[:-1]) and len(every) == -(-29 // chunk)
    for t in range(29, 40):
        logits, paged = _decode(engine, paged, [0, ids[t], 0], [0, 1, 0])
        assert np.abs(logits[1] - want[t]).max() <= TOL, t
    assert np.asarray(paged.lengths).tolist() == [0, 40, 0]


def test_ring_past_its_first_wrap_from_a_prompt_shorter_than_the_window(
        model, engine, ref_forward):
    ids = _ids(5 + 2 * W + 3, seed=2)
    want = ref_forward(model.params, ids)
    _, _, paged = _join(engine, paged_drive.alloc_chains(engine, 1), 0, ids[:5], chunk=T_REF)
    for t in range(5, len(ids)):
        logits, paged = _decode(engine, paged, [ids[t]], [1])
        assert np.abs(logits[0] - want[t]).max() <= TOL, t


def test_a_slot_reused_by_a_shorter_prompt_keeps_nothing_of_the_longer(
        model, engine, ref_forward):
    """Slot 0 serves 32 tokens, then a prompt of 5: the second tenant's
    state starts from zeros, its ring holds nothing of the first's. (The
    joins are of shapes other tests here compile: a program a (chunk,
    prompt) pair is this file's cost.)"""
    first, second = _ids(29, seed=3), _ids(11, seed=4)
    paged = paged_drive.alloc_chains(engine, 2)
    _, _, paged = _join(engine, paged, 0, first, chunk=12)
    for t in range(3):
        _, paged = _decode(engine, paged, [first[t], 0], [1, 0])
    want = ref_forward(model.params, second)
    last, _, paged = _join(engine, paged, 0, second[:5], chunk=T_REF)
    assert np.abs(last - want[4]).max() <= TOL
    for t in range(5, 11):
        logits, paged = _decode(engine, paged, [second[t], 0], [1, 0])
        assert np.abs(logits[0] - want[t]).max() <= TOL, t


def test_two_lengths_and_an_inactive_slot_in_one_decode_chunk(model, engine, ref_forward):
    """Slots 0 and 2 decode at lengths 5 and 29 in one chunk of 4 steps in
    which slot 2 runs out after 2, while slot 1 (a finished tenant, its
    state left where it stopped) sits idle: the idle slot's state, length
    and ring do not move, and the others' next logits are the reference's."""
    a, b, idle = _ids(10, seed=5), _ids(32, seed=6), _ids(W, seed=7)
    paged = paged_drive.alloc_chains(engine, 3)
    _, _, paged = _join(engine, paged, 0, a[:5], chunk=T_REF)
    _, _, paged = _join(engine, paged, 1, idle, chunk=T_REF)
    _, _, paged = _join(engine, paged, 2, b[:29], chunk=12)
    before = jax.tree.map(lambda x: np.asarray(x[1]), paged.state)
    # greedy continuations are the program's own: teacher-force through the reference
    out, tok, paged, rem = engine.decode_steps_paged(
        paged, jnp.asarray([a[5], 3, b[29]], jnp.int32), jnp.asarray([4, 0, 2], jnp.int32), 4)
    out = np.asarray(out)
    assert out[1].tolist() == [-1] * 4 and out[2, 2:].tolist() == [-1, -1]
    assert np.asarray(paged.lengths).tolist() == [9, W, 31]
    for x, y in zip(jax.tree.leaves(before),
                    jax.tree.leaves(jax.tree.map(lambda x: np.asarray(x[1]), paged.state))):
        np.testing.assert_array_equal(x, y)
    for slot, seq, n in ((0, a[:6], 4), (2, b[:30], 2)):
        seq = seq + out[slot, :n].tolist()
        want = ref_forward(model.params, seq)
        for i in range(n):  # each emitted token is the reference's first choice
            row = want[len(seq) - n - 1 + i]
            assert row.max() - row[seq[len(seq) - n + i]] <= TOL
        logits = np.asarray(engine.decode_logits_paged(paged, tok))
        assert np.abs(logits[slot] - want[-1]).max() <= TOL


def test_mamba_in_its_three_forms(model):
    """A whole sequence, two chunks with a padded second one, and single
    steps give the same rows, the same scan output and the same state."""
    lp = model.params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(1), (13, CFG.hidden_size))
    want, want_m = jax.jit(hs.mamba_sequence)(lp, u)
    ref_out, ref_m = jax.jit(ref.mamba)(jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), lp), u)
    np.testing.assert_allclose(np.asarray(want), np.asarray(ref_out), atol=2e-5)
    np.testing.assert_allclose(np.asarray(want_m), np.asarray(ref_m), atol=2e-5)
    tail = jnp.zeros((CFG.d_conv - 1, CFG.d_inner))
    s = jnp.zeros((CFG.d_state, CFG.d_inner))
    chunk = jax.jit(hs.mamba_chunk)
    o1, m1, tail, s = chunk(lp, u[:8], tail, s, 8)
    padded = jnp.concatenate([u[8:], jnp.ones((3, CFG.hidden_size))])
    o2, m2, tail, s = chunk(lp, padded, tail, s, 5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2[:5]])), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([m1, m2[:5]])), np.asarray(want_m),
                               atol=2e-5)
    st, ss = jnp.zeros((2,) + tail.shape), jnp.zeros((2,) + s.shape)
    active = jnp.asarray([True, False])
    step = jax.jit(hs.mamba_step)
    for t in range(13):
        o, m, st, ss = step(lp, jnp.stack([u[t], u[0]]), st, ss, active)
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want[t]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(st[0]), np.asarray(tail), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ss[0]), np.asarray(s), atol=2e-5)
    assert not np.asarray(st[1]).any() and not np.asarray(ss[1]).any()


def test_differential_attention_matches_the_pairwise_form(model):
    layer = CFG.layers_of("full")[0]
    lp = model.params["layers"][layer]
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (9, CFG.num_q_heads, CFG.head_dim))
    k = jax.random.normal(ks[1], (9, CFG.num_kv_heads, CFG.head_dim))
    v = jax.random.normal(ks[2], (9, CFG.num_kv_heads, CFG.head_dim))
    mask = jnp.tril(jnp.ones((9, 9), bool))
    attend = jax.jit(lambda q, k, v, mask: hs.diff_attend(
        q, k, v, mask, hs.diff_lambda(lp, layer), layer, lp["subln"], CFG.layer_norm_eps))
    got = hs.mm(attend(q, k, v, mask), lp["w_o"]) + lp["b_o"]
    want = jax.jit(lambda *a: ref.diff_attention(CFG, lp, layer, *a))(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # one query a slot over whole rows: the block-diagonal form's numbers
    rows = lambda x: jnp.stack([x.reshape(9, -1), x[::-1].reshape(9, -1)])
    seen = jnp.stack([mask[5], mask[3]])
    by_rows = jax.jit(lambda q, k, v, mask: hs.diff_attend_rows(
        q, k, v, mask, hs.diff_lambda(lp, layer), layer, lp["subln"], CFG.layer_norm_eps))(
            jnp.stack([q[5], q[3]]), rows(k), rows(v), seen)
    one = jax.jit(lambda q, k, v, mask: hs.diff_attend(
        q, k, v, mask, hs.diff_lambda(lp, layer), layer, lp["subln"], CFG.layer_norm_eps))
    for i, (row, keys, vals) in enumerate(((5, k, v), (3, k[::-1], v[::-1]))):
        np.testing.assert_allclose(
            np.asarray(by_rows[i]), np.asarray(one(q[row][None], keys, vals, seen[i][None])[0]),
            atol=2e-5)


def test_bfloat16_program_fails_the_tolerance(ctx, model, ref_forward):
    low = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("a_log", "d", "b_dt") else a.astype(jnp.bfloat16),
        model.params)
    eng = Engine(HybridSSMLLM(dataclasses.replace(CFG, dtype="bfloat16"), ctx, params=low),
                 backend="dist", max_len=T_REF)
    ids = _ids(29, seed=8)
    got, _, _ = _join(eng, paged_drive.alloc_chains(eng, 1), 0, ids, chunk=T_REF)
    assert np.abs(got - ref_forward(model.params, ids)[-1]).max() > 10 * TOL


# ------------------------------------------------------------- the server


def _serve(eng, sizes, **kw):
    srv = InferenceServer(eng, num_slots=2, chunk=4, prefill_chunk=12, **kw)
    reqs = [srv.submit(_ids(n, seed=20 + i), new) for i, (n, new) in enumerate(sizes)]
    seen = {id(r): {} for r in reqs}
    for _ in range(80):
        srv.step()
        srv._land_in_flight()  # the cache and the last tokens of the same chunk
        decoding = srv.scheduler.decoding_slots()
        if decoding:
            logits = np.asarray(eng.decode_logits_paged(srv.cache, jnp.asarray(srv._last)))
            for slot in decoding:
                r = slot.request
                seen[id(r)][len(r.prompt) + len(r.tokens) - 1] = logits[slot.idx]
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(len(r.tokens) == new for r, (_, new) in zip(reqs, sizes))
    return srv, [(r, seen[id(r)]) for r in reqs]


def test_served_matches_reference_and_counts_the_window(model, engine, ref_forward):
    """Three requests on two slots (the third joins the slot the first
    left): every decode step's logits and every served token against the
    reference; the window layers' counters are ``min(length, window)`` over
    ``length`` exactly, padded rows and idle slots in neither; the rows
    scanned and the rows a decode step really advanced are counted."""
    telemetry.reset()
    sizes = [(30, 6), (17, 11), (5, 9)]
    srv, served = _serve(engine, sizes)
    worst = 0.0
    for r, seen in served:
        want = ref_forward(model.params, r.prompt + r.tokens)
        assert seen, "no decode step was compared"
        for position, got in seen.items():
            worst = max(worst, float(np.abs(got - want[position]).max()))
        for i, tok in enumerate(r.tokens):
            row = want[len(r.prompt) - 1 + i]
            assert row.max() - row[tok] <= TOL
    assert worst <= TOL, worst
    n_win = len(CFG.layers_of("window"))
    for phase, spans in (("prefill", [(1, p) for p, _ in sizes]),
                         ("decode", [(p + 1, p + new - 1) for p, new in sizes])):
        lengths = np.concatenate([np.arange(a, b + 1) for a, b in spans])
        value = lambda name: telemetry.counter_value(name, phase=phase)
        assert value("tdt_swa_positions_visible_total") == n_win * lengths.sum(), phase
        assert value("tdt_swa_positions_attended_total") == n_win * np.minimum(lengths, W).sum()
        assert value("tdt_ssm_tokens_total") == len(lengths), phase
    decode_rows = sum(new - 1 for _, new in sizes)
    assert telemetry.counter_total("tdt_serving_decode_rows_total") == decode_rows
    chunks = telemetry.counter_total("tdt_serving_decode_chunks_total")
    assert 0 < decode_rows / (chunks * 4) <= 2
    srv.shutdown(drain=False)


def test_state_is_declared_priced_and_kept_out_of_the_pool(ctx, model, engine):
    """The pool holds ONE layer's K/V rows for the eight layers that read
    them (5120 B a token at the published widths); the slots' state is a
    kind of its own in ``tdt_kv_pool_bytes`` and in the ledger; the models
    that keep none have the empty state, which is no operand."""
    telemetry.reset()
    srv = InferenceServer(engine, num_slots=3, chunk=4)
    c, bs = CFG, srv.block_size
    assert srv.cache.k.shape == srv.cache.v.shape == (
        1, srv.num_blocks, 1, bs, c.num_kv_heads * c.head_dim)
    state = srv.cache.state
    assert [len(state[k]) for k in ("conv", "ssm", "ring_k", "ring_v")] == [3, 3, 2, 2]
    assert state["ssm"][0].shape == (3, c.d_state, c.d_inner)
    assert state["ssm"][0].dtype == jnp.float32
    assert state["ring_k"][0].shape == (3, W, c.num_kv_heads * c.head_dim)
    a_slot = (3 * (c.d_state * c.d_inner + (c.d_conv - 1) * c.d_inner)
              + 2 * 2 * W * c.num_kv_heads * c.head_dim) * 4
    assert srv.cache.slot_state_bytes == 3 * a_slot
    assert telemetry.gauge_value("tdt_kv_pool_bytes", kind="slot_state") == 3 * a_slot
    assert srv.kv_ledger.stats()["bytes_slot_state"] == 3 * a_slot
    assert srv.cache.bytes_per_block == 2 * c.num_kv_heads * c.head_dim * bs * 4
    srv.shutdown(drain=False)
    real = HYBRID_SSM_PRESETS["phi4flash"]
    rows = HybridSSMLLM.cache_rows(type("M", (), {"config": real}))
    assert sum(r.layers * r.heads * r.width for r in rows) * 2 == 5120
    per_slot = jax.eval_shape(lambda: HybridSSMLLM.slot_state(type("M", (), {"config": real}), 1))
    assert sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(per_slot)) == (
        9 * (16 * 5120 * 4 + 3 * 5120 * 2) + 8 * 2 * 512 * 20 * 64 * 2)
    for other in (DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(0)),
                  LatentSparseLLM(LatentSparseConfig(), ctx, key=jax.random.PRNGKey(0))):
        eng = Engine(other, backend="xla" if isinstance(other, DenseLLM) else "dist", max_len=32)
        paged = eng.alloc_paged(2, block_size=8, num_blocks=9)
        assert not eng.stateful and paged.state == () and eng.prompt_state() == ()
        args = (other.params, eng._decode_extra, jnp.zeros((2,), jnp.int32), paged.k, paged.v,
                paged.tables, paged.lengths, jnp.ones((2,), jnp.int32), 2, jax.random.PRNGKey(0))
        operands = jax.tree.leaves(eng._decode_chunk_paged.lower(*args).in_avals)
        assert len(operands) == len(jax.tree.leaves(args)) - 1  # the chunk size is static


def test_the_server_refuses_what_rests_on_the_pool_alone(model, engine):
    """A prefix hit, a rejected draft's rewind and a KV handoff rest on the
    pool's blocks alone; for a model with per-slot state each is refused or
    turned off explicitly."""
    telemetry.reset()
    with pytest.raises(ValueError, match="cannot be rewound"):
        InferenceServer(engine, num_slots=2, chunk=4, spec_k=2)
    srv = InferenceServer(engine, num_slots=2, chunk=4, prefill_chunk=12)
    assert srv.stateful and not srv.kv_ledger.prefix_reuse
    shared = _ids(24, seed=9)  # two requests with three whole blocks in common
    reqs = [srv.submit(shared + _ids(3, seed=10 + i), 3) for i in range(2)]
    srv.run()
    assert all(r.done and len(r.tokens) == 3 for r in reqs)
    assert telemetry.counter_total("tdt_serving_prefix_lookups_skipped_total") == 2
    assert telemetry.counter_total("tdt_kv_prefix_hits_total") == 0
    assert srv.kv_ledger.prefix.num_blocks_indexed == 0
    with pytest.raises(ValueError, match="token history"):
        srv.export_kv(reqs[0].req_id)
    with pytest.raises(ValueError, match="token history"):
        srv.import_kv(shared, 4, [1], {})
    srv.shutdown(drain=False)


@pytest.mark.chaos
def test_a_recovered_request_is_prefilled_again_from_its_history(engine, monkeypatch):
    """An abort in the second decode chunk: the rebuild re-prefills every
    in-flight request from its tokens, which rebuilds its state, and the
    streams are those of the same requests served undisturbed (first, on
    the module's engine at the served test's shapes, whose programs are
    there: what this test has to compile is the recovery's rebuild)."""
    from triton_dist_tpu.runtime import resilience

    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    telemetry.reset()
    resilience.reset_degradation()
    prompts = [_ids(30, seed=20), _ids(17, seed=21)]

    def serve(schedule):
        srv = InferenceServer(engine, num_slots=2, chunk=4, prefill_chunk=12)
        reqs = [srv.submit(p, 7) for p in prompts]
        with resilience.chaos_schedule(schedule):
            srv.run()
        srv.shutdown(drain=False)
        assert all(r.done for r in reqs)
        return [list(r.tokens) for r in reqs]

    try:
        want = serve("heal")
        got = serve("abort@decode:1,heal")
        assert telemetry.counter_value("tdt_serving_recoveries_total", from_backend="dist") == 1.0
        assert got == want and all(len(t) == 7 for t in got)
    finally:
        resilience.reset_degradation()
        engine.rebuild("dist")
