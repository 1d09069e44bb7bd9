"""The latent-attention / sparse-selection / held-experts decoder against
its plain reference (``tests/latent_sparse_ref.py``), on the CPU at a toy
size with the published structure: 1 dense + 4 expert layers, indexers
full, shared, shared, shared, full, 16 experts of which 4 are held, top 2,
``index_topk`` 16 under contexts of 48-96 so that the selection binds, and
a nonzero router bias.

Tolerance of the logits comparisons: program and reference both compute in
float32 here, and differ in the order of their sums (online softmax over
key blocks, the absorbed form at decode, sorted tiles of expert rows), which
moves a logit of size ~1 by some 1e-6; ``TOL`` leaves two orders of room
and the bfloat16 case, which reads ~1e-2, shows it is tight enough that a
program computing in a lower precision than stated fails it.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_sparse_ref as ref
from triton_dist_tpu.layers import latent_sparse as ls
from triton_dist_tpu.models import (
    PRESETS, DenseLLM, Engine, LatentSparseConfig, LatentSparseLLM, PagedKVCache, kv_rows)
from triton_dist_tpu.runtime import resilience, telemetry
from triton_dist_tpu.runtime.mesh import initialize_distributed
from triton_dist_tpu.serving import InferenceServer

TOL = 2e-4
CFG = LatentSparseConfig(experts_held=(0, 4))
T_REF = 128  # every reference pass runs at this one (padded) length


@pytest.fixture(scope="module")
def ctx():
    return initialize_distributed(
        devices=jax.devices()[:1], axis_names=("tp",), set_default=False)


@pytest.fixture(scope="module")
def model(ctx):
    return LatentSparseLLM(CFG, ctx, key=jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def engine(model):
    return Engine(model, backend="dist", max_len=T_REF)


@pytest.fixture(scope="module")
def ref_forward():
    @jax.jit
    def run(params, tokens):
        return ref.forward(CFG, params, tokens)

    def padded(params, tokens):
        seq = np.zeros((T_REF,), np.int32)
        seq[: len(tokens)] = tokens  # padding sits in every row's future
        return np.asarray(run(params, seq))[: len(tokens)]

    return padded


def _serve(eng, prefill_chunk, n_requests=3):
    """Serve requests of 70 and 96 tokens on 2 slots (the third joins when the
    first leaves) and return [(request, {position: program logits})]: after
    every loop iteration, the next-token logits of each decoding slot from
    the engine's own paged step over the server's cache."""
    srv = InferenceServer(eng, num_slots=2, chunk=4, prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(3)
    sizes = [(70, 6), (96, 11), (70, 9)][:n_requests]  # two lengths: two compiled shapes
    reqs = [srv.submit(rng.integers(0, CFG.vocab_size, size=n).tolist(), new)
            for n, new in sizes]
    seen = {id(r): {} for r in reqs}
    for _ in range(60):
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
    srv.shutdown(drain=False)
    assert all(len(r.tokens) == new for r, (_, new) in zip(reqs, sizes))
    return [(r, seen[id(r)]) for r in reqs]


@pytest.mark.parametrize("prefill_chunk", [T_REF, 32], ids=["whole_prompt", "chunked"])
def test_served_matches_reference(model, engine, ref_forward, prefill_chunk):
    """Prefill (whole or in chunks of 32 with a padded last one), then paged
    decode through a join and a leave, against the reference's full forward
    pass of each finished sequence: every decode step's logits, and every
    served token the reference's first choice."""
    telemetry.reset()
    worst = 0.0
    served = _serve(engine, prefill_chunk)
    for r, seen in served:
        seq = r.prompt + r.tokens
        want = ref_forward(model.params, seq)
        assert seen, "no decode step was compared"
        for position, got in seen.items():
            worst = max(worst, float(np.abs(got - want[position]).max()))
        for i, tok in enumerate(r.tokens):  # token 0 is the prefill's
            row = want[len(r.prompt) - 1 + i]
            assert row.max() - row[tok] <= TOL
    assert worst <= TOL, worst
    assert telemetry.counter_total("tdt_ep_dropped_tokens_total") == 0
    assert telemetry.counter_total("tdt_ep_dispatch_total") > 0
    # what the device counted on its two selecting layers is the exact
    # selection's arithmetic on the lengths: min(visible, index_topk) a real
    # query (the padded chunk's rows and the idle slot's are in neither)
    sizes = [(len(r.prompt), len(r.tokens)) for r, _ in served]
    for phase, spans in (("prefill", [(1, p) for p, _ in sizes]),
                         ("decode", [(p + 1, p + new - 1) for p, new in sizes])):
        seen = np.concatenate([np.arange(a, b + 1) for a, b in spans])
        got = [telemetry.snapshot()["counters"][f"tdt_dsa_positions_{what}_total"]
               for what in ("visible", "selected")]
        got = [sum(e["value"] for e in g if e["labels"]["phase"] == phase) for g in got]
        assert got == [2 * seen.sum(), 2 * np.minimum(seen, CFG.index_topk).sum()], phase
    held = sum(e["value"] for e in telemetry.snapshot()["counters"]["tdt_ep_expert_tokens_total"])
    assert held == 4 * CFG.experts_per_token * sum(p + new - 1 for p, new in sizes)


def _chunk_logits(model, params, tokens):
    """Logits of the last row of ``tokens`` from one whole-prompt chunk."""
    c = model.config
    n = len(tokens)
    rows = model.cache_rows()
    bufs = [jnp.zeros((r.layers, 1, r.heads, n, r.width), jnp.dtype(c.dtype)) for r in rows]
    if not hasattr(model, "_test_chunk"):  # one jitted object a model
        model._test_chunk = jax.jit(model.prefill_chunk_shard, static_argnums=(6,))
    logits, _, _ = model._test_chunk(
        params, jnp.asarray([tokens], jnp.int32), bufs[0], bufs[1],
        jnp.int32(0), jnp.int32(n - 1), "dist_ar")
    return np.asarray(logits[0])


def _with_indexer(params, layer, **changed):
    layers = list(params["layers"])
    layers[layer] = {**layers[layer], **changed}
    return {**params, "layers": layers}


def test_bfloat16_program_fails_the_tolerance(ctx, model, ref_forward):
    """The same weights computed in bfloat16 on the program's side lie far
    outside ``TOL``: the comparison would catch a lower precision."""
    keep = ("router", "router_bias")
    low = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in keep else a.astype(jnp.bfloat16), model.params)
    tokens = np.random.default_rng(11).integers(0, CFG.vocab_size, size=56).tolist()
    got = _chunk_logits(
        LatentSparseLLM(dataclasses.replace(CFG, dtype="bfloat16"), ctx, params=low), low, tokens)
    assert np.abs(got - ref_forward(model.params, tokens)[-1]).max() > 10 * TOL


@pytest.mark.parametrize("case", ["under_topk_is_dense", "shared_borrows_full"])
def test_selection(ctx, model, ref_forward, case):
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, CFG.vocab_size, size=56).tolist()
    p = model.params
    noise = jax.random.normal(jax.random.PRNGKey(5), p["layers"][0]["w_iq"].shape)
    if case == "under_topk_is_dense":
        # A context no longer than index_topk: every visible position is
        # selected, so the indexer cannot matter and the result is dense
        # latent attention (the reference with no selection at all).
        wide = LatentSparseLLM(dataclasses.replace(CFG, index_topk=64), ctx, params=p)
        base = _chunk_logits(wide, p, tokens)
        moved = _chunk_logits(wide, _with_indexer(p, 0, w_iq=noise), tokens)
        np.testing.assert_array_equal(base, moved)
        dense = jax.jit(lambda pp, t: ref.forward(
            dataclasses.replace(CFG, index_topk=64), pp, t))(p, jnp.asarray(tokens))
        assert np.abs(base - np.asarray(dense[-1])).max() <= TOL
    else:
        base = _chunk_logits(model, p, tokens)
        assert np.abs(base - ref_forward(p, tokens)[-1]).max() <= TOL
        # Layers 1-3 attend over layer 0's set: its indexer moves the result
        # (against the reference with the same weights, so through the
        # borrowed masks and not by chance) ...
        p0 = _with_indexer(p, 0, w_iq=noise)
        moved = _chunk_logits(model, p0, tokens)
        assert np.abs(moved - base).max() > 100 * TOL
        assert np.abs(moved - ref_forward(p0, tokens)[-1]).max() <= TOL
        # ... and a shared layer given indexer weights of its own reads none.
        own = {k: p["layers"][4][k] for k in ("w_iq", "w_ik", "w_iw", "ik_norm_w", "ik_norm_b")}
        np.testing.assert_array_equal(base, _chunk_logits(model, _with_indexer(p, 2, **own), tokens))


def test_expanded_equals_absorbed():
    """One query over the same selected rows: prefill's expanded form (K and
    V made per head) and decode's absorbed form (scores in the latent space)
    are the same numbers."""
    c = CFG
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    S, K = 40, 16
    rows = jax.random.normal(k[0], (S, c.latent_row))
    q_nope = jax.random.normal(k[1], (1, c.num_heads, c.qk_nope_head_dim))
    q_rope = jax.random.normal(k[2], (1, c.num_heads, c.qk_rope_head_dim))
    w_uk = jax.random.normal(k[3], (c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim)) / 4
    w_uv = jax.random.normal(k[4], (c.kv_lora_rank, c.num_heads, c.v_head_dim)) / 4
    sel = jnp.asarray(np.random.default_rng(0).permutation(S)[:K])
    allowed = jnp.zeros((1, S), bool).at[0, sel].set(True)
    exp = ls.attend_expanded_xla(q_nope, q_rope, rows, allowed, jnp.int32(S - 1), w_uk, w_uv, c,
                                 head_group=2, key_block=16)  # several groups and blocks
    ab = ls.attend_absorbed(q_nope, q_rope, rows[sel][None], jnp.ones((1, K), bool), w_uk, w_uv, c)
    np.testing.assert_allclose(np.asarray(exp), np.asarray(ab), atol=2e-5, rtol=1e-5)


def test_exact_selection_breaks_ties_like_top_k():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 2.0, 3.0, 0.5, 3.0, 9.0]])
    visible = jnp.asarray([[True] * 7 + [False]])
    mask, _ = ls.select_mask(scores, visible, 3)
    idx, real = ls.select_positions(scores, visible, 3)
    assert np.asarray(mask)[0].nonzero()[0].tolist() == [1, 2, 4] == sorted(np.asarray(idx)[0])
    assert np.asarray(real).all()
    few = jnp.asarray([[True, True] + [False] * 6])
    assert np.asarray(ls.select_mask(scores, few, 3)[0])[0].tolist() == [True, True] + [False] * 6
    assert np.asarray(ls.select_positions(scores, few, 3)[1]).sum() == 2


def test_tie_rows_counter_counts_the_rows_whose_ties_overflow(ctx, model):
    """An indexer whose head weights are zero scores every position 0.0, so
    each real prefill row that sees more than ``index_topk`` positions has
    more equal scores at its boundary than places: the selection walks the
    ties (the lowest positions win) on both selecting layers, and the rows
    leave the device with the chunk's other statistics."""
    p = model.params
    for layer in CFG.index_layers:
        p = _with_indexer(p, layer, w_iw=jnp.zeros_like(p["layers"][layer]["w_iw"]))
    eng = Engine(LatentSparseLLM(CFG, ctx, params=p), backend="dist", max_len=T_REF)
    telemetry.reset()
    served = _serve(eng, 32, n_requests=2)
    got = {e["labels"]["phase"]: e["value"]
           for e in telemetry.snapshot()["counters"]["tdt_dsa_select_tie_rows_total"]}
    prompts = [len(r.prompt) for r, _ in served]
    assert got == {"prefill": len(CFG.index_layers) * sum(n - CFG.index_topk for n in prompts)}
    # ... and the walk gave the lowest positions: what was selected is still
    # min(visible, index_topk) a row
    selected = sum(e["value"] for e in telemetry.snapshot()["counters"][
        "tdt_dsa_positions_selected_total"] if e["labels"]["phase"] == "prefill")
    assert selected == len(CFG.index_layers) * sum(
        np.minimum(np.arange(1, n + 1), CFG.index_topk).sum() for n in prompts)


def test_attend_tiles_counter_counts_the_tiles_of_the_mask(model, monkeypatch):
    """``tdt_dsa_attend_tiles_total``: a chunk of 16 rows at offset 32 over a
    buffer of 64, in tiles of 8 queries by 8 keys. ``under_diagonal`` is
    arithmetic: the key tiles that hold a position up to the chunk's last,
    times the query tiles, a layer. ``visited`` is what the masks hold: the
    tiles in which a row allows anything, which leaves out at least the
    tile above the first query tile's diagonal. ``unmasked`` is a layer
    with no indexer's: a selecting model reads 0."""
    from triton_dist_tpu.kernels import latent_flash

    monkeypatch.setattr(latent_flash, "QUERY_TILE", 8)
    monkeypatch.setattr(latent_flash, "KEY_TILE", 8)
    C, P, off, t = 16, 64, 32, 8
    masks = []
    inner = ls.attend_expanded
    monkeypatch.setattr(ls, "attend_expanded", lambda *a, **k: (
        masks.append((np.asarray(a[3]), np.asarray(k["table"]))), inner(*a, **k))[1])
    c = model.config
    bufs = [jnp.zeros((r.layers, 1, r.heads, P, r.width), jnp.dtype(c.dtype))
            for r in model.cache_rows()]
    tokens = jnp.asarray([np.arange(C) % c.vocab_size], jnp.int32)
    _, _, stats = model.prefill_chunk_shard(  # op by op: the masks are values
        model.params, tokens, bufs[0], bufs[1], jnp.int32(off), jnp.int32(C - 1), "dist_ar")
    visited, under, unmasked = (int(x) for x in stats["attend_tiles"])
    assert unmasked == 0
    assert under == c.num_layers * (C // t) * ((off + C) // t) == 5 * 2 * 6
    assert len(masks) == c.num_layers
    nonempty = [m.reshape(C // t, t, P // t, t).any(axis=(1, 3)) for m, _ in masks]
    assert all((n == table).all() for n, (_, table) in zip(nonempty, masks))
    assert visited == sum(int(n.sum()) for n in nonempty)
    assert 0 < visited <= under - c.num_layers  # rows 32..39 see no key of 40..47
    # the selection binds: 16 of the 33..48 positions a row sees
    assert all((m.sum(axis=1) == c.index_topk).all() for m, _ in masks)
    telemetry.reset()
    model.publish_step_stats(stats)
    got = {e["labels"]["kind"]: e["value"]
           for e in telemetry.snapshot()["counters"]["tdt_dsa_attend_tiles_total"]}
    assert got == {"visited": visited, "under_diagonal": under, "unmasked": 0}


def _expert_layer(model, layer=1, rows=40, seed=0):
    lp = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), model.params["layers"][layer])
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, CFG.hidden_size))
    return lp, x


def test_routing_by_score_plus_bias_with_gates_from_score(model):
    lp, x = _expert_layer(model)
    bias = lp["router_bias"].at[9].set(50.0)  # expert 9 is always chosen ...
    idx, g = ls.route_sigmoid(x, lp["router"], bias, 2, 2.5)
    s = np.asarray(jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=ref.HI)))
    idx, g = np.asarray(idx), np.asarray(g)
    assert (idx == 9).any(axis=1).all()
    chosen = np.take_along_axis(s, idx, axis=1)
    # ... and its gate is its score's share, the bias nowhere in it.
    np.testing.assert_allclose(g, 2.5 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    ridx, rg = ref.route(CFG, {**lp, "router_bias": bias}, x)
    np.testing.assert_array_equal(idx, np.asarray(ridx))
    np.testing.assert_allclose(g, np.asarray(rg), rtol=1e-5)


def test_shares_add_up_to_the_uncut_layer(model):
    """Every chip's share of the routed result, with the shared expert
    counted once, is what the uncut layer gives: 4 shares of 4 experts."""
    lp, x = _expert_layer(model, rows=70)
    full = dataclasses.replace(CFG, experts_held=(0, 16))
    for i, name in enumerate(("e_gate", "e_up", "e_down")):  # all 16 experts' weights
        shape = (16,) + lp[name].shape[1:]
        lp[name] = jax.random.normal(jax.random.PRNGKey(20 + i), shape) / np.sqrt(shape[1])
    shared = ref._ffn(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    uncut = jax.jit(lambda lp, x: ref.routed(full, lp, x))(lp, x) + shared
    idx, g = ls.route_sigmoid(x, lp["router"], lp["router_bias"], 2, 2.5)
    total = shared

    @jax.jit
    def both(held, first):  # the program's share and the reference's
        prog = ls.held_experts(x, idx, g, held["e_gate"], held["e_up"], held["e_down"],
                               first, tile=8)
        return prog, ref.routed(full, {**lp, **held}, x, held=(first, 4))

    for first in (0, 4, 8, 12):
        held = {k: lp[k][first:first + 4] for k in ("e_gate", "e_up", "e_down")}
        share, want = both(held, first)
        np.testing.assert_allclose(np.asarray(share), np.asarray(want), atol=2e-5)
        total = total + share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=5e-5)


def test_no_row_dropped_when_every_row_picks_one_expert(model):
    """300 rows that all choose experts 2 and 3 (more than a tile of one
    expert's rows, and no row of any other): every row is computed."""
    lp, x = _expert_layer(model, rows=300)
    bias = lp["router_bias"].at[jnp.asarray([2, 3])].set(50.0)
    idx, g = ls.route_sigmoid(x, lp["router"], bias, 2, 2.5)
    assert set(np.asarray(idx).ravel().tolist()) == {2, 3}
    got = jax.jit(lambda: ls.held_experts(
        x, idx, g, lp["e_gate"], lp["e_up"], lp["e_down"], 0, tile=64))()
    want = jax.jit(lambda: ref.routed(CFG, {**lp, "router_bias": bias}, x))()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    assert np.asarray(ls.expert_counts(idx, 16)).tolist()[2:4] == [300, 300]


def test_pools_follow_the_declared_rows(ctx, model):
    """``DenseLLM``'s pool is what it was (K and V pools of one shape, its
    bytes a block); the latent model's two pools differ by kind under one
    table, and the ledger's price of a block follows the declaration."""
    mc = PRESETS["test-dense"]
    dense = DenseLLM(mc, ctx, key=jax.random.PRNGKey(0))
    old = PagedKVCache.create(kv_rows(mc.num_layers, mc.num_kv_heads, mc.head_dim), 2,
                              block_size=8, num_blocks=9, max_len=64, dtype=jnp.float32)
    new = Engine(dense, backend="xla", max_len=64).alloc_paged(2, block_size=8, num_blocks=9)
    shape = (mc.num_layers, 9, mc.num_kv_heads, 8, mc.head_dim)
    assert old.k.shape == old.v.shape == new.k.shape == new.v.shape == shape
    assert old.bytes_per_block == new.bytes_per_block == 2 * int(np.prod(shape)) // 9 * 4
    assert new.kinds == ("k", "v")

    srv = InferenceServer(Engine(model, backend="dist", max_len=64), num_slots=2, chunk=4)
    c, bs = CFG, srv.block_size
    assert srv.cache.k.shape == (5, srv.num_blocks, 1, bs, c.latent_row)
    assert srv.cache.v.shape == (2, srv.num_blocks, 1, bs, c.index_head_dim)
    assert srv.cache.tables.shape == (2, 64 // bs)
    price = (5 * c.latent_row + 2 * c.index_head_dim) * bs * 4
    assert srv.cache.bytes_per_block == srv.kv_ledger.bytes_per_block == price
    assert srv.cache.bytes_per_block_by_kind == {
        "latent": 5 * c.latent_row * bs * 4, "index_key": 2 * c.index_head_dim * bs * 4}
    srv.shutdown(drain=False)


@pytest.mark.chaos
def test_probe_restores_a_model_that_has_the_paged_programs_only(model, monkeypatch):
    """Degraded by an abort in its second decode chunk, a server of this
    model comes back to its preferred backend: the half-open probe runs a
    chunked prefill and a paged step, which the model has, and no one-shot
    prefill, which it has not. No token is lost or doubled on the way, and
    the streams are those of the same requests served undisturbed."""
    monkeypatch.setenv("TDT_DEGRADE_PROBE_S", "0.01")
    telemetry.reset()
    resilience.reset_degradation()
    eng = Engine(model, backend="dist", max_len=64)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, size=20).tolist() for _ in range(2)]

    def serve(schedule):
        srv = InferenceServer(eng, num_slots=2, chunk=2)
        streams = {}
        reqs = [srv.submit(p, 7, on_token=lambda r, t, i: streams.setdefault(
            r.req_id, []).append(t)) for p in prompts]
        with resilience.chaos_schedule(schedule):
            srv.run()
            for _ in range(2000):  # backoffs of 10 ms, doubling while probes fail
                if eng.backend == "dist":
                    break
                if not srv.step():
                    time.sleep(0.005)
        srv.shutdown(drain=False)
        assert all(r.done and streams[r.req_id] == list(r.tokens) for r in reqs)
        return [list(r.tokens) for r in reqs]

    try:
        got = serve("abort@decode:1,heal")
        assert eng.backend == "dist", telemetry.events("serving_probe_failed")[-1:]
        assert not resilience.any_degraded()
        assert telemetry.counter_value("tdt_serving_recoveries_total", from_backend="dist") == 1.0
        assert telemetry.counter_value("tdt_serving_restores_total", to_backend="dist") == 1.0
        assert not telemetry.events("serving_probe_failed")
        assert got == serve("heal") and all(len(t) == 7 for t in got)
    finally:
        resilience.reset_degradation()

