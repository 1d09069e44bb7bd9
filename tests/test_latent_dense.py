"""Latent attention over the whole cache, with no indexer (``index_kinds``
``"none"``): ``LatentSparseLLM`` against the benchmark's plain reference
(``benchmark/reference/axk1.py``) on seeded weights at a toy size, the two
dense kernels (``latent_flash_prefill``, ``latent_flash_decode``) interpreted
against the XLA forms they replace at heads of 128 + 64 and values of 128,
YaRN's table against the published numbers, the router without a bias, and
the sixteen shares of an expert layer against the uncut layer.

Tolerance of the logits comparisons: program and reference both compute in
float32 here and differ in the order of their sums (online softmax over key
blocks, the absorbed form at decode, sorted tiles of expert rows), which
moves a logit of size ~1 by some 1e-6; ``TOL`` leaves two orders of room.
The kernels' comparisons are in bfloat16 at ``KTOL``: both sides round K, V
and ``p`` to bfloat16 at the same places, and differ in the order of the
float32 sums and in where the scale is applied.
"""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark.build import axk1 as build  # noqa: E402
from benchmark.reference import axk1 as ref  # noqa: E402
from triton_dist_tpu.kernels import latent_flash  # noqa: E402
from triton_dist_tpu.layers import latent_sparse as ls  # noqa: E402
from triton_dist_tpu.models import LatentSparseConfig  # noqa: E402
from triton_dist_tpu.runtime import telemetry  # noqa: E402

TOL = 2e-4
KTOL = 2e-2
TOY = json.loads((REPO / "tests/benchmark/toy/configs/toy-axk1.json").read_text())
REAL = json.loads((REPO / "benchmark/configs/a.x-k1-ep16-d5.json").read_text())
SEED = 2**31 + 4040
T_REF = 128
TILES = (8, 16)  # query rows, keys: a prefill chunk of 32 holds unmasked tiles


@pytest.fixture(scope="module")
def served():
    """The toy served through ``InferenceServer`` -> ``Engine`` ->
    ``LatentSparseLLM``: prompts of 70 and 96 in chunks of 32 (a padded last
    chunk, and none), a third request that joins when the first leaves; after
    every loop iteration the next-token logits of each decoding slot from the
    engine's own paged step over the server's pool. The prefill's tiles are
    ``TILES`` (the toy's heads do not tile, so they size the counters alone)."""
    telemetry.reset()
    key = harness.seed_key(SEED)
    model, eng, srv = build.build(TOY, key, jax.devices()[:1])
    rng = np.random.default_rng(3)
    sizes = [(70, 6), (96, 11), (70, 9)]
    reqs = [srv.submit(rng.integers(0, 256, size=n).tolist(), new) for n, new in sizes]
    seen = {id(r): {} for r in reqs}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(latent_flash, "QUERY_TILE", TILES[0])
        mp.setattr(latent_flash, "KEY_TILE", TILES[1])
        for _ in range(40):
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
    weights = ref.make_weights(TOY, key, jax.devices()[:1])
    return model, srv, weights, [(r, seen[id(r)]) for r in reqs]


def test_program_draws_the_references_weights(served):
    model, _, weights, _ = served
    for mine, theirs in zip(weights["layers"], model.params["layers"]):
        assert set(mine) == {k for k in theirs if not k.startswith(("ln", "q_norm", "kv_norm"))}
        for name, w in mine.items():
            np.testing.assert_array_equal(np.asarray(w), np.asarray(theirs[name]))
    np.testing.assert_array_equal(np.asarray(weights["head"]), np.asarray(model.params["lm_head"]))
    assert "router_bias" not in model.params["layers"][1]
    # no indexer anywhere: the pair's second kind has no layers and costs nothing
    latent, index = model.cache_rows()
    assert (latent.layers, latent.width, index.layers) == (5, 24, 0)


def test_served_matches_reference(served):
    """Chunked prefill of two lengths, then paged decode through a join and a
    leave, against the reference's full forward pass of each finished
    sequence: every decode step's logits, and every served token the
    reference's first choice."""
    _, srv, weights, reqs = served
    worst, compared = 0.0, 0
    for r, seen in reqs:
        seq = np.zeros((1, T_REF), np.int32)
        seq[0, : len(r.prompt) + len(r.tokens)] = r.prompt + r.tokens
        rows = np.arange(len(r.prompt) - 1, len(r.prompt) + len(r.tokens) - 1)[None]
        want = np.asarray(ref.logits_at(TOY, weights, seq, rows))[0]
        for i, tok in enumerate(r.tokens):  # token 0 is the prefill's
            assert want[i].max() - want[i][tok] <= TOL
        for position, got in seen.items():
            i = position - (len(r.prompt) - 1)
            if 0 <= i < len(want):
                worst = max(worst, float(np.abs(got - want[i]).max()))
                compared += 1
    assert compared >= 4 and worst <= TOL, (compared, worst)  # a chunk of 4 steps a look
    assert telemetry.counter_total("tdt_engine_fallbacks_total") == 0
    # the device's counts are the lengths' arithmetic: every earlier position
    # on each of the 5 layers (the padded chunk's rows and the idle slot's in
    # neither); what was read is whole tiles, so no less
    sizes = [(len(r.prompt), len(r.tokens)) for r, _ in reqs]
    want = {"prefill": 5 * sum(p * (p + 1) // 2 for p, _ in sizes),
            "decode": 5 * sum(sum(range(p + 1, p + new)) for p, new in sizes)}
    snap = telemetry.snapshot()["counters"]
    for phase, n in want.items():
        got = {what: sum(e["value"] for e in snap[f"tdt_latent_rows_{what}_total"]
                         if e["labels"]["phase"] == phase) for what in ("visible", "read")}
        assert got["visible"] == n and got["read"] >= n, (phase, got, n)
    # the toy's row is not whole lanes: the gather path reads the table's extent
    assert srv.cache.k.shape[-1] == 24


def test_unmasked_tiles_counter_is_the_arithmetic(served):
    """``tdt_dsa_attend_tiles_total{kind="unmasked"}``: every chunk of 32 (a
    prompt's buffer is its length, the last chunk padded) on each of the 5
    layers counts the (query tile, key tile) pairs whose last key is at or
    before the query tile's first position; never more than ``visited``."""
    _, _, _, reqs = served
    tq, tk = TILES
    want = 0
    for r, _ in reqs:
        P = len(r.prompt)
        last_key = (np.arange(-(-P // tk)) + 1) * tk - 1
        for off in range(0, P, 32):
            first = off + np.arange(32 // tq) * tq
            want += 5 * int((last_key[None, :] <= first[:, None]).sum())
    got = {e["labels"]["kind"]: e["value"]
           for e in telemetry.snapshot()["counters"]["tdt_dsa_attend_tiles_total"]}
    assert got["unmasked"] == want > 0
    assert got["unmasked"] <= got["visited"] <= got["under_diagonal"]


def test_a_lower_precision_fails_the_tolerance(served):
    """The reference's own bfloat16 rounding of every linear layer moves the
    logits far outside ``TOL``: the comparison would catch a lower precision."""
    _, _, weights, reqs = served
    r, _ = reqs[0]
    seq = np.zeros((1, T_REF), np.int32)
    seq[0, : len(r.prompt)] = r.prompt
    rows = np.asarray([[len(r.prompt) - 1]])
    hi = np.asarray(ref.logits_at(TOY, weights, seq, rows))
    lo = np.asarray(ref.logits_at(TOY, weights, seq, rows, precision="bfloat16"))
    assert np.abs(hi - lo).max() > 10 * TOL


# ------------------------------------------------------------------ kernels

#: Heads of 128 + 64, values of 128, a latent rank in whole lanes: the
#: published head shapes at a rank and a count the interpreter can afford.
KCFG = LatentSparseConfig(
    num_heads=8, kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    index_kinds=("none",) * 5, dtype="bfloat16",
    rope_scaling=ls.Yarn(factor=32.0, original_max=4096, mscale_all_dim=1.0))


def _qkv(key, C, P, c):
    k = jax.random.split(key, 5)
    bf = lambda kk, shape, s=1.0: (jax.random.normal(kk, shape) * s).astype(jnp.bfloat16)
    rank = c.kv_lora_rank
    rows = jnp.pad(bf(k[2], (P, c.latent_row)), ((0, 0), (0, c.cache_row - c.latent_row)))
    return (bf(k[0], (C, c.num_heads, c.qk_nope_head_dim)),
            bf(k[1], (C, c.num_heads, c.qk_rope_head_dim)), rows,
            bf(k[3], (rank, c.num_heads, c.qk_nope_head_dim), rank ** -0.5),
            bf(k[4], (rank, c.num_heads, c.v_head_dim), rank ** -0.5))


@pytest.mark.parametrize("C, P, off", [
    (64, 200, 0),     # the diagonal tiles alone
    (64, 200, 96),    # one unmasked tile, then the diagonal
    (64, 600, 448),   # three unmasked tiles a query tile
    (64, 330, 256),   # two unmasked, then a diagonal tile that straddles P's end
    (128, 200, 160),  # a padded final chunk: its last query tile, all rows past P,
                      # takes the tile that straddles P's end unmasked
], ids=["first_chunk", "deep_chunk", "several_unmasked", "straddles_p", "padded_final"])
def test_causal_prefill_kernel_matches_the_xla_body(monkeypatch, C, P, off):
    """``latent_flash_prefill`` (no mask handed over: the causal table and
    the positions) against ``attend_expanded_xla`` under the causal mask, at
    a head of 192 padded to 256 inside, key tiles wholly under a query tile
    (no mask) and across its diagonal (masked), and a prompt that is not
    whole key tiles. Rows past P are nobody's (a chunk drops them), and are
    not compared."""
    monkeypatch.setattr(latent_flash, "QUERY_TILE", 32)
    monkeypatch.setattr(latent_flash, "KEY_TILE", 128)
    c = KCFG
    assert c.cache_row == 256 and c.latent_row == 192
    q_nope, q_rope, rows, w_uk, w_uv = _qkv(jax.random.PRNGKey(off + 1), C, P, c)
    assert latent_flash.takes(C, c.num_heads, 128, 192, 128, 2)
    table, counts = ls.causal_tiles(C, P, jnp.int32(off))
    got = jax.jit(lambda *a: ls.attend_expanded(*a[:3], None, a[3], *a[4:], c, table=table))(
        q_nope, q_rope, rows, jnp.int32(off), w_uk, w_uv)
    pos = off + jnp.arange(C)
    allowed = jnp.arange(P)[None, :] <= pos[:, None]
    want = ls.attend_expanded_xla(q_nope, q_rope, rows, allowed, jnp.int32(off), w_uk, w_uv, c,
                                  head_group=4, key_block=64)
    assert got.shape == (C, c.num_heads * 128) and got.dtype == jnp.bfloat16
    sent = pos < P
    np.testing.assert_allclose(np.asarray(got, np.float32)[np.asarray(sent)],
                               np.asarray(want, np.float32)[np.asarray(sent)],
                               atol=KTOL, rtol=KTOL)
    # the table is the mask's: the tiles that allow anything, no others
    np.testing.assert_array_equal(np.asarray(table),
                                  np.asarray(latent_flash.tile_table(allowed, 32, 128)))
    # unmasked: a key tile's last key at or before the query tile's first row
    last_key = (np.arange(-(-P // 128)) + 1) * 128 - 1
    unmasked = int((last_key[None, :] <= off + np.arange(C // 32)[:, None] * 32).sum())
    assert int(counts[2]) == unmasked <= int(counts[0]) == int(table.sum()) <= int(counts[1])
    per_tile = np.asarray(sent).reshape(C // 32, 32).sum(axis=1)
    assert int(ls.tile_rows_read(table, sent, P)) == int(
        (per_tile * np.asarray(table).sum(axis=1)).sum()) * 128


def test_paged_decode_kernel_matches_the_gathered_form(monkeypatch):
    """``latent_flash_decode`` through ``attend_absorbed_paged`` against
    ``attend_absorbed`` over the gathered extent: 4 slots on a shuffled
    table, one that sees nothing, one whose length straddles a page and a
    tile, one of whole tiles, one at the table's end; tiles of 2 pages."""
    monkeypatch.setattr(latent_flash, "DECODE_TILE_BYTES", 2 * 16 * 256 * 2)
    c, B, bs, mb, L = KCFG, 4, 16, 6, 2
    k = jax.random.split(jax.random.PRNGKey(9), 6)
    bf = lambda kk, shape, s=1.0: (jax.random.normal(kk, shape) * s).astype(jnp.bfloat16)
    pool = bf(k[0], (L, B * mb + 1, 1, bs, c.cache_row))
    pool = pool.at[..., c.latent_row:].set(0)  # the pad a model writes
    tables = jnp.asarray(np.random.default_rng(2).permutation(B * mb).reshape(B, mb) + 1,
                         jnp.int32)
    lengths = jnp.asarray([0, 37, 64, 96], jnp.int32)
    q_nope, q_rope = bf(k[1], (B, 8, 128)), bf(k[2], (B, 8, 64))
    w_uk, w_uv = bf(k[3], (128, 8, 128), 128 ** -0.5), bf(k[4], (128, 8, 128), 128 ** -0.5)
    assert latent_flash.decode_takes(8, 128, pool.shape, mb, 2)
    assert latent_flash.decode_tile_pages(pool.shape, mb, 2) == 2
    got = jax.jit(lambda *a: ls.attend_absorbed_paged(*a[:3], 1, *a[3:], c))(
        q_nope, q_rope, pool, tables, lengths, w_uk, w_uv)
    rows = jnp.take(pool[1, :, 0], tables, axis=0).reshape(B, mb * bs, -1)
    real = jnp.arange(mb * bs)[None, :] < lengths[:, None]
    want = ls.attend_absorbed(q_nope, q_rope, rows, real.at[0, 0].set(True), w_uk, w_uv, c)
    assert got.shape == (B, 8 * 128) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got[0], np.float32), 0.0)  # nothing to see
    np.testing.assert_allclose(np.asarray(got[1:], np.float32), np.asarray(want[1:], np.float32),
                               atol=KTOL, rtol=KTOL)
    # a row of 576 in a pool of 576 is not the kernel's: pages are copied whole
    assert not latent_flash.decode_takes(64, 512, (5, 9, 1, 16, 576), 2064, 2)
    assert latent_flash.decode_takes(64, 512, (5, 9, 1, 16, 640), 2064, 2)


def test_model_takes_the_kernels_by_shape(monkeypatch):
    """A model whose head dims and latent rank tile: its row lies in whole
    lanes (192 in 256), its prefill chunk and its decode step run the two
    kernels (interpreted here), and both give what the XLA forms give from
    the same weights, buffers and pool."""
    from triton_dist_tpu.models import LatentSparseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    monkeypatch.setattr(latent_flash, "QUERY_TILE", 32)
    monkeypatch.setattr(latent_flash, "KEY_TILE", 128)
    c = dataclasses.replace(KCFG, dtype="float32", vocab_size=64, q_lora_rank=32,
                            mlp_kinds=("dense", "experts"), index_kinds=("none", "none"),
                            router_bias=False)
    ctx = initialize_distributed(devices=jax.devices()[:1], axis_names=("tp",), set_default=False)
    model = LatentSparseLLM(c, ctx, key=jax.random.PRNGKey(4))
    latent, index = model.cache_rows()
    assert (latent.width, index.layers) == (256, 0)
    C, P, B, bs, mb = 64, 96, 3, 16, 8
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 64, size=(1, C)), jnp.int32)
    kb = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (2, 1, 1, P, 256)).at[..., 192:].set(0)
    vb = jnp.zeros((0, 1, 1, P, c.index_head_dim))
    pk = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (2, B * mb + 1, 1, bs, 256))
    pk = pk.at[..., 192:].set(0)
    pv = jnp.zeros((0, B * mb + 1, 1, bs, c.index_head_dim))
    tables = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    lengths, active = jnp.asarray([37, 100, 5], jnp.int32), jnp.asarray([True, True, False])
    last = jnp.asarray([3, 9, 1], jnp.int32)

    def both():
        chunk = jax.jit(model.prefill_chunk_shard, static_argnums=(6,))(
            model.params, tokens, kb, vb, jnp.int32(32), jnp.int32(C - 1), "dist_ar")
        step = jax.jit(model.decode_shard_paged, static_argnums=(7,))(
            model.params, last, pk, pv, tables, lengths, active, "dist_ar")
        return chunk, step

    (lg_k, (kb_k, _), st_k), (dl_k, pk_k, _, ds_k) = both()
    monkeypatch.setattr(latent_flash, "takes", lambda *a: False)
    monkeypatch.setattr(latent_flash, "decode_takes", lambda *a: False)
    (lg_x, (kb_x, _), st_x), (dl_x, pk_x, _, ds_x) = both()
    np.testing.assert_allclose(np.asarray(lg_k), np.asarray(lg_x), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(kb_k), np.asarray(kb_x), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(dl_k[:2]), np.asarray(dl_x[:2]), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(pk_k[:, 1:]), np.asarray(pk_x[:, 1:]), atol=TOL, rtol=TOL)
    assert np.asarray(kb_k[..., 192:] == 0).all() and np.asarray(pk_k[:, 1:, ..., 192:] == 0).all()
    # in place the step reads whole tiles up to each live length; gathered, the extent
    tile = latent_flash.decode_tile_pages(pk.shape, mb, 4) * bs
    assert [int(x) for x in ds_k["rows_visible"]] == [0, 2 * (38 + 101)]
    assert int(ds_k["rows_read"][1]) == 2 * sum(-(-n // tile) * tile for n in (38, 101))
    assert int(ds_x["rows_read"][1]) == 2 * 2 * mb * bs
    assert [int(x) for x in st_k["rows_visible"]] == [int(x) for x in st_x["rows_visible"]] == [
        2 * sum(range(33, 97)), 0]


# ------------------------------------------------------ the rotary's table


def test_yarn_table_is_the_published_one():
    """At the published keys: the ramp between pairs 10 and 23, the fast
    pairs left alone, the slow ones stretched 32 times, cos and sin unscaled,
    the softmax scale 192^-0.5 x 1.81326 = 0.13086; the program's table is
    the reference's."""
    c = build.model_config(REAL)
    y = c.rope_scaling
    assert y.ramp_ends(64, 10000.0) == (10, 23)
    assert c.softmax_scale == pytest.approx(0.13086, abs=1e-5)
    assert c.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    assert y.rope_mscale == 1.0
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = y.inv_freq(64, 10000.0)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-12)
    mid = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(got[11:23], plain[11:23] / 32 * mid + plain[11:23] * (1 - mid),
                               rtol=1e-12)
    s = ref.sizes(REAL)
    np.testing.assert_array_equal(got, ref.yarn_inv_freq(64, s["theta"], s["yarn"]))
    assert ref.softmax_scale(s) == pytest.approx(c.softmax_scale, rel=1e-12)
    freqs, mscale = ls.rope_freqs(64, c)
    np.testing.assert_array_equal(np.asarray(freqs), got.astype(np.float32))
    assert mscale == 1.0
    # a plain table where the configuration scales nothing: GLM's, unchanged
    glm = LatentSparseConfig()
    assert glm.rope_scaling is None and glm.softmax_scale == 20 ** -0.5
    np.testing.assert_array_equal(
        np.asarray(ls.rope_freqs(8, glm)[0]),
        np.asarray(8e6 ** (-jnp.arange(4, dtype=jnp.float32) / 4)))


def test_a_shared_layer_needs_a_full_one_below_it():
    LatentSparseConfig(index_kinds=("none", "full", "shared", "none", "shared"))
    with pytest.raises(AssertionError, match="borrows"):
        LatentSparseConfig(index_kinds=("none", "shared", "full", "shared", "shared"))
    with pytest.raises(ValueError, match="topk_method"):
        build.model_config(dict(REAL, topk_method="noaux_tc"))
    with pytest.raises(ValueError, match="topk_method"):
        ref.sizes(dict(REAL, topk_method="group_limited_greedy"))


# --------------------------------------------------------------- the experts


def test_router_without_a_bias_ranks_the_scores():
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8))
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
    w = w.at[:, 5].set(w[:, 3])  # experts 3 and 5 tie on every row
    idx, g = ls.route_sigmoid(x, w, None, 4, 2.5)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)))
    for t in range(6):
        order = sorted(range(16), key=lambda e: (-s[t, e], e))[:4]  # ties to the lower index
        assert list(np.asarray(idx[t])) == order
        np.testing.assert_allclose(np.asarray(g[t]), 2.5 * s[t, order] / s[t, order].sum(),
                                   rtol=1e-5)
    biased, _ = ls.route_sigmoid(x, w, jnp.zeros((16,)).at[15].set(10.0), 4, 2.5)
    assert (np.asarray(biased)[:, 0] == 15).all() and (np.asarray(idx) != 15).any()


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 16 shares of a 32-expert layer give (each
    the program's ``held_experts`` over its own 2 experts, routed over all
    32), with the shared expert counted once, are the uncut reference's
    layer: nothing stands in for the absent chips, and nothing is lost."""
    E, shares, d, f, T = 32, 16, 64, 32, 40
    cfg = dict(TOY, n_routed_experts=E, experts_held=[0, E], published={"n_routed_experts": E})
    s = ref.sizes(cfg)
    k = jax.random.split(jax.random.PRNGKey(12), 8)
    n = lambda kk, shape, fan: jax.random.normal(kk, shape) / np.sqrt(fan)
    lp = {"router": n(k[0], (d, E), d), "e_gate": n(k[1], (E, d, f), d),
          "e_up": n(k[2], (E, d, f), d), "e_down": n(k[3], (E, f, d), f),
          "s_gate": n(k[4], (d, f), d), "s_up": n(k[5], (d, f), d), "s_down": n(k[6], (f, d), f)}
    h = jax.random.normal(k[7], (T, d))
    whole = ref._routed(s, "stated", lp, h) + ref._ffn(
        "stated", h, lp["s_gate"], lp["s_up"], lp["s_down"])
    idx, gates = ls.route_sigmoid(h, lp["router"], None, s["k"], s["scaling"], s["norm_topk"])
    per = E // shares
    share = jax.jit(lambda wg, wu, wd, first: ls.held_experts(h, idx, gates, wg, wu, wd, first))
    parts = [share(*(lp[w][i * per:(i + 1) * per] for w in ("e_gate", "e_up", "e_down")), i * per)
             for i in range(shares)]
    assert sum(bool(np.abs(np.asarray(p)).max() > 0) for p in parts) > shares // 2
    total = sum(parts) + ls.swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=TOL, rtol=TOL)
    # one share alone is what the cut reference gives for it
    cut = ref._routed(ref.sizes(dict(cfg, n_routed_experts=per, experts_held=[6, per])),
                      "stated", {**lp, **{w: lp[w][6:6 + per]
                                          for w in ("e_gate", "e_up", "e_down")}}, h)
    np.testing.assert_allclose(np.asarray(parts[3]), np.asarray(cut), atol=TOL, rtol=TOL)
