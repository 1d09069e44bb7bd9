"""The block-sparse / lightning decoder (``SparseLinearLLM``) against the
benchmark's plain reference (``benchmark/reference/minicpm_sala.py``: the
full forward pass in float32, no kernels, cache or batching), on the CPU at
the toy twin's configuration (``tests/benchmark/toy/configs/toy-sala.json``):
4 layers (sparse, lightning, lightning, sparse), blocks of 8 of which a
query takes 4 (the first, a window of 2, one learned), pooled keys of 4
every 2, so a context past 32 tokens selects.

Tolerance: program and reference both compute in float32 here and differ in
the order of their sums (a chunk's decayed products from a carried state,
an online order of the softmax, pooled keys summed a stride at a time),
which moves a logit of size ~0.1 (muP divides by 16) by some 1e-7; ``TOL``
leaves two orders of room. The planted faults show what it holds: the
selection replaced by the forced blocks alone and a slot's linear state
rounded to bfloat16 each miss it by over ten times.
"""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import paged_drive  # noqa: E402
import test_hybrid_ssm as hybrid_drive  # noqa: E402  (its join and its decode step, by hand)
from benchmark import harness  # noqa: E402
from triton_dist_tpu.kernels import lightning_attn as la  # noqa: E402
from triton_dist_tpu.layers import sparse_linear as sl  # noqa: E402
from triton_dist_tpu.models import Engine, SparseLinearLLM  # noqa: E402
from triton_dist_tpu.runtime import telemetry  # noqa: E402
from triton_dist_tpu.runtime.mesh import initialize_distributed  # noqa: E402
from triton_dist_tpu.serving import InferenceServer  # noqa: E402

TOL = 2e-5
CFG = json.loads((REPO / "tests/benchmark/toy/configs/toy-sala.json").read_text())
REF = harness._module(REPO / "benchmark/reference/minicpm_sala.py")
BUILD = harness._module(REPO / "benchmark/build/minicpm_sala.py")
KEY = harness.seed_key(2**31 + 35)
BLOCK, CHUNK, MAX_LEN = 8, 32, 256


@pytest.fixture(scope="module")
def ctx():
    return initialize_distributed(
        devices=jax.devices()[:1], axis_names=("tp",), set_default=False)


def _model(ctx, cfg=CFG):
    return SparseLinearLLM(BUILD.model_config(cfg), ctx, key=jnp.asarray(KEY))


@pytest.fixture(scope="module")
def engine(ctx):
    return Engine(_model(ctx), backend="dist", max_len=MAX_LEN)


@pytest.fixture(scope="module")
def weights():
    return REF.make_weights(CFG, KEY, jax.devices()[:1])


@pytest.fixture(scope="module")
def ref_logits(weights):
    def run(tokens, cfg=CFG, w=weights):
        seq = np.zeros((1, 160), np.int32)
        seq[0, : len(tokens)] = tokens  # padding sits in every row's future
        rows = np.arange(len(tokens))[None]
        return np.asarray(REF.logits_at(cfg, w, seq, rows, block=32))[0]

    return run


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], size=n).tolist()


def _join(eng, paged, slot, ids):
    """(every chunk's logits, paged'): the prompt in chunks of ``CHUNK``."""
    _, every, paged = hybrid_drive._join(eng, paged, slot, ids, CHUNK)
    return every, paged


_decode = hybrid_drive._decode


def test_weights_are_the_references(engine, weights):
    for mine, theirs in zip(weights["layers"], engine.model.params["layers"]):
        for name, w in mine.items():
            np.testing.assert_array_equal(np.asarray(w), np.asarray(theirs[name]))
    for name in ("embed", "head"):
        np.testing.assert_array_equal(np.asarray(weights[name]),
                                      np.asarray(engine.model.params[name]))


def test_chunked_prefill_then_decode_matches_the_forward_pass(engine, ref_logits):
    """A prompt of 120 (three chunks of 32 and one of 24 padded to 32) with
    the linear state and the pooled keys carried from chunk to chunk: every
    chunk's last row is the full forward's (a chunk past the first selects:
    5-15 visible blocks, 4 taken). Then 30 decode steps through the pool,
    the slot's pooled keys (15 completed on the way, across four pages) and
    its linear state, beside an idle slot."""
    ids = _ids(150, seed=1)
    want = ref_logits(ids)
    every, paged = _join(engine, paged_drive.alloc_chains(engine, 2, BLOCK), 1, ids[:120])
    for i, got in enumerate(every):
        assert np.abs(got - want[min(CHUNK * (i + 1), 120) - 1]).max() <= TOL, i
    for t in range(120, 150):
        logits, paged = _decode(engine, paged, [0, ids[t]], [0, 1])
        assert np.abs(logits[1] - want[t]).max() <= TOL, t
    assert np.asarray(paged.lengths).tolist() == [0, 150]


def test_two_lengths_an_idle_slot_and_a_slot_reused_after_a_longer_tenant(engine, ref_logits):
    """Slots 0 and 2 decode at lengths 40 and 120 in one chunk of 4 steps in
    which slot 2 runs out after 2, while slot 1 (a finished tenant) sits
    idle: its state and length do not move, the others' tokens are the
    reference's first choice and their next logits the reference's. Then
    slot 2 is given a shorter prompt: nothing of its 122-token tenant (linear
    state, pooled keys, pages) is left in the second's logits."""
    a, b, idle = _ids(50, seed=5), _ids(130, seed=6), _ids(40, seed=7)
    paged = paged_drive.alloc_chains(engine, 3, BLOCK)
    _, paged = _join(engine, paged, 0, a[:40])
    _, paged = _join(engine, paged, 1, idle)
    _, paged = _join(engine, paged, 2, b[:120])
    before = jax.tree.map(lambda x: np.asarray(x[1]), paged.state)
    out, tok, paged, _ = engine.decode_steps_paged(
        paged, jnp.asarray([a[40], 3, b[120]], jnp.int32), jnp.asarray([4, 0, 2], jnp.int32), 4)
    out = np.asarray(out)
    assert out[1].tolist() == [-1] * 4 and out[2, 2:].tolist() == [-1, -1]
    assert np.asarray(paged.lengths).tolist() == [44, 40, 122]
    for x, y in zip(jax.tree.leaves(before),
                    jax.tree.leaves(jax.tree.map(lambda x: np.asarray(x[1]), paged.state))):
        np.testing.assert_array_equal(x, y)
    logits = np.asarray(engine.decode_logits_paged(paged, tok))
    for slot, seq, n in ((0, a[:41], 4), (2, b[:121], 2)):
        seq = seq + out[slot, :n].tolist()
        want = ref_logits(seq)
        for i in range(n):  # each emitted token is the reference's first choice
            row = want[len(seq) - n - 1 + i]
            assert row.max() - row[seq[len(seq) - n + i]] <= TOL
        assert np.abs(logits[slot] - want[-1]).max() <= TOL
    second = _ids(50, seed=8)
    want = ref_logits(second)
    every, paged = _join(engine, paged, 2, second[:40])
    assert np.abs(every[-1] - want[39]).max() <= TOL
    for t in range(40, 50):
        logits, paged = _decode(engine, paged, [0, 0, second[t]], [0, 0, 1])
        assert np.abs(logits[2] - want[t]).max() <= TOL, t


def _forced_alone(score, own, topk, init_blocks, window_blocks):
    _, forced = SELECT(score, own, topk, init_blocks, window_blocks)
    return jnp.broadcast_to(forced, score.shape), forced


def _bf16_state(q, k, v, S, slope, active):
    o, S1 = STEP(q, k, v, S.astype(jnp.bfloat16).astype(jnp.float32), slope, active)
    return o, S1.astype(jnp.bfloat16).astype(jnp.float32)


SELECT, STEP = sl.select_blocks, la.lightning_step


@pytest.mark.parametrize("fault", ["forced_blocks_alone", "bfloat16_state"])
def test_the_tolerance_holds_the_selection_and_the_states_precision(
        ctx, ref_logits, monkeypatch, fault):
    """What ``TOL`` is for: with the learned block dropped, or a slot's
    linear state kept in bfloat16 through the decode steps, the same drive
    misses the reference by over ten times the tolerance."""
    if fault == "forced_blocks_alone":
        monkeypatch.setattr(sl, "select_blocks", _forced_alone)
    else:
        monkeypatch.setattr(la, "lightning_step", _bf16_state)
    eng = Engine(_model(ctx), backend="dist", max_len=MAX_LEN)
    ids = _ids(128, seed=1)
    want = ref_logits(ids)
    every, paged = _join(eng, paged_drive.alloc_chains(eng, 2, BLOCK), 1, ids[:120])
    worst = np.abs(every[-1] - want[119]).max()
    if fault == "bfloat16_state":  # the decode steps' fault; the other shows in the prefill
        for t in range(120, 128):
            logits, paged = _decode(eng, paged, [0, ids[t]], [0, 1])
            worst = max(worst, np.abs(logits[1] - want[t]).max())
    assert worst > 10 * TOL, worst


def test_served_counters_are_the_selections_own_and_the_server_refuses_what_rests_on_the_pool(
        engine):
    """Through ``InferenceServer`` (pages of the selection's block): blocks
    selected, visible and learned equal the host's arithmetic for the
    positions served, the lightning layers' rows are counted once, nothing
    falls back; a prefix lookup is skipped and counted, speculation is a
    constructor error and a KV handoff raises, each naming the cause."""
    c = engine.model.config
    names = ("tdt_bsa_blocks_selected_total", "tdt_bsa_blocks_visible_total",
             "tdt_bsa_blocks_learned_total", "tdt_bsa_pages_read_total",
             "tdt_linear_attn_rows_total")
    read = lambda: {(n, ph): telemetry.counter_value(n, phase=ph)
                    for n in names for ph in ("prefill", "decode")}
    skipped0 = telemetry.counter_total("tdt_serving_prefix_lookups_skipped_total")
    srv = InferenceServer(engine, num_slots=2, chunk=4, prefill_chunk=CHUNK, block_size=BLOCK)
    assert srv.stateful and srv.block_size == c.block_size
    before = read()
    p_len, new = 96, 6
    req = srv.submit(_ids(p_len, seed=9), new)
    while not req.done:
        srv.step()
    assert req.finish_reason == "ok" and len(req.tokens) == new
    got = {k: v - before[k] for k, v in read().items()}
    layers, hkv = len(c.layers_of("sparse")), c.num_kv_heads
    window = c.window_size // c.block_size

    def expected(positions):
        own = np.asarray(positions) // c.block_size
        visible = own + 1
        forced = np.minimum(visible, window) + (own >= window) * c.init_blocks
        selected = np.minimum(visible, c.topk)
        return [layers * hkv * int(x.sum()) for x in (selected, visible, selected - forced)]

    # the decode steps served: positions p_len ... p_len + new - 2 (the first token is prefill's)
    for phase, positions in (("prefill", range(p_len)), ("decode", range(p_len, p_len + new - 1))):
        want = expected(list(positions))
        assert [got[(n, phase)] for n in names[:3]] == want, phase
        assert got[("tdt_linear_attn_rows_total", phase)] == len(list(positions))
    # the toy's heads are not whole lanes: the plain form reads the whole extent
    blocks, chunks = -(-p_len // c.block_size), -(-p_len // CHUNK)
    assert got[("tdt_bsa_pages_read_total", "prefill")] == layers * hkv * blocks * chunks
    assert got[("tdt_bsa_pages_read_total", "decode")] == (
        layers * hkv * -(-MAX_LEN // c.block_size) * (new - 1))
    assert telemetry.counter_total("tdt_engine_fallbacks_total") == 0
    assert telemetry.counter_total("tdt_serving_prefix_lookups_skipped_total") == skipped0 + 1
    snap = telemetry.snapshot()["counters"]
    assert all(n in snap for n in names)
    with pytest.raises(ValueError, match="per-slot state cannot be rewound"):
        InferenceServer(engine, num_slots=2, spec_k=2, block_size=BLOCK)
    with pytest.raises(ValueError, match="model with per-slot state"):
        srv.export_kv(0)


def test_the_references_lightning_mixer_is_the_step_by_step_recurrence(weights):
    """The reference sums the recurrence a block of positions at a time; a
    position at a time, in numpy, it is the same to float32's rounding:
    over 150 positions (a block and a ragged second one)."""
    s = REF.sizes(CFG)
    lp = weights["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(2), (150, s["d"]), jnp.float32)
    got = np.asarray(REF._lightning(s, "stated", lp, u))
    H, D = s["lh"], s["ld"]
    z = np.asarray(REF._linear(u, lp["w_in"], "stated")).reshape(150, 4, H, D)
    q, k = (np.asarray(REF._rope(REF._rms(jnp.asarray(z[:, i]), s["eps"]), s["theta"]))
            for i in (0, 1))
    lam = np.exp(-(2.0 ** (-8.0 * (np.arange(H) + 1.0) / H)))[:, None, None]
    S, o = np.zeros((H, D, D)), np.zeros((150, H, D))
    for t in range(150):
        S = lam * S + k[t][:, :, None] * z[t, 2][:, None, :]
        o[t] = np.einsum("hd,hde->he", q[t], S) / np.sqrt(D)
    y = np.asarray(REF._rms(jnp.asarray(o, jnp.float32), s["eps"])) / (1 + np.exp(-z[:, 3]))
    want = np.asarray(REF._linear(jnp.asarray(y.reshape(150, H * D), jnp.float32), lp["w_o"],
                                  "stated"))
    np.testing.assert_allclose(got, want, atol=TOL)
