"""The plain reference against the program on ``test-dense`` widths in
float32 on the CPU: prefill logits, then decode through the paged pool, and
the served greedy tokens under the comparison that decides ``correct``. Then
the control at a size this test chooses: the reference in bfloat16, put in
the program's place, fails the same comparison."""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import correct, harness, stats  # noqa: E402
from benchmark.build import qwen3_dense as builder  # noqa: E402
from benchmark.reference import qwen3_dense as ref  # noqa: E402

CFG = json.loads((REPO / "tests/benchmark/toy/configs/toy-dense.json").read_text())
LIMIT = json.loads(
    (REPO / "tests/benchmark/toy/limits/toy-dense.toy-chat.json").read_text()
)["logit_gap"]["limit"]
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def program():
    from triton_dist_tpu.models import DenseLLM, Engine
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(
        devices=jax.devices()[:1], axis_names=("tp",), set_default=False)
    model = DenseLLM(builder.model_config(CFG), ctx, key=jnp.asarray(harness.seed_key(SEED)))
    return model, Engine(model, backend="dist", max_len=64)


def test_the_dense_builder_refuses_an_expert_preset_and_a_width_that_differs():
    assert builder.model_config(CFG).num_layers == CFG["num_hidden_layers"]
    with pytest.raises(ValueError):
        builder.model_config(dict(CFG, serving=dict(CFG["serving"], preset="test-moe")))
    with pytest.raises(ValueError):
        builder.model_config(dict(CFG, head_dim=64))


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(CFG, harness.seed_key(SEED), jax.devices()[:1])


def test_the_reference_draws_the_programs_weights(program, weights):
    p = program[0].params
    for mine, theirs in (("embed", p.embed), ("wqkv", p.wqkv), ("wo", p.wo),
                         ("gate", p.mlp_gate), ("up", p.mlp_up), ("down", p.mlp_down),
                         ("head", p.lm_head)):
        assert weights[mine].dtype == theirs.dtype
        np.testing.assert_array_equal(np.asarray(weights[mine]), np.asarray(theirs))
    for ones in (p.ln1, p.ln2, p.q_norm, p.k_norm, p.final_norm):
        assert float(jnp.abs(ones - 1).max()) == 0.0


def _serve_paged(engine, ids, steps, block_size=16):
    """Prefill ``ids`` in one chunk, scatter into a one-slot pool, then
    ``steps`` greedy decode steps through the pool. Returns (prefill logits,
    [decode logits], tokens)."""
    p_len = len(ids)
    max_blocks = -(-engine.max_len // block_size)
    paged = engine.alloc_paged(1, block_size=block_size, num_blocks=max_blocks + 1)
    kbuf, vbuf = engine.paged_kbuf_zeros(p_len)
    logits_p, kbuf, vbuf = engine.prefill_chunk(
        kbuf, vbuf, jnp.asarray([ids], jnp.int32), 0, p_len - 1)
    table = np.arange(1, max_blocks + 1, dtype=np.int32)
    paged = engine.complete_paged_prefill(paged, kbuf, vbuf, table, 0)
    paged = dataclasses.replace(
        paged, tables=jnp.asarray(table[None]), lengths=jnp.asarray([p_len], jnp.int32))
    toks = [int(jnp.argmax(logits_p[0]))]
    decode_logits = []
    for _ in range(steps):
        decode_logits.append(np.asarray(
            engine.decode_logits_paged(paged, jnp.asarray(toks[-1:], jnp.int32))[0]))
        out, _, paged, _ = engine.decode_steps_paged(
            paged, jnp.asarray(toks[-1:], jnp.int32), jnp.asarray([8], jnp.int32), 1)
        toks.append(int(np.asarray(out)[0, 0]))
    return np.asarray(logits_p[0]), decode_logits, toks


@pytest.mark.timeout(600)
def test_prefill_then_paged_decode_agree_with_the_reference(program, weights):
    _, engine = program
    ids = np.random.default_rng(7).integers(0, 256, size=21).tolist()
    steps = 4
    logits_p, logits_d, toks = _serve_paged(engine, ids, steps)
    seq = np.asarray([ids + toks[:-1]], np.int32)
    rows = np.asarray([[len(ids) - 1 + j for j in range(steps + 1)]], np.int32)
    want = np.asarray(ref.logits_at(CFG, weights, seq, rows))[0]
    assert np.abs(want[0] - logits_p).max() < 2e-4
    for j in range(steps):
        assert np.abs(want[j + 1] - logits_d[j]).max() < 2e-4
    # and under the comparison that decides ``correct``:
    log = stats.ReqLog(0, len(ids), len(toks), 0.0, prompt=ids)
    log.tokens, log.finish_reason = toks, "ok"
    got = correct.compare(ref, CFG, weights, [log], 64, 8)
    assert got["tokens_compared"] == steps + 1
    assert got["logit_gap"] <= LIMIT
    # one served token altered: the same comparison fails
    log.tokens = toks[:2] + [(toks[2] + 1) % 256] + toks[3:]
    assert correct.compare(ref, CFG, weights, [log], 64, 8)["logit_gap"] > LIMIT


TOY = REPO / "tests/benchmark/toy/BENCHMARK.json"


@pytest.mark.timeout(600)
def test_the_toys_second_architecture_agrees_with_its_reference():
    """``toy/build/qwen3_moe.py`` (the program's expert model class) against
    ``toy/reference/qwen3_moe.py``, both found by the configuration's
    ``architecture``: the weights to the bit, then prefill and paged decode."""
    cell = harness.load_cell(TOY, "toy-moe.toy-short", root=REPO)
    key = harness.seed_key(SEED)
    model, engine, server = cell.build.build(cell.cfg, key, jax.devices()[:1])
    try:
        assert type(model).__name__ == "EPMoELLM"
        w = cell.reference.make_weights(cell.cfg, key, jax.devices()[:1])
        p = model.params
        for mine, theirs in (("embed", p.embed), ("wqkv", p.wqkv), ("router", p.router),
                             ("gate", p.mlp_gate), ("up", p.mlp_up), ("down", p.mlp_down),
                             ("head", p.lm_head)):
            np.testing.assert_array_equal(np.asarray(w[mine]), np.asarray(theirs))
        # 8 tokens: a longer prompt whose rows agree on an expert overflows its slots
        ids = np.random.default_rng(11).integers(0, 256, size=8).tolist()
        steps = 3
        logits_p, logits_d, toks = _serve_paged(engine, ids, steps)
        seq = np.asarray([ids + toks[:-1]], np.int32)
        rows = np.asarray([[len(ids) - 1 + j for j in range(steps + 1)]], np.int32)
        want = np.asarray(cell.reference.logits_at(cell.cfg, w, seq, rows))[0]
        assert np.abs(want[0] - logits_p).max() < 2e-4
        for j in range(steps):
            assert np.abs(want[j + 1] - logits_d[j]).max() < 2e-4
        # the dense reference over the same tokens is another model's
        dense_w = ref.make_weights(CFG, key, jax.devices()[:1])
        assert np.abs(np.asarray(ref.logits_at(CFG, dense_w, seq, rows))[0] - want).max() > 0.1
    finally:
        cell.build.release(model, engine, server)
    assert model.params is None and server.cache is None


def _decision(numbers, served_requests):
    """``harness.decide`` over ``numbers`` for the toy cell, with a window
    that holds ``served_requests`` finished in full."""
    cell = harness.load_cell(TOY, "toy-dense.toy-chat", root=REPO)
    run = harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=None, reqs=served_requests,
                      t_open=0.0, t_close=2.0, t_drain_end=3.0, first_step=1, last_step=2,
                      telemetry=harness.Telemetry({}, {}), lowered_in_window=0)
    return harness.decide(cell, run, numbers)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_comes_out_as_not_correct(seed):
    """The control at this test's size: 8 sequences of 48 positions (376
    tokens compared, as many as a run compares). float32 stated, so the
    next precision below is bfloat16. Its first choices take the served
    tokens' place and go through the harness's own comparison and decision;
    so do the reference's own first choices, which have to pass."""
    assert ref.NEXT_LOWER[CFG["torch_dtype"]] == "bfloat16"
    w = ref.make_weights(CFG, harness.seed_key(seed), jax.devices()[:1])
    toks = np.random.default_rng(seed).integers(0, 256, size=(8, 48))
    reqs = []
    for i, row in enumerate(toks.tolist()):
        r = stats.ReqLog(i, 1, 47, 1.0, prompt=row[:1])
        r.tokens, r.finish_reason = row[1:], "ok"
        reqs.append(r)
    got = correct.compare(ref, CFG, w, reqs, 48, 47, control=True)
    control = got.pop("control")
    assert control["precision"] == "bfloat16" and control["tokens_compared"] == 8 * 47
    ok, compared = _decision(control, reqs)
    assert ok is False
    assert compared["logit_gap"][0] > 3 * LIMIT and compared["bad_requests"] == [0, 0]
    assert compared["tokens_short"] == [0, 0]
    # the stated precision in the same place is correct, to the last token
    seq = np.zeros((8, 48), np.int32)
    seq[:, :47] = toks[:, :47]
    rows = np.tile(np.arange(47, dtype=np.int32), (8, 1))
    first = np.asarray(ref.logits_at(CFG, w, seq, rows).argmax(-1))
    stated = correct.gaps(ref.logits_at(CFG, w, seq, rows), first, np.ones((8, 47), bool))
    ok, compared = _decision({"logit_gap": float(stated.max()), "tokens_compared": 8 * 47}, reqs)
    assert ok is True and compared["logit_gap"][0] == 0.0


def test_int8_reads_wider_than_bfloat16():
    w = ref.make_weights(CFG, harness.seed_key(4), jax.devices()[:1])
    toks = np.random.default_rng(4).integers(0, 256, size=(4, 32)).astype(np.int32)
    rows = np.tile(np.arange(32, dtype=np.int32), (4, 1))
    full = ref.logits_at(CFG, w, toks, rows)
    low = ref.logits_at(CFG, w, toks, rows, precision="int8")
    bf = ref.logits_at(CFG, w, toks, rows, precision="bfloat16")
    assert float(jnp.abs(low - full).max()) > float(jnp.abs(bf - full).max()) > 0
    with pytest.raises(ValueError):
        ref.logits_at(CFG, w, toks, rows, precision="fp8")


def test_blocks_and_padding_do_not_change_the_logits():
    w = ref.make_weights(CFG, harness.seed_key(5), jax.devices()[:1])
    toks = np.random.default_rng(5).integers(0, 256, size=(3, 24)).astype(np.int32)
    rows = np.tile(np.asarray([3, 11], np.int32), (3, 1))
    whole = np.asarray(ref.logits_at(CFG, w, toks, rows, block=3))
    by_two = np.asarray(ref.logits_at(CFG, w, toks, rows, block=2))
    padded = np.concatenate([toks, np.zeros((3, 8), np.int32)], axis=1)
    longer = np.asarray(ref.logits_at(CFG, w, padded, rows, block=3))
    np.testing.assert_allclose(whole, by_two, atol=1e-5)
    np.testing.assert_allclose(whole, longer, atol=1e-5)


def test_pack_and_choose():
    reqs = []
    for i, (p, n) in enumerate([(5, 3), (9, 2), (4, 6), (7, 1)]):
        r = stats.ReqLog(i, p, n, float(i), prompt=list(range(p)))
        r.tokens, r.finish_reason = list(range(100, 100 + n)), "ok"
        reqs.append(r)
    reqs[3].finish_reason = "deadline"
    picked = correct.choose(reqs, 2, seed=9)
    assert picked[0] is reqs[1] and len(picked) == 2 and reqs[3] not in picked
    assert correct.choose(reqs, 2, seed=9) == picked
    tokens, pos, served, mask = correct.pack([reqs[0]], 16, 4)
    assert tokens[0, :7].tolist() == [0, 1, 2, 3, 4, 100, 101]
    assert pos[0].tolist() == [4, 5, 6, 6] and served[0].tolist() == [100, 101, 102, 102]
    assert mask[0].tolist() == [True, True, True, False]
    with pytest.raises(ValueError):
        correct.pack([reqs[2]], 8, 4)
