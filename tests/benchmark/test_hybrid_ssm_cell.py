"""The real manifest's ``phi4flash`` files (build, counts, reference, the
three new readers) driven through the harness on the CPU by a toy twin:
``toy/BENCHMARK.hybrid_ssm.json`` is the toy's manifest, which is not this
file's to edit, with one configuration, one cell and four metrics appended,
and the twin's configuration, mix and limits are files beside the toy's. So
the twin, like the real cell, is files and entries alone. The timed path
broken underneath in four ways (:data:`TAMPERS`) has to come out as not
correct; and the benchmark's blocked copy of the reference has to agree
with the plain one in ``tests/``."""

import dataclasses
import io
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))

from benchmark import harness  # noqa: E402

TWIN = REPO / "tests/benchmark/toy/BENCHMARK.hybrid_ssm.json"
CELL = "toy-hybrid.toy-reason"
REAL_CELL = "phi-4-mini-flash.reason"
SEED = 2**31 + 3232  # the driver's seeds are large
NEW = ("ssm_scan_roofline", "swa_attended_pct", "decode_rows_per_step",
       "prefill_chunks_per_join")


# ----- the timed path broken underneath: (model, engine, server, setattr).
# ``patch(obj, name, value)`` sets an attribute (pytest's monkeypatch here,
# plain ``setattr`` in a one-off process on the chip).


def stale_state(model, engine, server, patch):
    """A join starts from slot 0's recurrent state (whatever its tenant
    left there) in place of zeros; the rings do start empty."""
    def not_reset():
        live = server.cache.state
        return {k: [jnp.copy(x[:1]) if k in ("ssm", "conv") else jnp.zeros_like(x[:1])
                    for x in v] for k, v in live.items()}

    patch(engine, "prompt_state", not_reset)


def window_not_applied(model, engine, server, patch):
    """Every position attended: the rings as long as the longest sequence."""
    patch(model, "config", dataclasses.replace(
        model.config, sliding_window=engine.max_len))
    engine.rebuild(engine.backend)
    server.cache = server._fresh_cache()


def memory_zeroed(model, engine, server, patch):
    """The gated memory units gate zeros in place of the last Mamba layer's
    scan."""
    from triton_dist_tpu.layers import hybrid_ssm as hs

    gmu = hs.gmu
    patch(hs, "gmu", lambda lp, u, m: gmu(lp, u, jnp.zeros_like(m)))
    engine.rebuild(engine.backend)


def lambda_zeroed(model, engine, server, patch):
    """Plain attention's maps: the second softmax of every pair dropped."""
    from triton_dist_tpu.layers import hybrid_ssm as hs

    patch(hs, "diff_lambda", lambda lp, layer: jnp.float32(0.0))
    engine.rebuild(engine.backend)


TAMPERS = {"stale_state": stale_state, "window_not_applied": window_not_applied,
           "memory_zeroed": memory_zeroed, "lambda_zeroed": lambda_zeroed}


def _run(trace, **kw):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TWIN, CELL, SEED, 1.0, trace, jax.devices()[:1],
                              out=out, err=err, root=REPO, **kw)
    return result, {l["phase"]: l for l in map(json.loads, out.getvalue().splitlines()[:-1])}


def test_twin_is_the_toys_manifest_with_entries_appended():
    mine = json.loads(TWIN.read_text())
    toy = json.loads(TWIN.with_name("BENCHMARK.json").read_text())
    assert [m["name"] for m in mine["per_layer"][len(toy["per_layer"]):]] == list(NEW)
    assert dict(mine, configs=mine["configs"][:-1], workloads=mine["workloads"][:-1],
                per_layer=mine["per_layer"][:-len(NEW)]) == toy
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    judged = {m["name"]: m for m in real["per_layer"]}
    assert mine["per_layer"][-len(NEW):] == [
        {k: v for k, v in judged[n].items() if k != "workloads"} for n in NEW]
    assert all(judged[n]["workloads"] == [REAL_CELL] for n in NEW[:3])
    # the architecture's files are the real manifest's own, found by name
    cell = harness.load_cell(TWIN, CELL, root=REPO)
    for kind, mod in (("build", cell.build), ("reference", cell.reference),
                      ("counts", cell.counts.architecture)):
        assert mod.__file__ == str(REPO / f"benchmark/{kind}/phi4flash.py")


def test_real_configuration_is_the_published_one_uncut():
    """Every number of the catalog's ``config`` under the same key, nothing
    reduced, the program's config at the published widths, and the bytes
    the file states reckoned again from the model."""
    real = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg = real.cfg
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
        "vocab_size": 200064}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and not cfg["left_out"]
    assert {e["name"] for e, _ in real.per_layer} >= set(NEW) | {
        "decode_roofline", "serve_mfu", "prefill_chunk_roofline", "hbm_peak_pct"}
    assert not {"prefill_roofline", "paged_flash_decode_roofline"} & {
        e["name"] for e, _ in real.per_layer}
    mc = real.build.model_config(cfg)
    assert [len(mc.layers_of(k)) for k in ("mamba", "window", "full", "gmu", "cross")] == [
        9, 8, 1, 7, 7]
    assert mc.layers_of("full") == (17,) and mc.layers_of("mamba")[-1] == 16
    assert (mc.head_dim, mc.d_inner, mc.d_state, mc.dt_rank) == (64, 5120, 16, 160)
    from triton_dist_tpu.models import hybrid_ssm as H

    fake = type("M", (), {"config": mc})
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    elems = mc.vocab_size * mc.hidden_size + 2 * mc.hidden_size
    weights = 2 * elems
    for layer in range(mc.num_layers):
        shapes = ([(n, s) for n, s, _ in H.layer_tensors(mc, layer)]
                  + [(n, s) for n, (s, _) in H.layer_fixed(mc, layer).items()])
        elems += sum(int(np.prod(s)) for _, s in shapes)
        weights += sum(int(np.prod(s)) * (4 if n in H.SCAN_F32 else 2) for n, s in shapes)
    sv, stated = cfg["serving"], cfg["bytes"]
    per_slot = nbytes(jax.eval_shape(lambda: H.HybridSSMLLM.slot_state(fake, 1)))
    blocks = sv["slots"] * -(-sv["max_len"] // sv["block_size"]) + 1
    row = sum(r.layers * r.heads * r.width for r in H.HybridSSMLLM.cache_rows(fake)) * 2
    assert (stated["parameters"], stated["weights"]) == (elems, weights)
    assert (stated["slot_state_per_slot"], stated["slot_state"]) == (
        per_slot, sv["slots"] * per_slot)
    assert (stated["pool_per_token"], stated["pool"]) == (row, blocks * sv["block_size"] * row)
    assert stated["resident"] == weights + sv["slots"] * per_slot + stated["pool"]
    assert 0.5 < stated["resident"] / 16e9 == pytest.approx(stated["share_of_16e9"], abs=1e-4)


def test_counts_are_the_least_the_architecture_needs():
    """A decode step at 32 rows of 1300 positions: every weight once, 8
    reads of one layer's K/V, 8 window reads, the state in and out."""
    real = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg, counts = real.cfg, real.counts
    step = counts.decode_steps(cfg, 1, [1300] * 32)
    state = 8 * 5120 * 1300 + 8 * 512 * 5120 + 2 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert step["bytes"] == pytest.approx(cfg["bytes"]["weights"] + 32 * state, rel=0.01)
    assert step["bytes"] / 819e9 == pytest.approx(12.6e-3, rel=0.03)
    # a row's matrix work is twice the parameters (the embedding once, as the head)
    assert step["flops"] / 32 == pytest.approx(2 * cfg["bytes"]["parameters"], rel=0.05)
    chunk, whole = counts.prefill_chunk(cfg, 512), counts.prefill(cfg, 512)
    assert chunk["flops"] < whole["flops"] < 1.01 * chunk["flops"]  # the last row's layers
    assert whole["bytes"] == pytest.approx(cfg["bytes"]["weights"], rel=0.02)
    assert counts.prefill(cfg, 1536)["flops"] == pytest.approx(3 * chunk["flops"], rel=0.02)
    scan = counts.ssm_scan(cfg, 512)
    assert scan["flops"] == 7 * 512 * 5120 * 16
    assert scan["bytes"] == 4 * (3 * 512 * 5120 + 2 * 512 * 16 + 3 * 16 * 5120 + 5120)


@pytest.mark.timeout(600)
def test_twin_cell_is_correct_and_reads_the_new_metrics():
    result, phases = _run(trace=True, control=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["control"]["correct"] is False  # bfloat16 in a float32 cell's place
    assert phases["window"]["lowerings_in_window"] == 0
    assert not any(phases["window"]["zero_counters"].values())
    got = result["metrics"]
    # counters read anywhere; no share of a roofline is reported off the chip
    assert set(NEW[1:]) <= set(got) and NEW[0] not in got
    assert 25.0 < got["swa_attended_pct"]["value"] < 75.0  # a window of 8 under 9-35
    assert 1.0 <= got["decode_rows_per_step"]["value"] <= 4.0
    assert 1.0 <= got["prefill_chunks_per_join"]["value"] <= 3.0  # prompts of 1-3 chunks


@pytest.mark.timeout(600)
@pytest.mark.parametrize("name", list(TAMPERS))
def test_twin_cell_broken_underneath_is_not_correct(name, monkeypatch):
    from triton_dist_tpu.runtime import telemetry

    names = [f"tdt_swa_positions_{what}_total" for what in ("visible", "attended")]
    before = []

    def after_warm_up(model, engine, server):
        before[:] = [telemetry.counter_value(n, phase="decode") for n in names]
        TAMPERS[name](model, engine, server, monkeypatch.setattr)

    result, _ = _run(trace=False, tamper=after_warm_up)
    assert result["correct"] is False
    value, limit = result["compared"]["logit_gap"]
    assert value > 10 * limit
    # ``swa_attended_pct``'s counters come from the window layers' own masks
    visible, attended = (telemetry.counter_value(n, phase="decode") - b
                         for n, b in zip(names, before))
    if name == "window_not_applied":
        assert attended == visible > 0
    else:
        assert 0.25 * visible < attended < 0.75 * visible


def test_the_benchmarks_copy_agrees_with_the_plain_reference():
    """Same weights (drawn by either side from the key), same logits: the
    blocked copy against ``tests/hybrid_ssm_ref.py``, with blocks of
    queries small enough to be several."""
    import hybrid_ssm_ref as plain
    from triton_dist_tpu.models.hybrid_ssm import init_params
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    cell = harness.load_cell(TWIN, CELL, root=REPO)
    ref, cfg = cell.reference, cell.cfg
    key = harness.seed_key(SEED)
    weights = ref.make_weights(cfg, key, jax.devices()[:1])
    mc = cell.build.model_config(cfg)
    ctx = initialize_distributed(devices=jax.devices()[:1], axis_names=("tp",), set_default=False)
    params = init_params(mc, jnp.asarray(key), ctx)
    for mine, theirs in zip(weights["layers"], params["layers"]):
        for name, w in mine.items():
            np.testing.assert_array_equal(np.asarray(w), np.asarray(theirs[name]))
    np.testing.assert_array_equal(np.asarray(weights["embed"]), np.asarray(params["embed"]))
    tokens = np.random.default_rng(5).integers(0, 256, size=(2, 40)).astype(np.int32)
    rows = np.asarray([[20, 31, 39], [7, 8, 35]], np.int32)
    got = np.asarray(ref.logits_at(cfg, weights, tokens, rows, block=16))
    for i in range(2):
        want = np.asarray(jax.jit(lambda p, t: plain.forward(mc, p, t))(params, tokens[i]))
        np.testing.assert_allclose(got[i], want[rows[i]], atol=2e-4)
