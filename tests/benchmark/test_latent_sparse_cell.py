"""The real manifest's ``glm_moe_dsa`` files (build, counts, reference, the
four new readers) driven through the harness on the CPU by a toy twin:
``toy/BENCHMARK.latent_sparse.json`` is the toy's manifest, which is not
this file's to edit, with one configuration, one cell and the four metrics
appended, and the twin's configuration, mix and limits are files beside the
toy's. So the twin, like the real cell, is files and entries alone. The
timed path broken underneath (the selection skipped; a held expert left
out) has to come out as not correct; and the benchmark's blocked copy of
the reference has to agree with the plain one in ``tests/``."""

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

TWIN = REPO / "tests/benchmark/toy/BENCHMARK.latent_sparse.json"
CELL = "toy-latent.toy-longdoc"
SEED = 2**31 + 2828  # the driver's seeds are large
NEW = ("dsa_selected_pct", "expert_rows_per_call", "prefill_chunks_per_join",
       "prefill_chunk_roofline")


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
    # the architecture's files are the real manifest's own, found by name
    cell = harness.load_cell(TWIN, CELL, root=REPO)
    for kind, mod in (("build", cell.build), ("reference", cell.reference),
                      ("counts", cell.counts.architecture)):
        assert mod.__file__ == str(REPO / f"benchmark/{kind}/glm_moe_dsa.py")
    real_cell = harness.load_cell(REPO / "BENCHMARK.json", "glm-5.2-ep16-d5.longdoc")
    assert {e["name"] for e, _ in real_cell.per_layer} >= set(NEW) | {
        "decode_roofline", "serve_mfu"}
    assert "prefill_roofline" not in {e["name"] for e, _ in real_cell.per_layer}
    # the real configuration builds the program's config at the published widths
    mc = real_cell.build.model_config(real_cell.cfg)
    assert (mc.num_layers, mc.latent_row, mc.index_topk, mc.num_experts, mc.experts_held) == (
        5, 576, 2048, 256, (0, 16))
    assert mc.index_layers == (0, 4) and mc.mlp_kinds[0] == "dense"


def test_one_deal_offers_every_seed_the_same_sizes_in_the_same_order():
    """``loops/closed_one_deal.py``: the deck's hands one after another, the
    same for every seed; a client that finished takes the next card; the
    ids are the seed's."""
    from benchmark import traffic

    cell = harness.load_cell(REPO / "BENCHMARK.json", "glm-5.2-ep16-d5.longdoc")
    mix = dict(cell.mix, first_card=0)

    def deal(seed, n):
        src = cell.loop.source(mix, seed, 1000)
        got = src.due(0.0, None)
        assert [c for c, _, _ in got] == list(range(mix["clients"]))
        while len(got) < n:
            got += src.due(1.0, [5, 2])
        return got

    a, b = deal(SEED, 38), deal(SEED + 1, 38)
    sizes = [(len(p), new) for _, p, new in a]
    assert sizes == [(len(p), new) for _, p, new in b]
    assert all(pa != pb for (_, pa, _), (_, pb, _) in zip(a, b))
    assert [c for c, _, _ in a[8:12]] == [5, 2, 5, 2]
    assert sorted(sizes[:15]) == sorted(traffic.deck(mix)) and sizes[15:30] == sizes[:15]
    for h in range(0, 36, 3):  # whole hands: every new-token count once
        assert sorted(new for _, new in sizes[h:h + 3]) == mix["max_new"]["values"]
    assert deal(SEED, 38) == a  # the same seed gives the same inputs
    # ``first_card``: the same round, entered six cards (two hands) later
    later = cell.loop.source(dict(mix, first_card=6), SEED, 1000).due(0.0, None)
    assert [(len(p), new) for _, p, new in later] == sizes[6:14]


@pytest.mark.timeout(600)
def test_twin_cell_is_correct_and_reads_the_new_metrics():
    result, phases = _run(trace=True, control=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["control"]["correct"] is False  # bfloat16 in a float32 cell's place
    assert phases["window"]["lowerings_in_window"] == 0
    assert not any(phases["window"]["zero_counters"].values())
    got = result["metrics"]
    # counters read anywhere; no share of a roofline is reported off the chip
    assert set(NEW[:3]) <= set(got) and NEW[3] not in got
    assert 12.0 < got["dsa_selected_pct"]["value"] < 75.0
    assert got["prefill_chunks_per_join"]["value"] > 1.5  # prompts of 48-96 in chunks of 32
    assert 0.0 < got["expert_rows_per_call"]["value"]


def _skip_selection(model, engine, server):
    """Attend to everything visible: the indexer computed and ignored."""
    model.config = dataclasses.replace(model.config, index_topk=10**6)
    engine.rebuild(engine.backend)


def _leave_out_an_expert(model, engine, server):
    layers = [dict(lp) for lp in model.params["layers"]]
    for lp in layers[1:]:
        lp["e_down"] = lp["e_down"].at[1].set(0.0)
    model.params = {**model.params, "layers": layers}


@pytest.mark.timeout(600)
@pytest.mark.parametrize("tamper", [_skip_selection, _leave_out_an_expert],
                         ids=["selection_skipped", "held_expert_left_out"])
def test_twin_cell_broken_underneath_is_not_correct(tamper):
    from triton_dist_tpu.runtime import telemetry

    names = [f"tdt_dsa_positions_{what}_total" for what in ("visible", "selected")]
    before = []

    def after_warm_up(model, engine, server):
        before[:] = [telemetry.counter_total(n) for n in names]
        tamper(model, engine, server)

    result, _ = _run(trace=False, tamper=after_warm_up)
    assert result["correct"] is False
    value, limit = result["compared"]["logit_gap"]
    assert value > 10 * limit
    # ``dsa_selected_pct``'s counters come from the selection on the device:
    # skipped, everything visible is selected; left alone, a fifth
    visible, selected = (telemetry.counter_total(n) - b for n, b in zip(names, before))
    if tamper is _skip_selection:
        assert selected == visible > 0
    else:
        assert 0.12 * visible < selected < 0.5 * visible


def test_the_benchmarks_copy_agrees_with_the_plain_reference():
    """Same weights (drawn by either side from the key), same logits: the
    blocked copy against ``tests/latent_sparse_ref.py``, with blocks small
    enough to be several, and the experts' gathered path forced."""
    import latent_sparse_ref as plain
    from triton_dist_tpu.models.latent_sparse import init_params
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
    np.testing.assert_array_equal(np.asarray(weights["head"]), np.asarray(params["lm_head"]))
    tokens = np.random.default_rng(5).integers(0, 256, size=(2, 80)).astype(np.int32)
    rows = np.asarray([[40, 63, 79], [17, 30, 70]], np.int32)
    got = np.asarray(ref.logits_at(cfg, weights, tokens, rows, block=(2, 16, 32)))
    for i in range(2):
        want = np.asarray(jax.jit(lambda p, t: plain.forward(mc, p, t))(params, tokens[i]))
        np.testing.assert_allclose(got[i], want[rows[i]], atol=2e-4)
    s = ref.sizes(cfg)
    lp = weights["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (80, s["d"]))
    dense = ref._routed(s, "stated", lp, x)
    np.testing.assert_allclose(np.asarray(ref._routed(s, "stated", lp, x, cap=40)),
                               np.asarray(dense), atol=2e-5)  # the rows that chose it, gathered
    np.testing.assert_allclose(np.asarray(ref._routed(s, "stated", lp, x, cap=4)),
                               np.asarray(dense), atol=2e-5)  # more chose it than cap: all rows
