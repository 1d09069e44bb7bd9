"""The real manifest's ``minicpm_sala`` files (build, counts, reference, the
five new readers) driven through the harness on the CPU by a toy twin:
``toy/BENCHMARK.sala.json`` is the toy's manifest, which is not this file's
to edit, with one configuration, one cell and six metrics appended, and the
twin's configuration, mix and limits are files beside the toy's. So the
twin, like the real cell, is files and entries alone. The timed path broken
underneath in four ways (:data:`TAMPERS`) has to come out as not correct
(``slow``: a harness run each); the trace's readers are held on a hand-made
trace, since no share of a roofline is reported off the chip."""

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

from benchmark import harness, peaks, stats  # noqa: E402
from benchmark import trace as tr  # noqa: E402

TWIN = REPO / "tests/benchmark/toy/BENCHMARK.sala.json"
CELL = "toy-sala.toy-longqa"
REAL_CELL = "minicpm-sala-d12.longqa"
SEED = 2**31 + 3535  # the driver's seeds are large
NEW = ("bsa_selected_pct", "bsa_select_ms_per_chunk", "lightning_chunk_roofline",
       "bsa_prefill_roofline", "bsa_decode_roofline")
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


# ----- the timed path broken underneath: (model, engine, server, setattr).
# ``patch(obj, name, value)`` sets an attribute (pytest's monkeypatch here,
# plain ``setattr`` in a one-off process on the chip).


def forced_blocks_alone(model, engine, server, patch):
    """The selection replaced by the forced blocks: the first and the
    window, no learned block."""
    from triton_dist_tpu.layers import sparse_linear as sl

    select = sl.select_blocks

    def forced_alone(score, own, topk, init_blocks, window_blocks):
        _, forced = select(score, own, topk, init_blocks, window_blocks)
        return jnp.broadcast_to(forced, score.shape), forced

    patch(sl, "select_blocks", forced_alone)
    engine.rebuild(engine.backend)


def lam_one(model, engine, server, patch):
    """No decay: every lightning head's ``lam`` forced to 1."""
    from triton_dist_tpu.kernels import lightning_attn as la

    patch(la, "slopes", lambda heads: jnp.zeros((heads,), jnp.float32))
    engine.rebuild(engine.backend)


def stale_state(model, engine, server, patch):
    """A join starts from slot 0's linear state (whatever its tenant left
    there) in place of zeros; the pooled keys do start empty."""
    def not_reset():
        live = server.cache.state
        return {k: [jnp.copy(x[:1]) if k == "linear" else jnp.zeros_like(x[:1]) for x in v]
                for k, v in live.items()}

    patch(engine, "prompt_state", not_reset)


def state_frozen(model, engine, server, patch):
    """A decode step reads the slots' linear state and writes nothing: the
    state stays as the prefill left it."""
    from triton_dist_tpu.kernels import lightning_attn as la

    step = la.lightning_step

    def frozen(q, k, v, S, slope, active):
        return step(q, k, v, S, slope, active)[0], S

    patch(la, "lightning_step", frozen)
    engine.rebuild(engine.backend)


TAMPERS = {"forced_blocks_alone": forced_blocks_alone, "lam_one": lam_one,
           "stale_state": stale_state, "state_frozen": state_frozen}


def _run(trace, **kw):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TWIN, CELL, SEED, 1.0, trace, jax.devices()[:1],
                              out=out, err=err, root=REPO, **kw)
    return result, {l["phase"]: l for l in map(json.loads, out.getvalue().splitlines()[:-1])}


def test_twin_is_the_toys_manifest_with_entries_appended():
    mine = json.loads(TWIN.read_text())
    toy = json.loads(TWIN.with_name("BENCHMARK.json").read_text())
    names = NEW + ("prefill_chunks_per_join",)
    assert [m["name"] for m in mine["per_layer"][len(toy["per_layer"]):]] == list(names)
    assert dict(mine, configs=mine["configs"][:-1], workloads=mine["workloads"][:-1],
                per_layer=mine["per_layer"][:-len(names)]) == toy
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    judged = {m["name"]: m for m in real["per_layer"]}
    assert mine["per_layer"][-len(names):] == [
        {k: v for k, v in judged[n].items() if k != "workloads"} for n in names]
    # the five new entries are the manifest's last, each lists the one cell
    assert [m["name"] for m in real["per_layer"][-5:]] == list(NEW)
    assert all(judged[n]["workloads"] == [REAL_CELL] for n in NEW)
    assert real["workloads"][-1]["name"] == REAL_CELL and real["workloads"][-1]["chips"] == 1
    # the architecture's files are the real manifest's own, found by name
    cell = harness.load_cell(TWIN, CELL, root=REPO)
    for kind, mod in (("build", cell.build), ("reference", cell.reference),
                      ("counts", cell.counts.architecture)):
        assert mod.__file__ == str(REPO / f"benchmark/{kind}/minicpm_sala.py")


def test_real_configuration_is_the_published_one_cut_in_depth_alone():
    """Every key of the catalog's ``config`` unchanged but the two in
    ``reduced`` (layers 9-20 of the published list, in order), the
    program's config at the published widths, the mix as ISSUE 35 names it,
    and the bytes the file states reckoned again from the model."""
    real = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg, mix = real.cfg, real.mix
    if CATALOG.is_file():
        rows = [json.loads(l) for l in CATALOG.read_text().splitlines()]
        row = next(r for r in rows if r["name"] == "MiniCPM-SALA")
        assert cfg["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if cfg[k] != v}
        assert differ == set(cfg["reduced"]) == {"num_hidden_layers", "mixer_types"}
        assert cfg["published"]["mixer_types"] == row["config"]["mixer_types"]
        assert cfg["mixer_types"] == row["config"]["mixer_types"][9:21]
    assert cfg["published"]["num_hidden_layers"] == 32 and cfg["num_hidden_layers"] == 12
    assert (cfg["mixer_types"].count("minicpm4"), cfg["mixer_types"].count("lightning-attn")) == (3, 9)
    assert cfg["serving"] == {"backend": "dist", "chips": 1, "tp": 1, "mesh_axis": "tp",
                              "slots": 8, "max_len": 16640, "chunk": 8, "block_size": 64,
                              "prefill_chunk": 2048}
    assert (mix["loop"], mix["clients"], mix["deck"], mix["hand"], mix["check_requests"]) == (
        "closed_one_deal", 8, 15, 3, 4)
    assert mix["prompt_len"] == {"values": [10240, 12288, 16384], "weights": [0.4, 0.4, 0.2]}
    assert mix["max_new"]["values"] == [64, 128, 256]
    assert {e["name"] for e, _ in real.per_layer} >= set(NEW) | {
        "decode_roofline", "serve_mfu", "prefill_chunk_roofline", "prefill_chunks_per_join",
        "hbm_peak_pct", "device_idle_pct"}
    # ``decode_rows_per_step`` lists ``reason`` alone: ``test_hybrid_ssm_cell.py`` holds it to that
    assert "decode_rows_per_step" not in {e["name"] for e, _ in real.per_layer}
    mc = real.build.model_config(cfg)
    assert mc.layers_of("sparse") == (0, 7, 8) and len(mc.layers_of("lightning")) == 9
    assert (mc.head_dim, mc.num_q_heads, mc.num_kv_heads, mc.lightning_heads) == (128, 32, 2, 32)
    assert (mc.block_size, mc.topk, mc.pooled_extent) == (64, 64, 1040)
    assert mc.residual_scale == pytest.approx(1.4 / 32 ** 0.5) and mc.logit_scale == 1 / 16
    from triton_dist_tpu.models import sparse_linear as S

    fake = type("M", (), {"config": mc})
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    elems = 2 * mc.vocab_size * mc.hidden_size + mc.hidden_size
    for layer in range(mc.num_layers):
        elems += sum(int(np.prod(s)) for _, s in S.layer_tensors(mc, layer))
        elems += sum(n for _, n in S.layer_ones(mc, layer))
    sv, stated = cfg["serving"], cfg["bytes"]
    per_slot = nbytes(jax.eval_shape(lambda: S.SparseLinearLLM.slot_state(fake, 1)))
    blocks = sv["slots"] * -(-sv["max_len"] // sv["block_size"]) + 1
    row = sum(r.layers * r.heads * r.width for r in S.SparseLinearLLM.cache_rows(fake)) * 2
    assert (stated["parameters"], stated["weights"]) == (elems, 2 * elems)
    assert (stated["slot_state_per_slot"], stated["slot_state"]) == (
        per_slot, sv["slots"] * per_slot)
    assert per_slot == 9 * 32 * 128 * 128 * 4 + 3 * 1040 * 256 * 2
    assert (stated["pool_per_token"], stated["pool"]) == (row, blocks * sv["block_size"] * row)
    assert stated["resident"] == 2 * elems + sv["slots"] * per_slot + stated["pool"]
    assert 0.45 < stated["resident"] / 16e9 == pytest.approx(stated["share_of_16e9"], abs=1e-4)


def test_counts_are_the_least_the_architecture_needs():
    real = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg, counts = real.cfg, real.counts
    # a decode step at 8 rows of 13000: every weight once; a row reads 64
    # blocks' K and V on 3 layers, its pooled keys, 9 states in and out
    step = counts.decode_steps(cfg, 1, [13000] * 8)
    attended = 63 * 64 + (13000 - 1) % 64 + 1
    row = (3 * (attended * 1024 + (13000 - 32) // 16 * 512 + 512 + 1024)
           + 2 * 9 * 32 * 128 * 128 * 4)
    assert step["bytes"] == pytest.approx(
        cfg["bytes"]["weights"] - 73448 * 4096 * 2 + 8 * row, rel=0.002)
    assert step["bytes"] / 819e9 == pytest.approx(9.36e-3, rel=0.01)
    # a row's matrix work is twice the parameters less the embedding's lookup;
    # its attention, selection and nine state updates add 3 %
    assert step["flops"] / 8 == pytest.approx(
        2 * (cfg["bytes"]["parameters"] - 73448 * 4096), rel=0.04)
    chunk, whole = counts.prefill_chunk(cfg, 2048), counts.prefill(cfg, 2048)
    assert chunk["flops"] < whole["flops"] < 1.001 * chunk["flops"]  # the last row's head
    assert chunk["flops"] / 197e12 == pytest.approx(69.9e-3, rel=0.01)
    # past 4096 positions a query attends 64 blocks, not its whole context
    assert counts.prefill(cfg, 16384)["flops"] < 8.2 * chunk["flops"]
    light = counts.lightning_chunk(cfg, 2048)
    assert light == {"flops": 4.0 * 2048 * 4096 * 128,
                     "bytes": 4.0 * 2048 * 4096 * 2 + 2 * 32 * 128 * 128 * 4}
    pre = counts.bsa_prefill(cfg, 2048)
    assert pre["flops"] == 4.0 * 32 * 128 * 2048 * 2049 / 2
    assert pre["bytes"] == 2 * 2048 * 4096 * 2 + 2048 * 1024
    dec = counts.bsa_decode(cfg, [13000] * 8)
    assert dec["flops"] == 4.0 * 32 * 128 * 8 * attended
    assert dec["bytes"] == 8 * attended * 1024 + 8 * 4096 * 6
    assert counts.bsa_decode(cfg, [100])["bytes"] == 100 * 1024 + 4096 * 6  # dense while it fits


def _traced(cell, ops, programs, reqs=()):
    E = tr.Ev
    planes = {"/device:TPU:0": {"XLA Ops": [E(*o) for o in ops],
                                "XLA Modules": [E(*p) for p in programs]},
              "/host:CPU": {"python3": [E("server.step", 0, 10_000_000)]}}
    empty = {"counters": {}, "histograms": {}, "digests": {}}
    return harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=peaks.peaks_for("TPU v5 lite"),
                       reqs=list(reqs), t_open=0.0, t_close=1.0, t_drain_end=1.0, first_step=1,
                       last_step=1, telemetry=harness.Telemetry(empty, empty),
                       lowered_in_window=0, traced_first_step=1, traced_last_step=1,
                       trace=tr.reduce(planes))


def test_trace_readers_on_a_hand_made_trace():
    """One prefill chunk (nine lightning calls, three selections, three
    attends) and one decode chunk of two steps (three attends a step) of one
    request at 13000: each reader finds its kernel by name and lays its
    seconds against the counts; on a trace without them (the parent's, or
    another model's) nothing is read and nothing raises."""
    cell = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    mods = {e["name"]: m for e, m in cell.per_layer}
    call = "%{}.{} = f32[8]{{0}} custom-call(%p.1), custom_call_target=\"tpu_custom_call\""
    ops, t = [], 1_000_000
    for name, n, dur in (("lightning_chunk", 9, 400_000), ("bsa_select", 3, 500_000),
                         ("bsa_prefill", 3, 2_000_000), ("bsa_decode", 6, 100_000)):
        for i in range(n):
            ops.append((call.format(name, i), t, dur))
            t += dur + 1000
    programs = [("jit_chunk_fn(7)", 900_000, 20_000_000),
                ("jit_decode_chunk_paged(5)", 30_000_000, 600_000)]
    req = stats.ReqLog(0, 12999, 64, 0.0, token_step=[0, 1, 1], tokens=[1, 2, 3])
    run = _traced(cell, ops, programs, [req])
    counts, cfg = cell.counts, cell.cfg
    least = lambda w: counts.least_seconds(w, run.peaks)["seconds"]
    assert mods["bsa_select_ms_per_chunk"].read(run) == pytest.approx(1.5)
    assert mods["lightning_chunk_roofline"].read(run) == pytest.approx(
        100 * least(counts.lightning_chunk(cfg, 2048)) / 400e-6)
    assert mods["bsa_prefill_roofline"].read(run) == pytest.approx(
        100 * least(counts.bsa_prefill(cfg, 2048)) / 2e-3)
    want = 3 * (least(counts.bsa_decode(cfg, [13000])) + least(counts.bsa_decode(cfg, [13001])))
    assert mods["bsa_decode_roofline"].read(run) == pytest.approx(100 * want / 600e-6)
    assert all(0 < mods[n].read(run) < 100 for n in NEW[2:])
    bare = _traced(cell, [("%fusion.9 = bf16[4] fusion(%p)", 1_000_000, 400_000),
                          ("%bsa_decode_ref.1 = f32[8] fusion(%p)", 2_000_000, 5)], programs, [req])
    assert [mods[n].read(bare) for n in NEW[1:]] == [None] * 4
    run.trace = None
    assert [mods[n].read(run) for n in NEW] == [None] * 5


@pytest.mark.timeout(600)
def test_twin_cell_is_correct_and_reads_the_new_metrics():
    result, phases = _run(trace=True)
    assert result["correct"] is True and result["failed"] == 0
    assert phases["window"]["lowerings_in_window"] == 0
    assert not any(phases["window"]["zero_counters"].values())
    got = result["metrics"]
    # counters read anywhere; no share of a roofline is reported off the chip
    assert "bsa_selected_pct" in got and not set(NEW[1:]) & set(got)
    # 4 blocks of the 1-20 a query of a prompt of 12-20 blocks sees
    assert 30.0 < got["bsa_selected_pct"]["value"] < 60.0
    assert 3.0 <= got["prefill_chunks_per_join"]["value"] <= 5.0


def test_the_control_misses_the_twins_limit():
    """The reference in bfloat16 in the served tokens' place, on two fixed
    sequences of 160 (a window's sample is some thirty tokens of whichever
    requests it finished, and bfloat16 flips one token in thirty on logits
    that muP divides by 16: a sample that size can hold no flip): of 256
    tokens several are not the float32 reference's first choice, by 5-60
    times the twin's limit."""
    from benchmark import correct

    cell = harness.load_cell(TWIN, CELL, root=REPO)
    ref, cfg = cell.reference, cell.cfg
    weights = ref.make_weights(cfg, harness.seed_key(SEED), jax.devices()[:1])
    tokens = np.random.default_rng(SEED).integers(0, 256, size=(2, 160)).astype(np.int32)
    rows = np.broadcast_to(np.arange(32, 160), (2, 128))
    exact = ref.logits_at(cfg, weights, tokens, rows, block=32)
    low = ref.logits_at(cfg, weights, tokens, rows, precision=ref.NEXT_LOWER[cfg["torch_dtype"]],
                        block=32)
    mask = np.ones(rows.shape, bool)
    gaps = np.asarray(correct.gaps(exact, np.asarray(low.argmax(-1)), mask))
    limit = cell.limits["logit_gap"]["limit"]
    assert not np.asarray(correct.gaps(exact, np.asarray(exact.argmax(-1)), mask)).any()
    assert (gaps > limit).sum() >= 3 and gaps.max() > 5 * limit


@pytest.mark.slow
@pytest.mark.timeout(600)
@pytest.mark.parametrize("name", list(TAMPERS))
def test_twin_cell_broken_underneath_is_not_correct(name, monkeypatch):
    result, _ = _run(trace=False, tamper=lambda *a: TAMPERS[name](*a, monkeypatch.setattr))
    assert result["correct"] is False
    value, limit = result["compared"]["logit_gap"]
    assert value > 10 * limit
