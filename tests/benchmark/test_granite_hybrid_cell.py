"""The real manifest's ``granite_hybrid`` files (build, counts, reference, the
three new readers) driven through the harness on the CPU by a toy twin:
``toy/BENCHMARK.granite.json`` is the toy's manifest, which is not this
file's to edit, with one configuration, one cell and five metrics appended:
the two chunked-prefill metrics the real cell lists, and the three readers of
the Mamba-2 kernels and counters (:data:`NEW`), which stand beside the toy's
readers under ``toy/layer_metrics/`` and which the real manifest does not
list yet. The twin's configuration, mix and limits are files beside the
toy's. So the twin, like the real cell, is files and entries alone. The timed path broken underneath (:data:`TAMPERS`: the state not
carried between chunks, ``dt`` forced to 0, the ``D`` skip dropped, the gate
dropped) has to come out as not correct; a one-off process on the chip
imports the same four and plants them under the real cell through
``harness.run_cell(..., tamper=...)``."""

import io
import json
import os
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

TWIN = REPO / "tests/benchmark/toy/BENCHMARK.granite.json"
CELL = "toy-granite.toy-rag"
REAL_CELL = "granite-4.0-h-micro.rag"
SEED = 2**31 + 4242  # seeds past 2**31, as a benchmark run may be given
NEW = ("ssd_chunk_roofline", "ssd_step_roofline", "ssd_state_read_pct")
# a JSON-lines catalog of published model configurations, checked when given
CATALOG = pathlib.Path(os.environ.get("TDT_MODEL_CATALOG", ""))


def _run(trace, **kw):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TWIN, CELL, SEED, 2.5, trace, jax.devices()[:1],
                              out=out, err=err, root=REPO, **kw)
    return result, {l["phase"]: l for l in map(json.loads, out.getvalue().splitlines()[:-1])}


def test_twin_is_the_toys_manifest_with_entries_appended():
    mine = json.loads(TWIN.read_text())
    toy = json.loads(TWIN.with_name("BENCHMARK.json").read_text())
    listed = ("prefill_chunks_per_join", "prefill_chunk_roofline")
    names = NEW + listed
    assert [m["name"] for m in mine["per_layer"][len(toy["per_layer"]):]] == list(names)
    assert dict(mine, configs=mine["configs"][:-1], workloads=mine["workloads"][:-1],
                per_layer=mine["per_layer"][:-len(names)]) == toy
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    judged = {m["name"]: m for m in real["per_layer"]}
    assert mine["per_layer"][-len(listed):] == [
        {k: v for k, v in judged[n].items() if k != "workloads"} for n in listed]
    assert all(REAL_CELL in judged[n]["workloads"] for n in listed)
    # the new readers' entries: the layer names the real manifest already has
    layers = {m["layer"] for m in real["per_layer"]}
    assert all(m["moves"] == "out_tokens_per_s" and m["layer"] in layers
               for m in mine["per_layer"][-len(names):-len(listed)])
    assert real["workloads"][-1]["name"] == REAL_CELL and real["workloads"][-1]["chips"] == 1
    # the architecture's files are the real manifest's own, found by name
    cell = harness.load_cell(TWIN, CELL, root=REPO)
    for kind, mod in (("build", cell.build), ("reference", cell.reference),
                      ("counts", cell.counts.architecture)):
        assert mod.__file__ == str(REPO / f"benchmark/{kind}/granite_hybrid.py")


def test_real_configuration_is_the_published_one_uncut():
    """Every key of the catalog's ``config`` unchanged, nothing reduced, the
    program's config at the published widths, the cell's mix,
    and the bytes the file states reckoned again from the model."""
    real = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg, mix = real.cfg, real.mix
    if CATALOG.is_file():
        rows = [json.loads(l) for l in CATALOG.read_text().splitlines()]
        row = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == set()
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 40
    assert cfg["serving"] == {"backend": "dist", "chips": 1, "tp": 1, "mesh_axis": "tp",
                              "slots": 32, "max_len": 8832, "chunk": 8, "block_size": 16,
                              "prefill_chunk": 2048}
    assert (mix["loop"], mix["clients"], mix["deck"], mix["hand"], mix["check_requests"]) == (
        "closed_one_deal", 32, 15, 3, 4)
    assert mix["prompt_len"] == {"values": [2048, 4096, 8192], "weights": [0.4, 0.4, 0.2]}
    assert mix["max_new"]["values"] == [150, 310, 630]
    assert max(mix["prompt_len"]["values"]) + max(mix["max_new"]["values"]) <= 8832
    names = {e["name"] for e, _ in real.per_layer}
    assert names >= {"decode_roofline", "serve_mfu", "prefill_chunk_roofline",
                                "prefill_chunks_per_join", "hbm_peak_pct", "device_idle_pct"}
    assert not names & {"ssm_scan_roofline", "swa_attended_pct", "decode_rows_per_step",
                        "lightning_chunk_roofline", "prefill_roofline"}
    mc = real.build.model_config(cfg)
    assert mc.layers_of("full") == (5, 15, 25, 35) and len(mc.layers_of("mamba2")) == 36
    assert (mc.hidden_size, mc.intermediate_size, mc.num_q_heads, mc.num_kv_heads,
            mc.head_dim) == (2048, 8192, 32, 8, 64)
    assert (mc.mamba_heads, mc.mamba_head_dim, mc.mamba_state, mc.mamba_conv,
            mc.mamba_chunk) == (64, 64, 128, 4, 256)
    assert (mc.residual_scale, mc.logit_scale, mc.attention_scale, mc.scale_emb) == (
        0.22, 0.125, 0.015625, 12.0)
    assert mc.tie_embeddings and mc.vocab_size == 100352
    from triton_dist_tpu.models import sparse_linear as S

    fake = type("M", (), {"config": mc})
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    elems = mc.vocab_size * mc.hidden_size + mc.hidden_size
    f32 = 0
    for layer in range(mc.num_layers):
        for name, n in ([(n, int(np.prod(s))) for n, s in S.layer_tensors(mc, layer)]
                        + S.layer_ones(mc, layer)):
            elems += n
            f32 += n if name in S.SSD_F32 else 0
    sv, stated = cfg["serving"], cfg["bytes"]
    per_slot = nbytes(jax.eval_shape(lambda: S.SparseLinearLLM.slot_state(fake, 1)))
    blocks = sv["slots"] * -(-sv["max_len"] // sv["block_size"]) + 1
    row = sum(r.layers * r.heads * r.width for r in S.SparseLinearLLM.cache_rows(fake)) * 2
    assert (stated["parameters"], stated["weights"]) == (elems, 2 * elems + 2 * f32)
    assert 3.19e9 < elems < 3.2e9 and f32 == 36 * 3 * 64
    assert (stated["slot_state_per_slot"], stated["slot_state"]) == (
        per_slot, sv["slots"] * per_slot)
    assert per_slot == 36 * (128 * 4096 * 4 + 3 * 4352 * 2)
    assert (stated["pool_per_token"], stated["pool"]) == (row, blocks * sv["block_size"] * row)
    assert row == 4 * 2 * 8 * 64 * 2
    assert stated["resident"] == stated["weights"] + sv["slots"] * per_slot + stated["pool"]
    assert 0.6 < stated["resident"] / 16e9 == pytest.approx(stated["share_of_16e9"], abs=1e-4)


def test_counts_are_the_least_the_architecture_needs():
    real = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg, counts = real.cfg, real.counts
    # a decode step at 32 rows of 5000: every weight once (the head the
    # embedding's transpose), each row's 36 states and tails in and out, its
    # K/V read on 4 layers and its own rows written
    step = counts.decode_steps(cfg, 1, [5000] * 32)
    state = 36 * 2 * (128 * 4096 * 4 + 3 * 4352 * 2)
    kv = 4 * 5001 * 2048
    assert step["bytes"] == pytest.approx(
        cfg["bytes"]["weights"] + 32 * (state + kv), rel=0.001)
    assert 0.38 < 32 * 36 * 2 * 128 * 4096 * 4 / step["bytes"] < 0.40  # the state's share
    assert step["bytes"] / 819e9 == pytest.approx(15.4e-3, rel=0.02)
    # a row's matrix work is twice the parameters less the embedding's lookup
    assert step["flops"] / 32 == pytest.approx(
        2 * cfg["bytes"]["parameters"] + 4 * 32 * 64 * 5000 * 4, rel=0.02)
    chunk, whole = counts.prefill_chunk(cfg, 2048), counts.prefill(cfg, 2048)
    assert chunk["flops"] < whole["flops"] < 1.001 * chunk["flops"]  # the last row's head
    assert chunk["flops"] / 197e12 == pytest.approx(64.0e-3, rel=0.01)
    ssd = counts.ssd_chunk(cfg, 2048)
    assert ssd["flops"] == 8 * (2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64
                                                           + 4 * 256 * 128 * 64))
    assert ssd["bytes"] == 2048 * (2 * 4096 * 2 + 4 * 64 + 2 * 128 * 2) + 2 * 128 * 4096 * 4
    dec = counts.ssd_step(cfg, 32)
    assert dec == {"flops": 4.0 * 32 * 128 * 4096,
                   "bytes": 32.0 * (2 * 128 * 4096 * 4 + 2 * 4096 * 2 + 4 * 64 + 2 * 128 * 2)}


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
    """One prefill chunk (36 ``ssd_chunk`` calls) and one decode chunk of two
    steps of two requests, one of which ends after the first step (36
    ``ssd_step`` calls a step): each reader finds its kernel by name and
    lays its seconds against the counts; on a trace without them (the
    parent's, or another model's) nothing is read and nothing raises."""
    cell = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    mods = {e["name"]: m for e, m in harness.load_cell(TWIN, CELL, root=REPO).per_layer}
    call = "%{}.{} = f32[8]{{0}} custom-call(%p.1), custom_call_target=\"tpu_custom_call\""
    ops, t = [], 1_000_000
    for name, n, dur in (("ssd_chunk", 36, 200_000), ("ssd_step", 72, 150_000)):
        for i in range(n):
            ops.append((call.format(name, i), t, dur))
            t += dur + 1000
    programs = [("jit_chunk_fn(7)", 900_000, 20_000_000),
                ("jit_decode_chunk_paged(5)", 30_000_000, 600_000)]
    reqs = [stats.ReqLog(0, 4000, 150, 0.0, token_step=[0, 1, 1], tokens=[1, 2, 3]),
            stats.ReqLog(1, 2000, 150, 0.0, token_step=[0, 1], tokens=[1, 2])]
    run = _traced(cell, ops, programs, reqs)
    counts, cfg = cell.counts, cell.cfg
    least = lambda w: counts.least_seconds(w, run.peaks)["seconds"]
    assert mods["ssd_chunk_roofline"].read(run) == pytest.approx(
        100 * least(counts.ssd_chunk(cfg, 2048)) / 200e-6)
    want = 36 * (least(counts.ssd_step(cfg, 2)) + least(counts.ssd_step(cfg, 1)))
    assert mods["ssd_step_roofline"].read(run) == pytest.approx(100 * want / (72 * 150e-6))
    assert all(0 < mods[n].read(run) < 100 for n in NEW[:2])
    bare = _traced(cell, [("%fusion.9 = bf16[4] fusion(%p)", 1_000_000, 400_000)], programs, reqs)
    assert [mods[n].read(bare) for n in NEW] == [None] * 3
    run.trace = None
    assert [mods[n].read(run) for n in NEW] == [None] * 3


@pytest.mark.timeout(600)
def test_twin_cell_is_correct_and_reads_the_new_metrics():
    result, phases = _run(trace=True)
    assert result["correct"] is True and result["failed"] == 0
    assert phases["window"]["lowerings_in_window"] == 0
    assert not any(phases["window"]["zero_counters"].values())
    got = result["metrics"]
    # counters read anywhere; no share of a roofline is reported off the chip
    assert "ssd_state_read_pct" in got and not {"ssd_chunk_roofline", "ssd_step_roofline"} & set(got)
    assert got["ssd_state_read_pct"]["value"] == 100.0  # idle slots are not swept


# ------------------------------------------------------------ planted faults


def state_not_carried(model, engine, server):
    """Every prefill chunk starts from a zero state and conv tail."""
    from triton_dist_tpu.layers import sparse_linear as sl

    chunk = sl.mamba2_chunk
    sl.mamba2_chunk = lambda lp, u, tail, s, *a: chunk(lp, u, jnp.zeros_like(tail),
                                                       jnp.zeros_like(s), *a)
    engine.rebuild(engine.backend)


def dt_zero(model, engine, server):
    """``dt`` forced to 0: no decay and nothing added, in chunks and steps."""
    from triton_dist_tpu.kernels import lightning_attn as la

    chunk, step = la.ssd_chunk, la.ssd_step
    la.ssd_chunk = lambda x, dt, *a, **k: chunk(x, jnp.zeros_like(dt), *a, **k)
    la.ssd_step = lambda x, dt, *a: step(x, jnp.zeros_like(dt), *a)
    engine.rebuild(engine.backend)


def d_skip_dropped(model, engine, server):
    """The ``D x`` skip left out of every Mamba-2 layer."""
    layers = [dict(lp) for lp in model.params["layers"]]
    for lp in layers:
        if "d" in lp:
            lp["d"] = jnp.zeros_like(lp["d"])
    model.params = {**model.params, "layers": layers}


def gate_dropped(model, engine, server):
    """``y`` normed and projected without ``silu(z)``."""
    from triton_dist_tpu.layers import sparse_linear as sl

    out = sl._mamba2_out
    sl._mamba2_out = lambda lp, y, z, eps: out(lp, y, jnp.full_like(z, 30.0), eps)
    engine.rebuild(engine.backend)


TAMPERS = {"state_not_carried": state_not_carried, "dt_zero": dt_zero,
           "d_skip_dropped": d_skip_dropped, "gate_dropped": gate_dropped}


@pytest.mark.slow
@pytest.mark.timeout(600)
@pytest.mark.parametrize("name", list(TAMPERS))
def test_twin_cell_broken_underneath_is_not_correct(name, monkeypatch):
    from triton_dist_tpu.kernels import lightning_attn as la
    from triton_dist_tpu.layers import sparse_linear as sl

    for mod, attr in ((sl, "mamba2_chunk"), (sl, "_mamba2_out"), (la, "ssd_chunk"),
                      (la, "ssd_step")):
        monkeypatch.setattr(mod, attr, getattr(mod, attr))
    result, _ = _run(trace=False, tamper=TAMPERS[name])
    assert result["correct"] is False
    value, limit = result["compared"]["logit_gap"]
    assert value > 10 * limit
