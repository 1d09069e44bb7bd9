"""The three per-layer metrics that read the program's own spans
(``loop_self_ms_per_chunk``, ``join_self_ms``, ``loop_exposed_ms_per_chunk``):
each reader on a hand-made ``Run`` (two snapshots; a ``trace`` dict with
``idle_gaps`` and ``programs``), on a program that has no such span (the
parent commit: nothing is read, nothing raises), and on one traced toy cell,
where the idle labels have to include the program's spans and every loop
iteration still carries the harness's mark. The toy cell gets the readers
from a manifest of this file's own, ``toy/BENCHMARK.loop_spans.json``: the
toy's manifest, which is not this file's to edit, with the three entries
appended."""

import io
import json
import pathlib
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

TOY = REPO / "tests/benchmark/toy/BENCHMARK.loop_spans.json"
SEED = 2**31 + 2424
NEW = ("loop_self_ms_per_chunk", "join_self_ms", "loop_exposed_ms_per_chunk")


def test_manifest_is_the_toys_with_the_judged_entries_appended():
    mine, toy = json.loads(TOY.read_text()), json.loads(TOY.with_name("BENCHMARK.json").read_text())
    added = mine["per_layer"][len(toy["per_layer"]):]
    assert [m["name"] for m in added] == list(NEW)
    assert dict(mine, per_layer=mine["per_layer"][:-len(NEW)]) == toy
    judged = {m["name"]: m for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]}
    # the toy's entries list no cells: its every cell that reports the moved metric reads them
    assert added == [{k: v for k, v in judged[n].items() if k != "workloads"} for n in NEW]


def _readers():
    cell = harness.load_cell(TOY, "toy-dense.toy-doc", root=REPO)
    mods = {e["name"]: m for e, m in cell.per_layer}
    assert set(NEW) <= set(mods)
    return cell, mods


def _snap(chunks, joins, **self_s):
    return {
        "counters": {"tdt_serving_decode_chunks_total": [{"labels": {}, "value": chunks}],
                     "tdt_serving_joins_total": [{"labels": {}, "value": joins}]},
        "histograms": {},
        "digests": {"tdt_span_self_seconds": [
            {"labels": {"phase": p}, "sum": s, "n": 1} for p, s in self_s.items()]},
    }


def _run(cell, before, after, trace=None):
    return harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=None, reqs=[], t_open=0.0,
                       t_close=1.0, t_drain_end=1.0, first_step=1, last_step=2,
                       telemetry=harness.Telemetry(before, after), lowered_in_window=0,
                       trace=trace)


def test_self_time_readers_on_two_snapshots():
    cell, mods = _readers()
    before = _snap(10, 4, tdt_serving_step=1.0, tdt_serving_join=0.5,
                   tdt_serving_prefill_arm=0.25, tdt_engine_dispatch=7.0)
    after = _snap(20, 8, tdt_serving_step=1.02, tdt_serving_join=0.52,
                  tdt_serving_prefill_arm=0.26, tdt_serving_prefill_complete=0.03,
                  tdt_serving_emit=0.05, tdt_engine_dispatch=9.0,
                  tdt_scheduler_join_free_slots=3.0, tdt_serving_recovery=4.0)
    run = _run(cell, before, after)
    # the loop's own spans, all phases, a chunk: the engine's and the
    # scheduler's self time is theirs, a recovery is not the loop's steady
    # work, and what was there before is not counted
    assert mods["loop_self_ms_per_chunk"].read(run) == pytest.approx(
        1e3 * (0.02 + 0.02 + 0.01 + 0.03 + 0.05) / 10)
    # join, arming and completion, a join
    assert mods["join_self_ms"].read(run) == pytest.approx(1e3 * (0.02 + 0.01 + 0.03) / 4)
    assert mods["loop_exposed_ms_per_chunk"].read(run) is None  # no trace


def test_exposed_reader_on_idle_gaps_and_programs():
    cell, mods = _readers()
    trace = {
        "idle_gaps": [["tdt_scheduler_join_free_slots", 0.2], ["np.asarray(jax.Array)", 0.05],
                      ["tdt_serving_emit", 0.03], ["server.step", 0.02],
                      ["tdt_engine_cache_scatter", 0.01], ["outside server.step", 0.004]],
        "programs": [("jit_decode_chunk", 0.0, 0.18), ("jit_paged_gather", 0.2, 0.01),
                     ("jit_decode_chunk", 0.3, 0.18), ("jit_chunk_fn", 0.5, 0.1)],
    }
    run = _run(cell, {}, {}, trace)
    assert mods["loop_exposed_ms_per_chunk"].read(run) == pytest.approx(
        1e3 * (0.2 + 0.03 + 0.01) / 2)
    trace["programs"] = []  # a rehearsal off the chip has no program line
    assert mods["loop_exposed_ms_per_chunk"].read(run) is None


def test_a_program_without_the_spans_reads_nothing():
    """The parent commit under this PR's benchmark files: no digest, no join
    counter, no span of the program's among the idle labels."""
    cell, mods = _readers()
    old = {"counters": {"tdt_serving_decode_chunks_total": [{"labels": {}, "value": 9}]},
           "histograms": {}, "digests": {}}
    trace = {"idle_gaps": [["server.step", 0.05], ["np.asarray(jax.Array)", 0.03]],
             "programs": [("jit_decode_chunk", 0.0, 0.18)]}
    run = _run(cell, {"counters": {}, "histograms": {}, "digests": {}}, old, trace)
    assert [mods[n].read(run) for n in NEW] == [None, None, None]


@pytest.fixture(scope="module")
def traced_toy():
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TOY, "toy-dense.toy-doc", SEED, 1.0, True, jax.devices()[:1],
                              out=out, err=err, root=REPO)
    return result, [json.loads(l) for l in out.getvalue().strip().splitlines()]


@pytest.mark.timeout(600)
def test_traced_toy_cell_names_its_idle_gaps(traced_toy):
    result, lines = traced_toy
    phases = {l["phase"]: l for l in lines[:-1]}
    assert phases["trace"]["steps_marked"] == phases["trace"]["steps_traced"] >= 1
    assert phases["window"]["lowerings_in_window"] == 0 and result["correct"] is True
    labels = {label: s for label, s in result["breakdown"]["idle_gaps"]}
    mine = {l for l in labels if l.startswith(("tdt_serving_", "tdt_engine_", "tdt_scheduler_"))}
    assert mine, labels
    # the harness's mark round the whole call no longer holds the idle time
    assert labels.get("server.step", 0.0) <= 0.1 * sum(labels.values())


@pytest.mark.timeout(600)
def test_traced_toy_cell_reports_the_self_time_metrics(traced_toy):
    result, _ = traced_toy
    got = result["metrics"]
    assert got["loop_self_ms_per_chunk"]["value"] > 0 and got["join_self_ms"]["value"] > 0
    assert got["join_self_ms"]["unit"] == "ms"
    # a loop's own Python cannot take longer than the loop's iterations
    if "chunk_period_p50_ms" in got:  # a slow machine's window may hold one burst
        assert got["loop_self_ms_per_chunk"]["value"] < got["chunk_period_p50_ms"]["value"]
    # off the chip the trace has no program line: the device metric is silent
    assert "loop_exposed_ms_per_chunk" not in got
