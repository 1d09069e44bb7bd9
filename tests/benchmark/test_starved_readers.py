"""The seven per-layer metrics that read the device's ledger, a request's
time in its slot and a decode chunk's wall time (``host_starved_pct``,
``loop_starved_ms_per_chunk``, ``join_starved_ms``,
``decode_chunks_ahead_pct``, ``decode_chunk_wall_mean_ms``,
``prefill_residence_mean_ms``, ``prefill_own_mean_ms``): each reader on a
hand-made ``Run`` of two snapshots, on a program that has none of the
counters (the parent commit: nothing is read, nothing raises), with 0 and
not nothing where only the numerator is missing, and on one traced toy
cell. The toy cell gets the readers from a manifest of this file's own,
``toy/BENCHMARK.starved.json``: the toy's manifest, which is not this file's
to edit, with the seven entries appended."""

import io
import json
import pathlib
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

TOY = REPO / "tests/benchmark/toy/BENCHMARK.starved.json"
SEED = 2**31 + 3737
NEW = ("host_starved_pct", "loop_starved_ms_per_chunk", "join_starved_ms",
       "decode_chunks_ahead_pct", "decode_chunk_wall_mean_ms",
       "prefill_residence_mean_ms", "prefill_own_mean_ms")
LEDGER = "tdt_engine_device_starved_seconds_total"


def test_manifest_is_the_toys_with_the_judged_entries_appended():
    mine, toy = json.loads(TOY.read_text()), json.loads(TOY.with_name("BENCHMARK.json").read_text())
    added = mine["per_layer"][len(toy["per_layer"]):]
    assert [m["name"] for m in added] == list(NEW)
    assert dict(mine, per_layer=mine["per_layer"][:-len(NEW)]) == toy
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    judged = {m["name"]: m for m in real["per_layer"]}
    # the toy's entries list no cells: its every cell that reports the moved metric reads them
    assert added == [{k: v for k, v in judged[n].items() if k != "workloads"} for n in NEW]
    # the judged entries list every cell there is (by name: where they stand
    # in the list is not held, a later PR appends after them)
    cells = [w["name"] for w in real["workloads"]]
    assert all(judged[n]["workloads"][:5] == cells[:5] for n in NEW)


def _readers():
    cell = harness.load_cell(TOY, "toy-dense.toy-doc", root=REPO)
    mods = {e["name"]: m for e, m in cell.per_layer}
    assert set(NEW) <= set(mods)
    return cell, mods


def test_span_lists_cover_the_self_time_readers_and_do_not_overlap():
    _, mods = _readers()
    loop, join = (set(mods[n].PHASES) for n in ("loop_starved_ms_per_chunk", "join_starved_ms"))
    both = harness.load_cell(REPO / "tests/benchmark/toy/BENCHMARK.loop_spans.json",
                             "toy-dense.toy-doc", root=REPO)
    self_time = {e["name"]: m for e, m in both.per_layer}
    assert not loop & join
    assert set(self_time["join_self_ms"].PHASES) <= join
    assert set(self_time["loop_self_ms_per_chunk"].PHASES) - join <= loop


def _snap(counters=None, hists=None, starved=None):
    return {
        "counters": {n: [{"labels": dict(labels), "value": v} for labels, v in series]
                     for n, series in (counters or {}).items()},
        "histograms": {n: [{"labels": {}, "sum": s, "count": c}]
                       for n, (s, c) in (hists or {}).items()},
        "digests": {"tdt_span_starved_seconds": [
            {"labels": {"phase": p}, "sum": s, "n": 1} for p, s in (starved or {}).items()]},
    }


def _run(cell, before, after, window_s=2.0):
    return harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=None, reqs=[], t_open=0.0,
                       t_close=window_s, t_drain_end=window_s, first_step=1, last_step=2,
                       telemetry=harness.Telemetry(before, after), lowered_in_window=0)


def test_readers_on_two_snapshots():
    cell, mods = _readers()
    one = lambda v: [((), v)]
    before = _snap(
        counters={LEDGER: [((("after", "prefill_chunk"),), 1.0), ((("after", "no_work"),), 5.0)],
                  "tdt_serving_decode_chunks_total": one(10), "tdt_serving_joins_total": one(4),
                  "tdt_serving_decode_chunks_ahead_total": one(6),
                  "tdt_serving_decode_sync_boundaries_total": [((("why", "finish"),), 4.0)]},
        hists={"tdt_serving_decode_chunk_seconds": (1.0, 10),
               "tdt_serving_prefill_residence_seconds": (2.0, 4),
               "tdt_serving_prefill_own_seconds": (1.0, 4)},
        starved={"tdt_serving_fetch": 0.5, "tdt_serving_join": 0.25})
    after = _snap(
        counters={LEDGER: [((("after", "prefill_chunk"),), 1.04), ((("after", "no_work"),), 6.0),
                           ((("after", "decode_land:finish"),), 0.06)],
                  "tdt_serving_decode_chunks_total": one(30), "tdt_serving_joins_total": one(8),
                  "tdt_serving_decode_chunks_ahead_total": one(11),
                  "tdt_serving_decode_sync_boundaries_total": [((("why", "finish"),), 19.0)]},
        hists={"tdt_serving_decode_chunk_seconds": (3.0, 30),
               "tdt_serving_prefill_residence_seconds": (4.4, 8),
               "tdt_serving_prefill_own_seconds": (1.6, 8)},
        starved={"tdt_serving_fetch": 0.52, "tdt_serving_join": 0.26,
                 "tdt_engine_dispatch": 0.03, "tdt_engine_sample_logits": 0.01,
                 "tdt_engine_paged_kbuf": 0.005, "tdt_serving_probe": 9.0})
    run = _run(cell, before, after)
    got = {n: mods[n].read(run) for n in NEW}
    # every ``after`` but ``no_work``, over the window's two seconds
    assert got["host_starved_pct"] == pytest.approx(100 * (0.04 + 0.06) / 2.0)
    # the loop's spans and the engine's beneath them, a chunk; a probe is no steady work
    assert got["loop_starved_ms_per_chunk"] == pytest.approx(1e3 * (0.02 + 0.03) / 20)
    # the join's three spans and what opens beneath them, a join
    assert got["join_starved_ms"] == pytest.approx(1e3 * (0.01 + 0.01 + 0.005) / 4)
    assert got["decode_chunks_ahead_pct"] == pytest.approx(100 * 5 / 20)
    assert got["decode_chunk_wall_mean_ms"] == pytest.approx(1e3 * 2.0 / 20)
    assert got["prefill_residence_mean_ms"] == pytest.approx(1e3 * 2.4 / 4)
    assert got["prefill_own_mean_ms"] == pytest.approx(1e3 * 0.6 / 4)


def test_a_program_without_the_counters_reads_nothing():
    """The parent commits under this PR's benchmark files: chunks and joins
    are counted, the ledger, the histograms and (before PR 36) the
    boundaries are not."""
    cell, mods = _readers()
    old = _snap(counters={"tdt_serving_decode_chunks_total": [((), 9)],
                          "tdt_serving_joins_total": [((), 3)]},
                hists={"tdt_serving_queue_wait_seconds": (1.0, 3)})
    run = _run(cell, _snap(), old)
    assert [mods[n].read(run) for n in NEW] == [None] * len(NEW)
    # PR 36's program counts its boundaries and has no ledger: one reader reads
    pr36 = _snap(counters={"tdt_serving_decode_chunks_total": [((), 9)],
                           "tdt_serving_decode_sync_boundaries_total": [((("why", "finish"),), 9)]})
    got = {n: mods[n].read(_run(cell, _snap(), pr36)) for n in NEW}
    assert got.pop("decode_chunks_ahead_pct") == 0.0 and set(got.values()) == {None}


def test_zero_and_not_nothing_where_only_the_numerator_is_missing():
    cell, mods = _readers()
    quiet = _snap(counters={LEDGER: [((("after", "no_work"),), 3.0)],
                            "tdt_serving_decode_chunks_total": [((), 12)],
                            "tdt_serving_joins_total": [((), 2)],
                            "tdt_serving_decode_sync_boundaries_total": [((("why", "finish"),), 12)]})
    run = _run(cell, _snap(), quiet)
    for name in NEW[:4]:
        assert mods[name].read(run) == 0.0, name
    # no chunk landed, no request joined in the window: nothing to divide by
    still = _snap(counters={LEDGER: [((("after", "no_work"),), 3.0)]})
    run = _run(cell, still, still)
    assert mods["host_starved_pct"].read(run) == 0.0
    assert [mods[n].read(run) for n in NEW[1:]] == [None] * 6


@pytest.fixture(scope="module")
def traced_toy():
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TOY, "toy-dense.toy-doc", SEED, 1.0, True, jax.devices()[:1],
                              out=out, err=err, root=REPO)
    return result, [json.loads(l) for l in out.getvalue().strip().splitlines()]


@pytest.mark.timeout(600)
def test_traced_toy_cell_reports_the_seven(traced_toy):
    result, lines = traced_toy
    phases = {l["phase"]: l for l in lines[:-1]}
    assert phases["window"]["lowerings_in_window"] == 0 and result["correct"] is True
    got = result["metrics"]
    assert set(NEW) <= set(got)
    assert [got[n]["unit"] for n in NEW] == ["%", "ms", "ms", "%", "ms", "ms", "ms"]
    # (no upper limit here: the CPU runs a program inside the call that
    # issues it, so the toy's device "starves" all of its one second, and
    # the snapshots' move holds the interval that was open as the window
    # opened and the whole of the step that was running as it closed)
    assert got["host_starved_pct"]["value"] > 0.0
    assert 0.0 <= got["decode_chunks_ahead_pct"]["value"] <= 100.0
    assert got["loop_starved_ms_per_chunk"]["value"] > 0 and got["join_starved_ms"]["value"] > 0
    # a request's own prefill lies within its time in the slot
    assert 0.0 < got["prefill_own_mean_ms"]["value"] <= got["prefill_residence_mean_ms"]["value"]
    assert got["decode_chunk_wall_mean_ms"]["value"] > 0


@pytest.mark.timeout(600)
def test_traced_toy_cell_starves_under_spans_the_readers_know(traced_toy):
    """Every span under which the toy's window starved the device is on one
    of the two readers' lists: a span the loop gains later has to be put on
    one, or its seconds are in ``host_starved_pct`` and in neither."""
    from triton_dist_tpu.runtime import telemetry

    _, mods = _readers()
    known = set(mods["loop_starved_ms_per_chunk"].PHASES) | set(mods["join_starved_ms"].PHASES)
    seen = {e["labels"]["phase"] for e in
            telemetry.snapshot()["digests"].get("tdt_span_starved_seconds", [])}
    assert seen and seen <= known, seen - known
