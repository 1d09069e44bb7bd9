"""``joins_ahead_pct``, the share of the window's joins that went ahead of
the landing of the chunk that freed their slot: its reader on hand-made
pairs of snapshots. A program that counts no joins ahead (one from before
the join ahead) reads nothing and nothing raises; one that counts them and
had none ahead reads 0; otherwise the share of the joins between the two
snapshots. The reader stands beside the toy's readers, and not under
``benchmark/``, until the real manifest can list it: every cell reports the
end-to-end metric it moves, so its entry would name all of them."""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

READER = harness._module(REPO / "tests/benchmark/toy/layer_metrics/joins_ahead_pct.py")
JOINS, AHEAD = "tdt_serving_joins_total", "tdt_serving_joins_ahead_total"


def _snap(**counters):
    return {"counters": {n: [{"labels": {}, "value": v}] for n, v in counters.items()}}


def _read(before, after):
    run = harness.Run(cell=None, seed=1, chips=1, tp=1, peaks=None, reqs=[], t_open=0.0,
                      t_close=2.0, t_drain_end=2.0, first_step=1, last_step=2,
                      telemetry=harness.Telemetry(before, after), lowered_in_window=0)
    return READER.read(run)


@pytest.mark.parametrize("before,after,want", [
    # the parent: joins counted, none ahead counted
    (_snap(**{JOINS: 3.0}), _snap(**{JOINS: 9.0}), None),
    # counted, and none went ahead in the window (nor before it)
    (_snap(**{JOINS: 3.0, AHEAD: 0.0}), _snap(**{JOINS: 9.0, AHEAD: 0.0}), 0.0),
    # the window's own: 6 joins, 3 of them ahead (2 went ahead before it)
    (_snap(**{JOINS: 4.0, AHEAD: 2.0}), _snap(**{JOINS: 10.0, AHEAD: 5.0}), 50.0),
    # no join in the window: nothing to divide by
    (_snap(**{JOINS: 4.0, AHEAD: 2.0}), _snap(**{JOINS: 4.0, AHEAD: 2.0}), None),
], ids=["no-counter", "none-ahead", "share", "no-join"])
def test_reader_reads_the_windows_share(before, after, want):
    got = _read(before, after)
    assert got == (None if want is None else pytest.approx(want))
    assert (READER.LAYER, READER.UNIT, READER.SOURCE, READER.MOVES) == (
        "server loop (serving/server.py)", "%", "program_counter", "out_tokens_per_s")


def test_every_cell_reports_what_it_moves():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = harness.load_cell(REPO / "BENCHMARK.json", w["name"])
        assert READER.MOVES in {e["name"] for e, _ in cell.end_to_end}, w["name"]
