"""The harness end to end on the CPU, entered below the command's look for
a chip: toy cells (``test-dense`` widths, both mixes scaled down, one
device and four) that ``toy/BENCHMARK.json`` adds by files and entries
alone, with ``--trace 0`` and ``--trace 1``; the command itself, which
takes the contract's four arguments and fails with no TPU; and a run with
the timed path broken underneath, which has to come out as not correct."""

import io
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness, stats  # noqa: E402

TOY = REPO / "tests/benchmark/toy/BENCHMARK.json"
SEED = 2**31 + 12345  # the driver's seeds are large


def _run(cell, trace, n_dev=1, seconds=1.0, **kw):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TOY, cell, SEED, seconds, trace, jax.devices()[:n_dev],
                              out=out, err=err, root=REPO, **kw)
    lines = out.getvalue().strip().splitlines()
    return result, [json.loads(l) for l in lines], err.getvalue()


def _shape(result, lines, err, manifest_keys):
    assert lines[-1] == json.loads(json.dumps(result))
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert set(result["metrics"]) <= manifest_keys
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert result["device"]["platform"] == "cpu"
    assert err.strip().splitlines()[-1].startswith("compared (value, limit): ")
    phases = {l["phase"]: l for l in lines[:-1]}
    assert set(phases) - {"trace"} == {"setup", "window", "check"}
    assert all(l["platform"] == "cpu" for l in phases.values())
    assert phases["window"]["lowerings_in_window"] == 0
    assert not any(phases["window"]["zero_counters"].values())
    return phases


@pytest.mark.timeout(600)
def test_toy_cell_end_to_end_metrics_and_its_control():
    result, lines, err = _run("toy-dense.toy-chat", trace=False, control=True)
    m = json.loads(TOY.read_text())
    phases = _shape(result, lines, err, {e["name"] for e in m["end_to_end"]})
    assert set(result["metrics"]) == {"out_tokens_per_s", "ttft_mean_ms", "tpot_mean_ms",
                                      "setup_s"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == phases["window"]["requests_submitted"] >= 4
    assert "breakdown" not in result
    assert result["compared"]["logit_gap"][0] <= result["compared"]["logit_gap"][1]
    assert phases["check"]["first_choice_share"] == 1.0
    assert phases["setup"]["warm_up_requests"] == 3
    # The control's numbers go through the same decision, under a key of
    # their own. (At a toy run's 14 tokens bfloat16 picks the same tokens;
    # test_reference.py holds the control at the size that separates it.)
    assert result["control"]["precision"] == "bfloat16"
    assert set(result["control"]["compared"]) == set(result["compared"])


@pytest.mark.timeout(600)
def test_toy_cell_per_layer_metrics_from_a_trace():
    result, lines, err = _run("toy-dense.toy-doc", trace=True)
    m = json.loads(TOY.read_text())
    phases = _shape(result, lines, err, {e["name"] for e in m["per_layer"]})
    got = set(result["metrics"])
    # counters and the harness's own times read anywhere; no share of a
    # peak or of a roofline is reported off the chip (a reader that finds
    # nothing returns nothing), and the four-chip metrics are not this cell's
    assert {"queue_wait_mean_ms", "ttft_p90_ms", "tpot_p90_ms", "cache_scatter_ms_per_chunk",
            "device_idle_pct", "requests_finished", "setup_build_s", "setup_warm_up_s"} <= got
    assert not got & {"decode_roofline", "prefill_roofline", "serve_mfu", "hbm_peak_pct",
                      "paged_flash_decode_roofline", "flash_attention_roofline"}
    # set-up's two parts are the set-up line's own, and lie inside ``setup_s``
    build_s, warm_s = (result["metrics"][n]["value"] for n in ("setup_build_s", "setup_warm_up_s"))
    assert (build_s, warm_s) == (phases["setup"]["build_s"], phases["setup"]["warm_up_s"])
    assert 0 < build_s and 0 < warm_s and build_s + warm_s < phases["setup"]["setup_s"]
    # the readers get every operation of the trace; the result line its ten largest
    assert len(result["breakdown"]["device_ops"]) == 10
    # every loop iteration the profiler saw carries its mark in the trace
    assert phases["trace"]["steps_marked"] == phases["trace"]["steps_traced"] >= 1
    assert result["correct"] is True
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert 1 <= len(result["breakdown"]["device_ops"]) <= 10
    assert 1 <= len(result["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.timeout(900)
def test_toy_cell_on_four_devices():
    result, lines, err = _run("toy-tp4.toy-chat4", trace=True, n_dev=4, seconds=0.5)
    assert result["device"]["count"] == 4 and result["correct"] is True
    assert {"ttft_p90_ms", "queue_wait_mean_ms", "cache_scatter_ms_per_chunk"} <= set(
        result["metrics"])
    assert lines[0]["device_count"] == 4


@pytest.mark.timeout(600)
def test_arrivals_added_by_a_file_drive_the_same_loop():
    """``toy/loops/open.py`` and a mix that names it: an open loop, with
    nothing of the harness or the generator changed."""
    result, lines, err = _run("toy-dense.toy-open", trace=False, seconds=1.5)
    assert result["correct"] is True and result["failed"] == 0
    window = [l for l in lines if l.get("phase") == "window"][0]
    # some six arrivals a second, whatever the server finished meanwhile
    assert 3 <= window["requests_submitted"] <= 20
    assert set(result["metrics"]) == {"out_tokens_per_s", "ttft_mean_ms", "tpot_mean_ms",
                                      "setup_s"}


@pytest.mark.timeout(600)
def test_a_second_architecture_runs_from_the_toys_files_alone():
    """``toy-moe``: the program's expert model class, built, counted and
    compared by ``toy/{build,counts,reference}/qwen3_moe.py``, which the
    harness finds by the configuration's ``architecture``."""
    result, lines, err = _run("toy-moe.toy-short", trace=False)
    m = json.loads(TOY.read_text())
    phases = _shape(result, lines, err, {e["name"] for e in m["end_to_end"]})
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4 and phases["check"]["first_choice_share"] == 1.0
    assert phases["window"]["backend"] == "dist_ar"
    assert phases["setup"]["warm_up_requests"] == 3


def _alter_a_token(model, engine, server):
    """The decode chunk program's output, one token of one slot changed in
    every chunk."""
    inner = engine.decode_steps_paged

    def altered(*args, **kwargs):
        out, tok, paged, rem = inner(*args, **kwargs)
        out = np.array(out)
        out[:, 0] = (out[:, 0] + 1) % 256
        return out, tok, paged, rem

    engine.decode_steps_paged = altered


def _leave_the_pool_unchanged(model, engine, server):
    """The step that scatters a finished prefill into the block pool returns
    the pool as it got it: decode then attends to blocks nobody wrote."""
    engine.complete_paged_prefill = lambda paged, *args, **kwargs: paged


@pytest.mark.timeout(600)
@pytest.mark.parametrize("cell,tamper", [
    ("toy-dense.toy-chat", _alter_a_token), ("toy-dense.toy-chat", _leave_the_pool_unchanged),
    ("toy-moe.toy-short", _alter_a_token)], ids=["dense-token", "dense-pool", "moe-token"])
def test_the_timed_path_broken_underneath_is_not_correct(cell, tamper):
    """After warm-up and under the window; the rest of the run as it is."""
    result, lines, err = _run(cell, trace=False, tamper=tamper)
    assert result["correct"] is False
    gap, limit = result["compared"]["logit_gap"]
    assert gap > 100 * limit
    assert result["compared"]["bad_requests"] == [0, 0]
    assert "logit_gap" in err.strip().splitlines()[-1]


def _leave_a_chips_part_out(model, engine, server):
    """The exchange between chips without one chip's part: the last chip's
    rows of the attention output projection are zero, so what it adds to
    every all-reduce is missing. Shapes and shardings stay, nothing recompiles."""
    import dataclasses

    wo = model.params.wo
    rows = wo.shape[1] // 4
    cut = jax.device_put(wo.at[:, -rows:, :].set(0), wo.sharding)
    model.params = dataclasses.replace(model.params, wo=cut)


@pytest.mark.timeout(900)
def test_the_exchange_between_chips_broken_is_not_correct():
    result, lines, err = _run("toy-tp4.toy-chat4", trace=False, n_dev=4, seconds=0.5,
                              tamper=_leave_a_chips_part_out)
    assert result["correct"] is False
    gap, limit = result["compared"]["logit_gap"]
    assert gap > 100 * limit and result["compared"]["bad_requests"] == [0, 0]
    assert lines[1]["lowerings_in_window"] == 0


def test_a_request_that_never_finishes_is_not_correct():
    cell = harness.load_cell(TOY, "toy-dense.toy-chat", root=REPO)
    r = stats.ReqLog(0, 5, 3, 1.0, prompt=[1] * 5)
    r.token_t, r.token_step, r.tokens = [1.5], [1], [7]
    run = harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=None, reqs=[r], t_open=0.0,
                      t_close=2.0, t_drain_end=3.0, first_step=1, last_step=4,
                      telemetry=harness.Telemetry({}, {}), lowered_in_window=0)
    ok, compared = harness.decide(cell, run, {"logit_gap": 0.0, "tokens_compared": 99})
    assert ok is False and compared["bad_requests"] == [1, 0]
    ok, compared = harness.decide(cell, run, {})
    assert ok is False and compared["logit_gap"][0] is None


def _command(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(REPO / "benchmark/run.py"), *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)


def test_the_command_fails_with_no_tpu_and_prints_no_metric():
    cell = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    p = _command("--workload", cell, "--seed", str(SEED), "--seconds", "1", "--trace", "0")
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("extra", [["--devices", "cpu"], ["--control", "int8"], []])
def test_the_command_takes_the_contracts_four_arguments_only(extra):
    args = ["--workload", "x", "--seed", "1", "--seconds", "1"] + extra
    p = _command(*args)  # --trace is missing, or an argument is unknown
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_seeds_past_32_signed_bits_stay_distinct():
    keys = [tuple(harness.seed_key(s)) for s in (5, 2**31 + 5, 2**32 + 5)]
    assert len(set(keys)) == 3
    assert keys[0] == (0, 5) and keys[2] == (1, 5)
    assert tuple(np.asarray(jax.random.PRNGKey(5))) == keys[0]


def test_counters_are_read_as_a_difference():
    snap = lambda c, s, n, d: {
        "counters": {"chunks": [{"labels": {}, "value": c}]},
        "histograms": {"wait": [{"labels": {}, "sum": s, "count": n}]},
        "digests": {"phase": [{"labels": {"phase": "a", "backend": "dist"}, "sum": d, "n": 4},
                              {"labels": {"phase": "b", "backend": "dist"}, "sum": 9.0, "n": 1}]},
    }
    t = harness.Telemetry(snap(10, 1.0, 4, 2.0), snap(25, 4.0, 10, 3.5))
    assert t.counter("chunks") == 15
    assert t.histogram("wait") == (3.0, 6)
    assert t.digest("phase", phase="a") == (1.5, 0)
    assert t.digest("phase", phase="b") == (0.0, 0)
    assert t.counter("absent") == 0.0


def test_window_steps_from_the_token_log():
    cell = harness.load_cell(TOY, "toy-dense.toy-chat", root=REPO)
    a = stats.ReqLog(0, 10, 5, 0.0)
    a.token_step = [2, 3, 3, 4, 9]
    b = stats.ReqLog(1, 20, 2, 0.0)
    b.token_step = [3, 4]
    run = harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=None, reqs=[a, b], t_open=0.0,
                      t_close=1.0, t_drain_end=1.0, first_step=2, last_step=4,
                      telemetry=harness.Telemetry({}, {}), lowered_in_window=0)
    steps = run.window_steps()
    assert set(steps) == {2, 3, 4}
    assert steps[2]["prefill"] == [10] and not steps[2]["decode"]
    assert steps[3]["prefill"] == [20]
    assert list(steps[3]["decode"].values()) == [(2, 11)]  # rows attend 11 and 12
    assert sorted(steps[4]["decode"].values()) == [(1, 13), (1, 21)]
    # the traced steps are read the same way, where the trace marks each
    assert run.traced_steps() is None
    run.traced_first_step, run.traced_last_step = 4, 9
    run.trace = {"steps": [[0, 1]] * 5}
    assert run.traced_steps() is None  # six iterations, five marks
    run.trace = {"steps": [[0, 1]] * 6}
    assert set(run.traced_steps()) == {4, 9}
