"""``BENCHMARK.json`` against the contract's shape and against the files it
names: every cell resolves, every per-layer metric has its reader and the
reader declares what the manifest says, names and units hold only the
allowed characters."""

import json
import pathlib
import re
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

MANIFESTS = [REPO / "BENCHMARK.json", REPO / "tests/benchmark/toy/BENCHMARK.json"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def _load(path):
    return json.loads(path.read_text())


def _cells(path):
    return [w["name"] for w in _load(path)["workloads"]]


ALL_CELLS = [(m, c) for m in MANIFESTS for c in _cells(m)]


@pytest.mark.parametrize("path", MANIFESTS, ids=lambda p: p.parent.name or "root")
def test_shape_names_and_units(path):
    m = _load(path)
    assert set(m) == KEYS
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert 1 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24
    names = ([c["name"] for c in m["configs"]] + [w["name"] for w in m["workloads"]]
             + [w["traffic"] for w in m["workloads"]]
             + [e["name"] for e in m["end_to_end"] + m["per_layer"]]
             + [k for c in m["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    metrics = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(m["workloads"])
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in e["layer"] and 1 <= len(e["layer"]) <= 200
        if e["name"].endswith("_roofline") or "mfu" in re.split(r"[_.\-]", e["name"]):
            assert e["unit"] == "%"
    assert "setup_s" in [e["name"] for e in m["end_to_end"]]
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}


def test_real_manifest_paths_hold_the_benchmark_alone():
    m = _load(REPO / "BENCHMARK.json")
    assert m["command"] == ["python3", "benchmark/run.py"]
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert c["source"].startswith("https://")
        cfg = _load(REPO / c["file"])
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:  # never a width
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head_dim|_size)$", key)
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("path,cell", ALL_CELLS, ids=[c for _, c in ALL_CELLS])
def test_cell_resolves_and_metrics_agree(path, cell):
    c = harness.load_cell(path, cell, root=REPO)
    m = c.manifest
    reported = {e["name"] for e, _ in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    for entry, mod in c.end_to_end:
        assert (mod.UNIT, mod.SOURCE) == (entry["unit"], entry["source"])
        assert callable(mod.read)
    assert callable(c.loop.source)
    assert c.per_layer, "every cell reports at least one per-layer metric"
    assert c.cfg["serving"]["chips"] == c.chips
    assert hasattr(c.reference, "logits_at") and hasattr(c.reference, "make_weights")
    assert callable(c.build.build) and callable(c.build.release)
    for fn in ("prefill", "decode_steps", "per_chip", "least_seconds"):
        assert callable(getattr(c.counts, fn))
    held = [v["limit"] for v in c.limits.values() if isinstance(v, dict) and "limit" in v]
    assert held and all(lim > 0 for lim in held)
    for entry, mod in c.per_layer:
        assert entry["moves"] in reported
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert callable(mod.read)
    # A metric with no ``workloads`` key has to be reported wherever the
    # end-to-end metric it moves is: load_cell must have kept it.
    kept = {e["name"] for e, _ in c.per_layer}
    for e in m["per_layer"]:
        if "workloads" not in e and e["moves"] in reported:
            assert e["name"] in kept


def test_layers_are_spelt_alike():
    m = _load(REPO / "BENCHMARK.json")
    layers = {e["layer"] for e in m["per_layer"]}
    folded = {re.sub(r"\W+", "", l).lower() for l in layers}
    assert len(layers) == len(folded)


def test_toy_adds_by_files_and_entries_alone():
    """The toy manifest reuses the harness, the generator and the readers
    unchanged, and adds configurations, mixes, a per-layer metric, a kind of
    arrivals and an architecture (its build, counts and reference) as files
    under its own path."""
    toy = _load(MANIFESTS[1])
    assert toy["paths"][0] == "benchmark"
    own = REPO / toy["paths"][1]
    for c in toy["configs"]:
        assert (REPO / c["file"]).is_relative_to(own)
    assert (own / "layer_metrics/requests_finished.py").is_file()
    assert not (REPO / "benchmark/layer_metrics/requests_finished.py").exists()
    assert (own / "loops/open.py").is_file()
    assert not (REPO / "benchmark/loops/open.py").exists()
    cell = harness.load_cell(MANIFESTS[1], "toy-dense.toy-open", root=REPO)
    assert cell.loop.__file__ == str(own / "loops/open.py")
    # the second architecture: nothing of it exists under ``benchmark/``
    moe = harness.load_cell(MANIFESTS[1], "toy-moe.toy-short", root=REPO)
    arch = moe.cfg["architecture"]
    assert arch != cell.cfg["architecture"]
    assert not list((REPO / "benchmark").rglob(f"*{arch}*"))
    for kind, mod in (("build", moe.build), ("reference", moe.reference),
                      ("counts", moe.counts.architecture)):
        assert mod.__file__ == str(own / f"{kind}/{arch}.py")
    # and the first one's are the benchmark's own
    for kind, mod in (("build", cell.build), ("reference", cell.reference),
                      ("counts", cell.counts.architecture)):
        assert mod.__file__ == str(REPO / f"benchmark/{kind}/qwen3_dense.py")


def test_every_file_of_the_benchmark_is_one_a_cell_uses():
    """Nothing under ``benchmark/`` waits for a cell that is not there: each
    reader, mix, configuration, limits file, loop and architecture's file
    (reference, build, counts) is named by ``BENCHMARK.json`` or by a file it
    names."""
    m = _load(REPO / "BENCHMARK.json")
    cells = [harness.load_cell(REPO / "BENCHMARK.json", w["name"]) for w in m["workloads"]]
    used = {
        "layer_metrics": {e["name"] for e in m["per_layer"]},
        "end_to_end": {e["name"] for e in m["end_to_end"]},
        "traffic": {w["traffic"] for w in m["workloads"]},
        "limits": {w["name"] for w in m["workloads"]},
        "configs": {c["name"] for c in m["configs"]},
        "loops": {c.mix["loop"] for c in cells},
        "reference": {c.cfg["architecture"] for c in cells},
        "build": {c.cfg["architecture"] for c in cells},
        "counts": {c.cfg["architecture"] for c in cells},
    }
    for folder, names in used.items():
        found = {f.name.rsplit(".", 1)[0] for f in (REPO / "benchmark" / folder).iterdir()
                 if f.is_file()}
        assert found - {"__init__"} == names - {"__init__"}, folder


def test_the_harness_knows_no_model_class_and_no_key_of_one_block():
    """What knows the dense block lives in the architecture's three files."""
    words = re.compile(r"DenseLLM|PRESETS|is_moe|num_key_value_heads|intermediate_size")
    for name in ("harness", "correct", "stats", "traffic", "trace"):
        assert not words.search((REPO / f"benchmark/{name}.py").read_text()), name
    for kind in ("build", "counts", "reference"):
        assert words.search((REPO / f"benchmark/{kind}/qwen3_dense.py").read_text()), kind
