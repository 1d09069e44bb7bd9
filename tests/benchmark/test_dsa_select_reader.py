"""``dsa_select_ms_per_chunk``: the reader on a hand-made trace (the
selection's kernel inside the chunk program's operations), on a trace that
holds no such kernel (the parent commit's, which sorts; a model that does
not select: nothing is read, nothing raises), on no trace at all, and its
entry in the real manifest."""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark import trace as tr  # noqa: E402

NAME = "dsa_select_ms_per_chunk"
CELL = "glm-5.2-ep16-d5.longdoc"
KERNEL = ("%dsa_kth_value.{} = f32[2048,1]{{1,0:T(8,128)S(1)}} custom-call(%fusion.227), "
          "custom_call_target=\"tpu_custom_call\"")
SORT = "%sort.34 = (f32[2048,16384]{1,0}, s32[2048,16384]{1,0}) sort(%fusion.1, %iota.2)"


def _reader():
    cell = harness.load_cell(REPO / "BENCHMARK.json", CELL)
    return cell, {e["name"]: m for e, m in cell.per_layer}[NAME]


def _run(cell, ops, programs):
    E = tr.Ev
    planes = {"/device:TPU:0": {"XLA Ops": [E(*o) for o in ops],
                                "XLA Modules": [E(*p) for p in programs]},
              "/host:CPU": {"python3": [E("server.step", 0, 10_000_000)]}}
    empty = {"counters": {}, "histograms": {}, "digests": {}}
    return harness.Run(cell=cell, seed=1, chips=1, tp=1, peaks=None, reqs=[], t_open=0.0,
                       t_close=1.0, t_drain_end=1.0, first_step=1, last_step=1,
                       telemetry=harness.Telemetry(empty, empty), lowered_in_window=0,
                       trace=tr.reduce(planes))


def test_entry_lists_the_one_cell_that_selects():
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    assert m["per_layer"][-1] == {
        "name": NAME, "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "model step, prefill (models/engine.py, layers/, kernels/)",
        "moves": "out_tokens_per_s", "workloads": [CELL]}
    for w in ("qwen3-8b-d24.chat", "qwen3-8b-d24.doc"):
        assert NAME not in {e["name"] for e, _ in harness.load_cell(REPO / "BENCHMARK.json", w).per_layer}


def test_kernel_seconds_over_chunk_executions():
    cell, mod = _reader()
    # three chunks, two selecting layers each; a decode chunk between them
    ops = [(KERNEL.format(2), 1_000_000 * i, 700_000) for i in (1, 3, 5)]
    ops += [(KERNEL.format(3), 1_000_000 * i, 300_000) for i in (2, 4, 6)]
    ops += [("%fusion.9 = bf16[4] fusion(%p)", 7_500_000, 400_000)]
    programs = [("jit_chunk_fn(77)", 900_000, 1_500_000), ("jit_chunk_fn(78)", 2_900_000, 1_500_000),
                ("jit_chunk_fn(77)", 4_900_000, 1_500_000), ("jit_decode_chunk_paged(5)", 7_400_000, 600_000)]
    assert mod.read(_run(cell, ops, programs)) == pytest.approx(1.0)  # 3 x (0.7 + 0.3) ms / 3


def test_nothing_to_read_is_nothing_reported():
    cell, mod = _reader()
    chunk = [("jit_chunk_fn(77)", 900_000, 30_000_000)]
    # the parent's chunk program: a sort where the kernel is
    assert mod.read(_run(cell, [(SORT, 1_000_000, 27_600_000)], chunk)) is None
    # the kernel's name as part of another's is not the kernel
    assert mod.read(_run(cell, [("%dsa_kth_value_ref.1 = f32[8] fusion(%p)", 1_000_000, 5)], chunk)) is None
    # a trace of decode chunks alone
    assert mod.read(_run(cell, [(KERNEL.format(2), 1_000_000, 5)],
                         [("jit_decode_chunk_paged(5)", 900_000, 600_000)])) is None
    run = _run(cell, [(KERNEL.format(2), 1_000_000, 5)], chunk)
    run.trace = None  # an untraced run
    assert mod.read(run) is None
