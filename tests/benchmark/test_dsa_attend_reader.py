"""``dsa_attend_ms_per_chunk``: the reader on a hand-made trace (the flash
kernel inside the chunk program's operations, once a layer), on a trace that
holds no such kernel (the parent commit's, which attends in plain XLA through
``f32[16,2048,2048]`` fusions; a model with another attention: nothing is
read, nothing raises), on no trace at all, and its entry in the real
manifest."""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from test_dsa_select_reader import _run  # noqa: E402  (a hand-made trace as a harness.Run)

NAME = "dsa_attend_ms_per_chunk"
CELL = "glm-5.2-ep16-d5.longdoc"
KERNEL = ("%dsa_flash_prefill.{} = bf16[2048,16384]{{1,0:T(8,128)(2,1)}} custom-call(%reshape.100, "
          "%copy-done.1), custom_call_target=\"tpu_custom_call\"")
FUSION = "%fusion.431 = f32[16,2048,2048]{2,1,0:T(8,128)} fusion(%bitcast.9, %p.1), kind=kLoop"


def _reader():
    cell = harness.load_cell(REPO / "BENCHMARK.json", CELL)
    return cell, {e["name"]: m for e, m in cell.per_layer}[NAME]


def test_entry_lists_the_one_cell_that_attends_so():
    entries = json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]
    names = [e["name"] for e in entries]
    assert names.index(NAME) > names.index("dsa_select_ms_per_chunk")  # appended, nothing moved
    assert entries[names.index(NAME)] == {
        "name": NAME, "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "model step, prefill (models/engine.py, layers/, kernels/)",
        "moves": "out_tokens_per_s", "workloads": [CELL]}
    for w in ("qwen3-8b-d24.chat", "qwen3-8b-d24.doc"):
        assert NAME not in {e["name"] for e, _ in harness.load_cell(REPO / "BENCHMARK.json", w).per_layer}


def test_kernel_seconds_over_chunk_executions():
    cell, mod = _reader()
    # two chunks of five layers each, the kernel under five instruction names;
    # a decode chunk after them
    ops = [(KERNEL.format(layer), 1_000_000 * chunk + 150_000 * layer, 100_000 + 10_000 * layer)
           for chunk in (1, 3) for layer in range(1, 6)]
    ops += [("%fusion.9 = bf16[4] fusion(%p)", 7_500_000, 400_000)]
    programs = [("jit_chunk_fn(77)", 900_000, 1_500_000), ("jit_chunk_fn(78)", 2_900_000, 1_500_000),
                ("jit_decode_chunk_paged(5)", 7_400_000, 600_000)]
    # 2 x (0.11 + 0.12 + 0.13 + 0.14 + 0.15) ms / 2 chunks
    assert mod.read(_run(cell, ops, programs)) == pytest.approx(0.65)


def test_nothing_to_read_is_nothing_reported():
    cell, mod = _reader()
    chunk = [("jit_chunk_fn(77)", 900_000, 300_000_000)]
    # the parent's chunk program: the score matrix's fusions where the kernel is
    assert mod.read(_run(cell, [(FUSION, 1_000_000, 76_800_000)], chunk)) is None
    # the kernel's name as part of another's is not the kernel
    assert mod.read(_run(cell, [("%dsa_flash_prefill_ref.1 = f32[8] fusion(%p)", 1_000_000, 5)], chunk)) is None
    # a trace of decode chunks alone
    assert mod.read(_run(cell, [(KERNEL.format(2), 1_000_000, 5)],
                         [("jit_decode_chunk_paged(5)", 900_000, 600_000)])) is None
    run = _run(cell, [(KERNEL.format(2), 1_000_000, 5)], chunk)
    run.trace = None  # an untraced run
    assert mod.read(run) is None
