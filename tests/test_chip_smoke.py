"""CPU rehearsal of ``chip_smoke.py`` so the script cannot rot between chip
runs: its phases at toy size (``test-dense``, interpret mode) on one and on
four virtual devices, entered below the platform assert, and the assert
itself — with no TPU the script must exit non-zero and print no result.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

#: The chip's structure at a size the interpreter can serve: more requests
#: than slots, ragged and aligned prompt lengths, float32 toy widths.
TOY = chip_smoke.Sizes(
    preset="test-dense", depth=2, mega_depth=2, max_len=64, num_slots=2,
    chunk=2, requests=((5, 3), (20, 4), (9, 2)), compare_lens=(5, 20),
    oneshot_len=16, tol=1e-3,
)


def _phases(capsys) -> dict[str, dict]:
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return {l["phase"]: l for l in lines}


@pytest.mark.timeout(600)
def test_one_chip_phases_toy(capsys):
    chip_smoke.one_chip(jax.devices()[:1], TOY)
    phases = _phases(capsys)
    assert set(phases) == {
        "init", "serve/dist", "compare/dist-xla", "serve/mega",
        "compare/mega-xla",
    }
    for name in ("serve/dist", "serve/mega"):
        p = phases[name]
        assert p["tokens_out"] == sum(n for _, n in TOY.requests)
        assert p["lowerings_after_warmup"] == 0
        assert p["lowerings_in_warmup"] > 0
        assert not any(p["counters"].values())
    assert max(phases["compare/mega-xla"]["max_abs_logit_diff"].values()) <= TOY.tol


@pytest.mark.timeout(900)
def test_four_chips_phases_toy(capsys):
    chip_smoke.four_chips(jax.devices()[:4], TOY)
    phases = _phases(capsys)
    assert set(phases) == {"init", "serve/dist", "compare/dist-xla"}
    assert phases["serve/dist"]["tp"] == 4
    assert len(phases["init"]["bytes_in_use"]) == 4
    diffs = phases["compare/dist-xla"]["max_abs_logit_diff"]
    assert f"oneshot_prefill_{TOY.oneshot_len}" in diffs
    assert max(diffs.values()) <= TOY.tol


def test_exits_nonzero_without_tpu():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
        text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs 1 TPU device" in r.stderr


def test_serve_phase_refuses_a_dead_lowering_counter(monkeypatch):
    """With ``TDT_TELEMETRY=0`` the program's lowering counter stands still,
    and "none lowered after the warm-up" would pass blind: a warm-up that
    counted none fails the phase."""
    model, _ = chip_smoke.build_model(TOY, TOY.depth, jax.devices()[:1])
    monkeypatch.setattr(chip_smoke, "serve_once", lambda *a: ([[1]], 0.0, 0))
    with pytest.raises(AssertionError, match="not counting"):
        chip_smoke.serve_phase(model, "xla", TOY)
