"""AOT export + standalone C++ PJRT runtime.

Parity model: reference ``tools/compile_aot.py`` + ``triton_aot_runtime.cc``
— compile ahead of time, then serve from a native runtime with no Python in
the process. The execute leg needs a PJRT plugin that reaches a device, and
a child that loads the TPU's library cannot run where the tests run; here
the runtime is built and held to its contract up to the plugin it is given.
"""

import os
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.tools import aot


def test_export_artifact(tmp_path):
    x = np.arange(32, dtype=np.float32).reshape(4, 8) / 10
    w = np.ones((8, 4), np.float32) * 0.5
    d = aot.export_aot(lambda a, b: jnp.tanh(a @ b), (x, w), os.fspath(tmp_path))
    names = sorted(os.listdir(d))
    assert "program.mlir" in names and "compile_options.pb" in names
    assert "manifest.txt" in names and "input_0.bin" in names
    mlir = (tmp_path / "program.mlir").read_text()
    assert "stablehlo" in mlir and "module" in mlir
    manifest = (tmp_path / "manifest.txt").read_text().splitlines()
    assert manifest[0] == "f32 2 4 8" and manifest[1] == "f32 2 8 4"


def test_aot_flash_decode_space(tmp_path):
    """Reference AOT flash-decode wrappers (``flash_decode.py:763-1131``:
    pre-compiled decode entry points per (batch, split) config, served
    without tracing): the TPU analog exports the flash-decode kernel into
    an AotSpace over (batch signature × block_k algo). The 'persistent'
    variant (:587) needs no TPU analog — the grid-swept Pallas kernel IS
    persistent (one launch walks all KV blocks; SURVEY §2.4 row 39 note).
    Dispatch picks by batch signature; each artifact is a full standalone
    export, and the traced programs genuinely differ per block_k."""
    from triton_dist_tpu.kernels.flash_decode import flash_decode
    from triton_dist_tpu.tools.aot import AotSpace, export_aot_space

    hq, hkv, s, d = 4, 2, 128, 32

    def build(block_k=64):
        def f(q, kc, vc, lengths):
            return flash_decode(q, kc, vc, lengths, block_k=block_k)
        return f

    def args_for(b):
        rng = np.random.default_rng(b)
        return (
            jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32),
            jnp.asarray([s // 2] * b, jnp.int32),
        )

    space = [
        {"args": args_for(1), "algo": {"block_k": 64}},
        {"args": args_for(1), "algo": {"block_k": 128}},
        {"args": args_for(4), "algo": {"block_k": 64}},
    ]
    root = export_aot_space("flash_decode", build, space, os.fspath(tmp_path))
    sp = AotSpace(root)
    assert len(sp.points) == 3

    a1, a4 = args_for(1), args_for(4)
    art1 = sp.select(a1)  # first-exported algo wins: block_k=64
    assert "block_k-64" in art1
    assert "block_k-128" in sp.select(a1, algo={"block_k": 128})
    assert sp.select(a4) != art1
    with pytest.raises(KeyError):
        sp.select(args_for(2))  # off-grid batch → loud error
    # The algo is real: the two bsz=1 programs differ (block partitioning
    # is baked into the traced kernel).
    p64 = (pathlib.Path(art1) / "program.mlir").read_text()
    p128 = (pathlib.Path(sp.select(a1, algo={"block_k": 128})) /
            "program.mlir").read_text()
    assert p64 != p128


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_build_runtime(tmp_path):
    out = aot.build_runtime(os.fspath(tmp_path / "tdt_aot_run"))
    assert os.path.exists(out) and os.access(out, os.X_OK)


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_runtime_takes_its_plugin_as_an_argument(tmp_path):
    """Export → build → run: the runtime loads exactly the plugin it is
    handed and says so when it cannot — no default path, no options file
    written behind the caller's back."""
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16) / 100
    w = (np.ones((16, 8), np.float32) * 0.1)
    art = aot.export_aot(
        lambda a, b: jnp.tanh(a @ b) + 1.0, (x, w), os.fspath(tmp_path / "art")
    )
    binary = aot.build_runtime(os.fspath(tmp_path / "tdt_aot_run"))
    missing = os.fspath(tmp_path / "no_such_plugin.so")
    r = aot.run_aot(art, plugin=missing, binary=binary, timeout=60)
    assert r.returncode != 0
    assert "no_such_plugin.so" in r.stderr
    assert not (pathlib.Path(art) / "options.txt").exists()
    assert not (pathlib.Path(art) / "output_0.bin").exists()


def test_aot_config_space_dispatch(tmp_path):
    """Config-space export + runtime dispatch (reference aot_compile_spaces,
    compile_aot.py:62 + ep_a2a.py:64-77): a grid of (signature, algo)
    variants exports as one space; AotSpace selects by input signature and
    algo, raising loudly off-grid."""
    import jax.numpy as jnp

    from triton_dist_tpu.tools.aot import AotSpace, export_aot_space

    def build(block=4):
        # The algo changes the traced program (tile-summed matmul).
        def f(a, b):
            acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
            for i in range(0, a.shape[1], block):
                acc += a[:, i:i + block] @ b[i:i + block, :]
            return acc
        return f

    x8 = np.ones((8, 8), np.float32)
    x16 = np.ones((16, 8), np.float32)
    w = np.ones((8, 4), np.float32)
    space = [
        {"args": (x8, w), "algo": {"block": 4}},
        {"args": (x8, w), "algo": {"block": 8}},
        {"args": (x16, w), "algo": {"block": 4}},
    ]
    root = export_aot_space("toy_gemm", build, space, os.fspath(tmp_path))

    sp = AotSpace(root)
    assert len(sp.points) == 3
    # Signature-only dispatch: first exported algo wins for (8,8).
    art = sp.select((x8, w))
    assert "block-4" in art
    # Explicit algo dispatch.
    art8 = sp.select((x8, w), algo={"block": 8})
    assert "block-8" in art8 and art8 != art
    # Different shape → different artifact.
    assert sp.select((x16, w)) not in (art, art8)
    # Every artifact is a full runnable export (program + manifests).
    for p in sp.points:
        d = pathlib.Path(root) / p["artifact"]
        assert (d / "program.mlir").exists() and (d / "manifest.txt").exists()
    # Off-grid signature fails loudly.
    with pytest.raises(KeyError):
        sp.select((np.ones((3, 8), np.float32), w))
