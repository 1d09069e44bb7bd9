"""AOT export + standalone C++ runtime bridge.

Reference: ``python/triton_dist/tools/compile_aot.py`` (860 LoC — AOT
compiler generating C sources + dispatch) and
``tools/runtime/triton_aot_runtime.cc`` (CUDA-driver runtime). TPU
redesign: ``export_aot`` lowers a jitted function to a **StableHLO
artifact** (program.mlir + serialized CompileOptionsProto + input manifest
and raw input bytes); ``csrc/tdt_aot_runtime.cc`` is a dependency-free C++
binary that dlopens the PJRT plugin it is given (libtpu / any conforming
backend), compiles the artifact, executes it on raw buffers, and writes raw
outputs — serving with zero Python in the process. ``build_runtime`` shells
the documented g++ line; ``run_aot`` wraps the binary for tests.
"""

from __future__ import annotations

import os
import pathlib
import subprocess

import numpy as np


_DTYPE_NAMES = {
    "float32": "f32",
    "bfloat16": "bf16",
    "float16": "f16",
    "int32": "i32",
    "int8": "i8",
    "uint8": "u8",
}

def _tf_include_dir() -> str:
    import tensorflow  # the env ships TF; only its headers are used

    return os.path.join(os.path.dirname(tensorflow.__file__), "include")


def repo_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parents[2]


def export_aot(fn, args, outdir: str) -> str:
    """Lower ``jax.jit(fn)(*args)`` to a runtime artifact directory.

    Writes program.mlir (StableHLO text), compile_options.pb
    (xla.CompileOptionsProto), manifest.txt (one ``dtype ndim dims...`` line
    per input), input_<i>.bin (raw bytes of ``args``), and expected_<i>.bin
    (the Python-side outputs, for end-to-end runtime validation)."""
    import jax
    from jaxlib import xla_client

    out = pathlib.Path(outdir)
    out.mkdir(parents=True, exist_ok=True)

    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jfn.lower(*args)
    (out / "program.mlir").write_text(lowered.as_text(dialect="stablehlo"))
    (out / "compile_options.pb").write_bytes(
        xla_client.CompileOptions().SerializeAsString()
    )

    lines = []
    for i, a in enumerate(args):
        a = np.asarray(a)
        name = _DTYPE_NAMES[a.dtype.name]
        lines.append(f"{name} {a.ndim} " + " ".join(str(d) for d in a.shape))
        (out / f"input_{i}.bin").write_bytes(np.ascontiguousarray(a).tobytes())
    (out / "manifest.txt").write_text("\n".join(lines) + "\n")

    res = jfn(*args)
    leaves = jax.tree.leaves(res)
    out_lines = []
    for i, r in enumerate(leaves):
        r = np.asarray(r)
        out_lines.append(r.dtype.name)
        (out / f"expected_{i}.bin").write_bytes(np.ascontiguousarray(r).tobytes())
    (out / "outputs_manifest.txt").write_text("\n".join(out_lines) + "\n")
    return str(out)


def build_runtime(out_bin: str | None = None) -> str:
    """Compile csrc/tdt_aot_runtime.cc with g++ (the documented build line)."""
    src = repo_root() / "csrc" / "tdt_aot_runtime.cc"
    out_bin = out_bin or str(repo_root() / "csrc" / "tdt_aot_run")
    cmd = [
        "g++", "-O2", "-std=c++17", f"-I{_tf_include_dir()}",
        str(src), "-ldl", "-o", out_bin,
    ]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    return out_bin


def run_aot(artifact_dir: str, *, plugin: str,
            binary: str | None = None, iters: int = 1,
            timeout: int = 300) -> subprocess.CompletedProcess:
    """Run the C++ runtime on an exported artifact through the PJRT plugin
    at ``plugin``; outputs land next to the artifact. The runtime is its own
    process and takes the device for itself: run it where no other process
    holds the chip. A plugin that needs client-create options reads them
    from ``<artifact_dir>/options.txt`` (see the runtime's header)."""
    binary = binary or str(repo_root() / "csrc" / "tdt_aot_run")
    return subprocess.run(
        [binary, plugin, artifact_dir, str(iters)],
        capture_output=True, text=True, timeout=timeout,
    )


def compare_outputs(artifact_dir: str, *, rtol: float = 1e-4) -> int:
    """Compare output_<i>.bin against expected_<i>.bin with the TRUE dtypes
    (outputs_manifest.txt written at export): floating outputs compare with
    tolerance, integer/bool outputs bit-exact — a raw-f32 reinterpretation
    would vacuously pass mismatched int outputs as ~1e-44 denormals.
    Returns the number of outputs compared."""
    import ml_dtypes  # bfloat16 numpy dtype (ships with jax)

    out = pathlib.Path(artifact_dir)
    dtypes = (out / "outputs_manifest.txt").read_text().split()
    n = 0
    while (out / f"expected_{n}.bin").exists():
        dt = np.dtype(
            ml_dtypes.bfloat16 if dtypes[n] == "bfloat16" else dtypes[n]
        )
        e = np.frombuffer((out / f"expected_{n}.bin").read_bytes(), dt)
        g = np.frombuffer((out / f"output_{n}.bin").read_bytes(), dt)
        assert e.shape == g.shape, (n, e.shape, g.shape)
        if np.issubdtype(dt, np.floating) or dt == ml_dtypes.bfloat16:
            np.testing.assert_allclose(
                g.astype(np.float32), e.astype(np.float32), rtol=rtol, atol=rtol
            )
        else:
            np.testing.assert_array_equal(g, e)
        n += 1
    return n


# --------------------------------------------------------------------------
# Config-space export + runtime dispatch (reference ``aot_compile_spaces``,
# compile_aot.py:62, usage ep_a2a.py:64-77: a grid of signatures and
# algo-infos compiled ahead of time, dispatched at runtime).
# --------------------------------------------------------------------------


def _space_key(sig: str, algo: dict) -> str:
    """Directory-safe point key: signature + sorted algo items."""
    algo_part = "_".join(f"{k}-{v}" for k, v in sorted(algo.items()))
    sig_part = sig.replace(",", "+").replace(":", ".")
    return f"{sig_part}__{algo_part}" if algo_part else sig_part


def export_aot_space(name: str, build, space, outdir: str) -> str:
    """Export a GRID of compiled variants of one op (the
    ``aot_compile_spaces`` analog): ``space`` is a list of
    ``{"args": (arrays...), "algo": {...static config...}}`` points;
    ``build(**algo)`` returns the traceable function for that config. Each
    point lands in ``outdir/name/<key>/`` as a full ``export_aot`` artifact,
    and ``outdir/name/space.json`` maps every point's input signature +
    algo to its artifact — the dispatch table :class:`AotSpace` (and any
    non-Python serving layer: it is plain JSON + the C runtime's artifact
    format) selects from."""
    import json

    from triton_dist_tpu.tools.tune import arg_signature

    root = pathlib.Path(outdir) / name
    root.mkdir(parents=True, exist_ok=True)
    table = []
    for point in space:
        args = point["args"]
        algo = dict(point.get("algo", {}))
        sig = arg_signature(args)
        key = _space_key(sig, algo)
        export_aot(build(**algo), args, str(root / key))
        table.append({"signature": sig, "algo": algo, "artifact": key})
    (root / "space.json").write_text(json.dumps(
        {"name": name, "points": table}, indent=1, sort_keys=True))
    return str(root)


class AotSpace:
    """Runtime dispatcher over an exported config space: pick the artifact
    whose signature matches the inputs (and, optionally, a requested algo),
    then hand it to the C++ runtime (``run_aot``) or any PJRT host."""

    def __init__(self, root: str):
        import json

        self.root = pathlib.Path(root)
        data = json.loads((self.root / "space.json").read_text())
        self.name = data["name"]
        self.points = data["points"]

    def select(self, args, algo: dict | None = None) -> str:
        """Artifact dir for these inputs. With ``algo=None`` and several
        algo variants for the signature, the FIRST exported wins (export
        order is preference order, like the reference's algo_info lists)."""
        from triton_dist_tpu.tools.tune import arg_signature

        sig = arg_signature(args)
        for p in self.points:
            if p["signature"] == sig and (algo is None or p["algo"] == algo):
                return str(self.root / p["artifact"])
        raise KeyError(
            f"AotSpace {self.name!r}: no artifact for signature {sig!r}"
            + (f" with algo {algo}" if algo else "")
            + f"; have {[(p['signature'], p['algo']) for p in self.points]}"
        )

    def run(self, args, algo: dict | None = None, workdir: str | None = None,
            **kw):
        """Dispatch + execute through the C++ runtime on THESE input values.
        The selected artifact is COPIED to a per-run directory first — the
        exported artifact stays pristine and concurrent dispatches can't
        interleave input writes. The copy drops the export-time
        expected_*.bin (they pair with the export-time inputs, not these —
        ``compare_outputs`` on a run dir would be comparing against the
        wrong baseline). ``workdir`` must not already exist and must not
        lie inside the space root (nothing is ever deleted here). Returns
        (CompletedProcess, run_dir)."""
        import shutil
        import tempfile

        art = pathlib.Path(self.select(args, algo)).resolve()
        if workdir is None:
            run_dir = pathlib.Path(tempfile.mkdtemp(prefix="aot_run_")) / "art"
        else:
            run_dir = pathlib.Path(workdir)
            if run_dir.exists():
                raise ValueError(f"workdir {run_dir} already exists")
            if self.root.resolve() in run_dir.resolve().parents:
                raise ValueError(
                    f"workdir {run_dir} lies inside the exported space "
                    f"{self.root} — refusing to write there"
                )
        shutil.copytree(
            art, run_dir,
            ignore=shutil.ignore_patterns("expected_*.bin", "outputs_manifest.txt"),
        )
        for i, a in enumerate(args):
            a = np.asarray(a)
            (run_dir / f"input_{i}.bin").write_bytes(
                np.ascontiguousarray(a).tobytes()
            )
        return run_aot(str(run_dir), **kw), str(run_dir)
