"""Tooling tests: perf models, profiler traces, tune cache, autotuner.

Parity model: reference ``comm_perf_model``/``gemm_perf_model`` consistency
checks and the profiler's trace-export contract.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.tools.perf_model import (
    CHIPS,
    allgather_time_s,
    allreduce_time_s,
    all_to_all_time_s,
    attention_time_s,
    chip_spec,
    gemm_time_s,
    overlap_efficiency,
    overlap_fraction,
    reduce_scatter_time_s,
)
from triton_dist_tpu.tools.profiler import ChromeTrace, profile_op


V5E = CHIPS["tpu v5 lite"]


def test_perf_model_rooflines():
    # MXU-bound large GEMM: time ≈ flops/peak.
    t = gemm_time_s(8192, 8192, 8192, jnp.bfloat16, V5E)
    assert abs(t - 2 * 8192**3 / (V5E.bf16_tflops * 1e12)) / t < 1e-6
    # HBM-bound skinny GEMM: bigger than pure-MXU time.
    t_skinny = gemm_time_s(8, 8192, 8192, jnp.bfloat16, V5E)
    assert t_skinny > 2 * 8 * 8192 * 8192 / (V5E.bf16_tflops * 1e12)
    # Monotonic in shape.
    assert gemm_time_s(4096, 4096, 4096, jnp.bfloat16, V5E) < t
    # Causal attention is half the flops of full.
    full = attention_time_s(4, 16, 4096, 128, jnp.bfloat16, V5E, causal=False)
    half = attention_time_s(4, 16, 4096, 128, jnp.bfloat16, V5E, causal=True)
    assert half < full


def test_perf_model_collectives():
    nbytes = 64 * 1024 * 1024
    ag = allgather_time_s(nbytes, 8, V5E)
    rs = reduce_scatter_time_s(nbytes, 8, V5E)
    ar = allreduce_time_s(nbytes, 8, V5E)
    assert ag == rs and abs(ar - 2 * ag) < 1e-12
    assert allgather_time_s(nbytes, 1, V5E) == 0.0
    # More ranks moves more total data over the ring.
    assert allgather_time_s(nbytes, 16, V5E) > ag
    assert all_to_all_time_s(nbytes, 8, V5E) > 0


def test_overlap_accounting():
    # Perfect overlap: measured == max leg.
    assert overlap_fraction(1.0, 1.0, 0.5) == 1.0
    # Fully serial.
    assert overlap_fraction(1.5, 1.0, 0.5) == 0.0
    # Halfway.
    assert abs(overlap_fraction(1.25, 1.0, 0.5) - 0.5) < 1e-9
    # Clipping.
    assert overlap_fraction(2.0, 1.0, 0.5) == 0.0
    assert overlap_fraction(0.9, 1.0, 0.5) == 1.0
    # Efficiency: BASELINE's ≥0.9 bar shape.
    assert abs(overlap_efficiency(1.1, 1.0, 0.8) - 1.0 / 1.1) < 1e-9


def test_chip_spec_lookup():
    assert chip_spec("TPU v5 lite").name == "tpu v5 lite"
    assert chip_spec("TPU v5p chip").name == "tpu v5"
    # An unknown device is an error, never assumed peaks — the CPU included.
    for kind in ("weird-device", "cpu"):
        with pytest.raises(KeyError):
            chip_spec(kind)


def test_compile_cache_follows_the_environment(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax reads it, the helper sets
    nothing. Unset: the fixed path ``<checkout>/.jax_cache``."""
    import pathlib

    from triton_dist_tpu.runtime.platform import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compile_cache()
        checkout = pathlib.Path(__file__).resolve().parents[1]
        assert jax.config.jax_compilation_cache_dir == str(checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chrome_trace(tmp_path):
    tr = ChromeTrace()
    x = jnp.ones((128, 128))
    with tr.span("matmul", pid=0) as s:
        s["block"] = jnp.dot(x, x)
    with tr.span("add", pid=1):
        pass
    path = tr.save(os.fspath(tmp_path / "trace.json"))
    data = json.load(open(path))
    names = [e["name"] for e in data["traceEvents"]]
    assert names == ["matmul", "add"]
    assert all(e["dur"] >= 0 and e["ph"] == "X" for e in data["traceEvents"])


def test_profile_op_xprof(tmp_path):
    """XProf capture around a jitted op drops trace artifacts."""
    d = os.fspath(tmp_path / "xprof")
    profile_op(lambda a: jnp.dot(a, a), (jnp.ones((64, 64)),), d, iters=2)
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "profiler should write trace files"


def test_topology_probe():
    from triton_dist_tpu.runtime.topology import probe, ring_order, split_ici_dcn_axes
    from triton_dist_tpu.runtime.platform import cpu_mesh

    info = probe()
    assert info.num_devices >= 1 and info.devices_per_process >= 1
    order = ring_order()
    assert sorted(order) == list(range(info.num_devices))
    m = cpu_mesh((2, 4), ("a", "b"))
    ici, dcn = split_ici_dcn_axes(m)
    # Single-process CPU sim: every axis is intra-process (ICI).
    assert set(ici) == {"a", "b"} and dcn == []


def test_ring_order_one_hop_property():
    """The snake walk yields single-hop neighbors on any torus shape."""
    import itertools
    from triton_dist_tpu.runtime.topology import TopologyInfo

    import triton_dist_tpu.runtime.topology as topo

    for shape in [(4, 4), (2, 2, 2), (2, 3, 4), (4, 4, 2), (2, 4, 2, 2)]:
        coords = list(itertools.product(*[range(s) for s in shape]))

        class FakeDev:
            def __init__(self, c):
                self.coords = c
                self.device_kind = "fake"
                self.process_index = 0

        devs = [FakeDev(c) for c in coords]
        order = topo.ring_order(devs)
        for a, b in zip(order, order[1:]):
            diff = sum(abs(x - y) for x, y in zip(coords[a], coords[b]))
            assert diff == 1, (shape, coords[a], coords[b])


def test_multiprocess_launcher(tmp_path):
    """scripts/launch.py --local: real multi-process jax.distributed
    rendezvous + a cross-process psum (the torchrun-wrapper analog,
    reference scripts/launch.sh)."""
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).parents[1]
    script = tmp_path / "smoke.py"
    script.write_text(
        "import jax, jax.numpy as jnp\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from triton_dist_tpu.runtime.mesh import initialize_distributed\n"
        "ctx = initialize_distributed(axis_names=('dp',))\n"
        "x = jnp.ones((jax.device_count(), 4)) * (jax.process_index() + 1)\n"
        "out = jax.jit(jax.shard_map(lambda v: jax.lax.psum(v, 'dp'), mesh=ctx.mesh,\n"
        "    in_specs=(P('dp'),), out_specs=P('dp'), check_vma=False))(x)\n"
        "assert jax.process_count() == 2\n"
        "expected = 3.0 * jax.local_device_count()  # procs contribute 1 and 2\n"
        "assert float(out.addressable_shards[0].data[0, 0]) == expected\n"
        "# A distributed kernel across PROCESS boundaries (the DCN analog):\n"
        "# the XLA-ring collective matmul runs over the 2-process mesh.\n"
        "from triton_dist_tpu.kernels.allgather_gemm import AGGemmMethod, ag_gemm_shard\n"
        "import numpy as np\n"
        "w = jax.device_count()\n"
        "a = jnp.ones((w * 4, 8)); b = jnp.ones((8, w * 4))\n"
        "out2 = jax.jit(jax.shard_map(lambda a_, b_: ag_gemm_shard(a_, b_, axis='dp', method=AGGemmMethod.XLA_RING),\n"
        "    mesh=ctx.mesh, in_specs=(P('dp'), P(None, 'dp')), out_specs=P(None, 'dp'), check_vma=False))(a, b)\n"
        "full = np.asarray(a) @ np.asarray(b)  # global value spans processes:\n"
        "for sh in out2.addressable_shards:  # compare the local shards only\n"
        "    np.testing.assert_allclose(np.asarray(sh.data), full[tuple(sh.index)])\n"
        "# Cross-rank contextual autotune (reference autotuner.py:97-250):\n"
        "# fake per-rank timings DISAGREE on the winner (rank0: cfg a wins,\n"
        "# rank1: cfg b wins); the max-allreduce must make both ranks pick\n"
        "# b (max scores: a=3, b=2) — divergent picks would mean divergent\n"
        "# HLO inside one SPMD program.\n"
        "import triton_dist_tpu.tools.tune as tune\n"
        "fake = {0: {'a': 1.0, 'b': 2.0}, 1: {'a': 3.0, 'b': 1.0}}\n"
        "tune.bench_device_time = lambda f, args, **kw: fake[jax.process_index()][f()]\n"
        "import pathlib\n"
        "cache = tune.TuneCache(path=pathlib.Path(__file__).parent / ('tune_%d.json' % jax.process_index()))\n"
        "best, t = tune.autotune('toy', [{'cfg': 'a'}, {'cfg': 'b'}],\n"
        "    lambda c: (lambda: c['cfg']), (), cache=cache, use_cache=False)\n"
        "assert best == {'cfg': 'b'} and t == 2.0, (best, t)\n"
        "print('SMOKE OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root) + os.pathsep + env.get("PYTHONPATH", "")
    # Children inherit the session's XLA_FLAGS (8 virtual CPU devices each);
    # the smoke assertions scale by local_device_count accordingly. Timeout
    # stays under the conftest watchdog (180 s) so a rendezvous hang fails
    # THIS test instead of hard-killing the session.
    r = subprocess.run(
        [sys.executable, str(root / "scripts" / "launch.py"), "--local", "2",
         str(script)],
        capture_output=True, text=True, timeout=150, env=env,
    )
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-500:])
    assert r.stdout.count("SMOKE OK") == 2


def test_device_memory_stats():
    """Allocator metrics surface (reference megakernel memory metrics):
    dict of ints, or {} on backends without allocator stats (CPU sim)."""
    from triton_dist_tpu.tools.profiler import device_memory_stats

    stats = device_memory_stats()
    assert isinstance(stats, dict)
    for v in stats.values():
        assert isinstance(v, int)


def test_flash_config_cache(tmp_path, monkeypatch):
    """flash_attention consults the tune cache at trace time, same
    discipline as gemm_config_for (r1 VERDICT: a config space nothing
    consumes is not an autotuner)."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.flash_attn import flash_config_for, flash_op_name
    from triton_dist_tpu.tools import tune

    monkeypatch.setenv("TDT_TUNE_CACHE", str(tmp_path / "cache.json"))
    q = jax.ShapeDtypeStruct((1, 4, 256, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    # Miss → measured default.
    assert flash_config_for(q, k, v, True) == (1024, 1024)
    # Seed the cache the way tune_flash persists winners (q, k, v key).
    cache = tune.TuneCache()
    cache.put(
        f"{flash_op_name(True)}|{tune.arg_signature([q, k, v])}",
        {"cfg": {"block_q": 128, "block_k": 64}, "time_s": 1e-3, "version": "x"},
    )
    cache.save()
    tune._default_cache = None  # drop the memoized miss
    assert flash_config_for(q, k, v, True) == (128, 64)
    # Non-causal key is distinct.
    assert flash_config_for(q, k, v, False) == (1024, 1024)


def test_flash_decode_config_cache(tmp_path, monkeypatch):
    """The --flash-decode sweep's WRITE path and flash_decode_config_for's
    READ path round-trip through the cache (writer/reader key drift would
    make the sweep a silent no-op — caught in r4 review: an early reader
    keyed on (q, kc) while autotune persists under the full timed arg
    list). Both back-leg lowerings — standalone decode and fused_attn_back
    — read the SAME key, so their block partitioning can't drift."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.flash_decode import flash_decode_config_for
    from triton_dist_tpu.tools import tune
    from triton_dist_tpu.tools.tune_gemm import tune_flash_decode

    monkeypatch.setenv("TDT_TUNE_CACHE", str(tmp_path / "cache.json"))
    b, hq, hkv, s, d = 1, 2, 1, 128, 32
    q = jax.ShapeDtypeStruct((b, hq, d), jnp.float32)
    kc = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.float32)
    vc = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.float32)
    # Miss → default 256.
    tune._default_cache = None
    assert flash_decode_config_for(q, kc, vc) == 256
    # THE REAL WRITE PATH: run the sweep (s=128 admits only block_k=128,
    # so the winner provably differs from the 256 default).
    best, _ = tune_flash_decode(b, hq, hkv, s, d, jnp.float32, verbose=False)
    assert best == {"block_k": 128}
    tune._default_cache = None
    assert flash_decode_config_for(q, kc, vc) == 128


def test_flash_bwd_config_cache(tmp_path, monkeypatch):
    """flash_attention_bwd consults its own tune-cache key at trace time,
    falling back to the FORWARD's tuned blocks (bwd and fwd optima track
    each other), then the default."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.flash_attn import (
        flash_bwd_config_for,
        flash_bwd_op_name,
        flash_op_name,
    )
    from triton_dist_tpu.tools import tune

    monkeypatch.setenv("TDT_TUNE_CACHE", str(tmp_path / "cache.json"))
    q = jax.ShapeDtypeStruct((1, 4, 256, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    # Total miss → default.
    assert flash_bwd_config_for(q, k, v, True) == (1024, 1024)
    # Forward-tuned only → bwd inherits the forward's blocks.
    cache = tune.TuneCache()
    cache.put(
        f"{flash_op_name(True)}|{tune.arg_signature([q, k, v])}",
        {"cfg": {"block_q": 256, "block_k": 128}, "time_s": 1e-3, "version": "x"},
    )
    cache.save()
    tune._default_cache = None
    assert flash_bwd_config_for(q, k, v, True) == (256, 128)
    # A dedicated bwd entry (tune_gemm --flash-bwd) takes precedence.
    cache = tune.TuneCache()
    cache.put(
        f"{flash_bwd_op_name(True)}|{tune.arg_signature([q, k, v])}",
        {"cfg": {"block_q": 64, "block_k": 64}, "time_s": 1e-3, "version": "x"},
    )
    cache.save()
    tune._default_cache = None
    assert flash_bwd_config_for(q, k, v, True) == (64, 64)


def test_bench_tune_entries_round_trip(tmp_path, monkeypatch):
    """The driver bench's ``tune_entries`` extras round-trip into the live
    cache readers (VERDICT r4 item 3): entries built with ``make_entry`` —
    the SAME helper every bench mini-sweep calls — merge via
    ``merge_entries`` and are then picked up by flash fwd/bwd/decode
    config_for AND the allreduce crossover routing, with no key drift."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.allreduce import (
        AllReduceMethod,
        ar_crossover_bytes,
        get_auto_all_reduce_method,
    )
    from triton_dist_tpu.kernels.flash_attn import (
        flash_bwd_op_name,
        flash_config_for,
        flash_bwd_config_for,
        flash_op_name,
    )
    from triton_dist_tpu.kernels.flash_decode import (
        flash_decode_config_for,
        flash_decode_op_name,
    )
    from triton_dist_tpu.tools import tune

    monkeypatch.setenv("TDT_TUNE_CACHE", str(tmp_path / "cache.json"))
    tune._default_cache = None

    q = jax.ShapeDtypeStruct((1, 4, 256, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    qd = jax.ShapeDtypeStruct((2, 4, 32), jnp.float32)
    kc = jax.ShapeDtypeStruct((2, 2, 128, 32), jnp.float32)

    # Exactly what the bench sections emit into extra["tune_entries"].
    emitted = dict(
        [
            tune.make_entry(flash_op_name(True), (q, k, v),
                            {"block_q": 256, "block_k": 512}, 1e-3),
            tune.make_entry(flash_bwd_op_name(True), (q, k, v),
                            {"block_q": 512, "block_k": 512}, 2e-3),
            tune.make_entry(flash_decode_op_name(), (qd, kc, kc),
                            {"block_k": 512}, 5e-5),
        ]
    )
    emitted["ar_crossover|world=8"] = {
        "cfg": {"crossover_bytes": 1 << 20}, "time_s": 2e-5, "version": "x"}

    # Defaults before the merge (cold cache).
    assert flash_config_for(q, k, v, True) == (1024, 1024)
    assert ar_crossover_bytes(8) == 256 * 1024

    tune.merge_entries(emitted)
    tune._default_cache = None  # drop the memoized misses

    assert flash_config_for(q, k, v, True) == (256, 512)
    assert flash_bwd_config_for(q, k, v, True) == (512, 512)
    assert flash_decode_config_for(qd, kc, kc) == 512
    assert ar_crossover_bytes(8) == 1 << 20
    # Routing obeys the measured crossover: 1 MiB-sized message is now
    # one-shot (would be two-shot under the 256 KiB static fallback).
    assert get_auto_all_reduce_method(1 << 20, 8) is AllReduceMethod.ONE_SHOT
    assert get_auto_all_reduce_method((1 << 20) + 2, 8) is AllReduceMethod.TWO_SHOT
    # Unknown world → static fallback, untouched by the world=8 entry.
    assert ar_crossover_bytes(4) == 256 * 1024

    # Malformed entries are rejected loudly, not silently merged.
    import pytest

    with pytest.raises(ValueError):
        tune.merge_entries({"bad": {"time_s": 1.0}})


def test_tune_cache_schema_version(tmp_path, monkeypatch):
    """Cache files from another schema load EMPTY — stale pre-PR files are
    ignored wholesale, never half-read (their entries may predate
    routing-relevant fields like the crossover values), and ``save()``
    stamps the current schema so the next load round-trips."""
    from triton_dist_tpu.tools import tune

    path = tmp_path / "cache.json"
    monkeypatch.setenv("TDT_TUNE_CACHE", str(path))
    tune._default_cache = None
    key = "gemm|8x8:float32,8x8:float32"
    entry = {"cfg": {"block_m": 8}, "time_s": 1.0, "version": "x"}

    # A pre-schema (v1-era) file: valid entries, no __schema__ marker.
    path.write_text(json.dumps({key: entry}))
    cache = tune.TuneCache()
    assert cache.get(key) is None
    assert not cache.has_op("gemm")

    # save() stamps the CURRENT schema; a fresh load round-trips entries
    # and never surfaces the marker as an entry.
    cache.put(key, entry)
    cache.save()
    raw = json.loads(path.read_text())
    assert raw["__schema__"] == {"version": tune.SCHEMA_VERSION}
    cache2 = tune.TuneCache()
    assert cache2.get(key)["cfg"] == {"block_m": 8}
    assert cache2.has_op("gemm")
    assert cache2.get("__schema__") is None

    # A FUTURE schema is ignored the same way (no forward half-read).
    raw["__schema__"] = {"version": tune.SCHEMA_VERSION + 1}
    path.write_text(json.dumps(raw))
    assert tune.TuneCache().get(key) is None

    # The committed v5e cache ships with the current schema marker — a
    # version bump without migrating it would silently dead the file.
    shipped = json.loads(
        (tune._DEFAULT_DIR / "tpu_v5_lite.json").read_text())
    assert shipped["__schema__"] == {"version": tune.SCHEMA_VERSION}


def test_overlap_report_dual_matched_lines(tmp_path, monkeypatch):
    """``overlap_report`` classifies each timeline line ONCE, with DMA
    precedence: a TPU ``"Stream #1 queue"`` row matches BOTH default line
    patterns, and counting it on both sides would overlap it with itself
    (overlap_frac_of_dma spuriously → 1.0). Synthetic planes: the dual
    row must land on the DMA side only, be reported in
    ``dual_matched_lines``, and contribute zero self-overlap."""
    from triton_dist_tpu.tools import xplane
    from triton_dist_tpu.tools.xplane import Event

    planes = {
        "/device:TPU:0": {
            # Compute-only row: one fusion op [0, 100).
            "XLA Ops": [Event("fusion.1", 0, 100)],
            # Dual-matched row ("stream" + "queue"): one DMA [200, 300) —
            # disjoint from compute, so any nonzero overlap here could only
            # come from double-counting the row on both sides.
            "Stream #1 queue": [Event("dma.copy", 200, 100)],
        },
        "/host:CPU": {"threads": [Event("noise", 0, 1000)]},
    }
    monkeypatch.setattr(xplane, "latest_capture", lambda d: "fake.xplane.pb")
    monkeypatch.setattr(xplane, "parse_xspace", lambda p: planes)
    rep = xplane.overlap_report(str(tmp_path))
    assert rep["dual_matched_lines"] == ["Stream #1 queue"]
    assert rep["dma_lines_seen"] == ["Stream #1 queue"]
    assert rep["compute_ps"] == 100
    assert rep["dma_ps"] == 100
    assert rep["overlap_ps"] == 0 and rep["overlap_frac_of_dma"] == 0.0
    # Genuine overlap still accounts: shift the DMA under the compute row.
    planes["/device:TPU:0"]["Stream #1 queue"] = [Event("dma.copy", 50, 100)]
    rep2 = xplane.overlap_report(str(tmp_path))
    assert rep2["overlap_ps"] == 50 and rep2["overlap_frac_of_dma"] == 0.5


def test_gemm_ar_crossover_agreed(tmp_path, monkeypatch):
    """GEMM-AR AUTO routing reads its M crossover only through
    ``agreed_cfg_value`` (cross-rank agreed; single-process degenerate =
    plain hit) and falls back to the static default on miss or malformed
    entries — same contract as the ar_crossover satellite fix."""
    from triton_dist_tpu.kernels.gemm_allreduce import (
        DEFAULT_GEMM_AR_CROSSOVER_M,
        GemmARMethod,
        gemm_ar_crossover_m,
        get_auto_gemm_ar_method,
    )
    from triton_dist_tpu.tools import tune

    monkeypatch.setenv("TDT_TUNE_CACHE", str(tmp_path / "cache.json"))
    tune._default_cache = None

    # Cold cache → static default, routing obeys it.
    assert gemm_ar_crossover_m(8) == DEFAULT_GEMM_AR_CROSSOVER_M
    assert (get_auto_gemm_ar_method(DEFAULT_GEMM_AR_CROSSOVER_M, 8)
            is GemmARMethod.LL_ONE_SHOT)
    # The next M whose ring chunks are whole sublane tiles (world * 8 rows).
    assert (get_auto_gemm_ar_method(DEFAULT_GEMM_AR_CROSSOVER_M + 64, 8)
            is GemmARMethod.PALLAS_FUSED)

    # The bench's emitted entry merges in and moves the routing point.
    tune.merge_entries({
        "gemm_ar_crossover|world=8": {
            "cfg": {"crossover_m": 256, "default_was": DEFAULT_GEMM_AR_CROSSOVER_M},
            "time_s": 1e-5, "version": "x"},
    })
    tune._default_cache = None  # drop the memoized miss
    assert gemm_ar_crossover_m(8) == 256
    assert get_auto_gemm_ar_method(256, 8) is GemmARMethod.LL_ONE_SHOT
    assert get_auto_gemm_ar_method(256 + 64, 8) is GemmARMethod.PALLAS_FUSED
    # Other world sizes are untouched by the world=8 entry.
    assert gemm_ar_crossover_m(4) == DEFAULT_GEMM_AR_CROSSOVER_M

    # A malformed entry (missing the field) falls back, never raises.
    tune.merge_entries({
        "gemm_ar_crossover|world=4": {
            "cfg": {"wrong_field": 1}, "time_s": 1e-5, "version": "x"},
    })
    tune._default_cache = None
    assert gemm_ar_crossover_m(4) == DEFAULT_GEMM_AR_CROSSOVER_M


def test_prefill_crossovers_agreed(tmp_path, monkeypatch):
    """The PR-4 prefill pair — AG-GEMM and GEMM-RS AUTO routing — reads its
    M crossovers only through ``agreed_cfg_value`` from the
    ``{ag_gemm,gemm_rs}_crossover|world=N`` entries bench.py's
    ``prefill_overlap`` section emits, with the static defaults on miss or
    malformed entries (same contract as ``test_gemm_ar_crossover_agreed``)."""
    import jax.numpy as jnp

    from triton_dist_tpu.kernels.allgather_gemm import (
        DEFAULT_AG_GEMM_CROSSOVER_M,
        AGGemmMethod,
        ag_gemm_crossover_m,
        get_auto_ag_gemm_method,
    )
    from triton_dist_tpu.kernels.gemm_reduce_scatter import (
        DEFAULT_GEMM_RS_CROSSOVER_M,
        GemmRSMethod,
        gemm_rs_crossover_m,
        get_auto_gemm_rs_method,
    )
    from triton_dist_tpu.tools import tune

    monkeypatch.setenv("TDT_TUNE_CACHE", str(tmp_path / "cache.json"))
    tune._default_cache = None

    # Cold cache → static defaults drive both routing points.
    assert ag_gemm_crossover_m(8) == DEFAULT_AG_GEMM_CROSSOVER_M
    assert gemm_rs_crossover_m(8) == DEFAULT_GEMM_RS_CROSSOVER_M
    assert (get_auto_ag_gemm_method(
                DEFAULT_AG_GEMM_CROSSOVER_M + 8, 64, 64, jnp.float32, 8)
            is AGGemmMethod.PALLAS_FUSED)
    assert get_auto_gemm_rs_method(512, 8) is GemmRSMethod.PALLAS_FUSED

    # The bench's emitted entries merge in and move both routing points.
    tune.merge_entries({
        "ag_gemm_crossover|world=8": {
            "cfg": {"crossover_m": 128,
                    "default_was": DEFAULT_AG_GEMM_CROSSOVER_M},
            "time_s": 1e-5, "version": "x"},
        "gemm_rs_crossover|world=8": {
            "cfg": {"crossover_m": 1024,
                    "default_was": DEFAULT_GEMM_RS_CROSSOVER_M},
            "time_s": 1e-5, "version": "x"},
    })
    tune._default_cache = None  # drop the memoized miss
    assert ag_gemm_crossover_m(8) == 128
    assert gemm_rs_crossover_m(8) == 1024
    assert (get_auto_ag_gemm_method(128, 64, 64, jnp.float32, 8)
            is AGGemmMethod.XLA_RING)
    assert (get_auto_ag_gemm_method(192, 64, 64, jnp.float32, 8)
            is AGGemmMethod.PALLAS_FUSED)
    assert get_auto_gemm_rs_method(1024, 8) is GemmRSMethod.XLA_RING
    assert get_auto_gemm_rs_method(1024 + 64, 8) is GemmRSMethod.PALLAS_FUSED
    # Other world sizes are untouched by the world=8 entries.
    assert ag_gemm_crossover_m(4) == DEFAULT_AG_GEMM_CROSSOVER_M
    assert gemm_rs_crossover_m(4) == DEFAULT_GEMM_RS_CROSSOVER_M

    # Malformed entries (missing the field) fall back, never raise.
    tune.merge_entries({
        "ag_gemm_crossover|world=4": {
            "cfg": {"wrong_field": 1}, "time_s": 1e-5, "version": "x"},
        "gemm_rs_crossover|world=4": {
            "cfg": {"wrong_field": 1}, "time_s": 1e-5, "version": "x"},
    })
    tune._default_cache = None
    assert ag_gemm_crossover_m(4) == DEFAULT_AG_GEMM_CROSSOVER_M
    assert gemm_rs_crossover_m(4) == DEFAULT_GEMM_RS_CROSSOVER_M


def test_xplane_parse_and_overlap(tmp_path):
    """The dependency-free .xplane.pb parser (r4 verdict missing #4's
    unexplored alternative — XProf duration rows wired into an overlap
    assertion): a real capture of a jitted op parses into planes/lines/
    events with positive durations, and the interval-overlap accounting is
    exact on synthetic data."""
    import jax.numpy as jnp

    from triton_dist_tpu.tools import profile_op
    from triton_dist_tpu.tools.xplane import (
        Event,
        latest_capture,
        overlap_ps,
        parse_xspace,
        select_events,
    )

    d = profile_op(lambda x: jnp.tanh(x @ x), (jnp.ones((256, 256)),),
                   str(tmp_path / "xp"))
    planes = parse_xspace(latest_capture(d))
    assert planes, "no planes parsed"
    # The CPU sim always carries a host plane with real thread timelines.
    host = [p for p in planes if "host" in p.lower()]
    assert host, planes.keys()
    evs = select_events(planes, "host", ".", ".")
    assert evs and any(e.dur_ps > 0 for e in evs)
    # The jitted computation itself must appear somewhere in the capture.
    all_names = {e.name for e in evs}
    assert any("tanh" in n or "jit" in n.lower() for n in all_names), (
        sorted(all_names)[:40])

    # Exact synthetic overlap accounting: compute [0,100)+[200,300),
    # dma [50,250) → overlap = 50 + 50.
    comp = [Event("c", 0, 100), Event("c", 200, 100)]
    dma = [Event("d", 50, 200)]
    assert overlap_ps(comp, dma) == 100
    # Self-overlapping rows are merged first (no double counting).
    comp2 = comp + [Event("c", 0, 100)]
    assert overlap_ps(comp2, dma) == 100
    # Disjoint → zero.
    assert overlap_ps([Event("c", 0, 10)], [Event("d", 20, 10)]) == 0


# ------------------------------------------------- tuned-defaults lint


def test_tuned_defaults_lint_repo_is_clean():
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_backend_maps_lint_repo_is_clean():
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "scripts/check_backend_maps.py"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_backend_maps_lint_flags_drift(tmp_path):
    """A map missing a backend, a stale extra entry, a non-literal map, and
    a demoted DECODE_MODE['mega'] / VERIFY_MODE['mega'] are each flagged
    with diagnostics."""
    import subprocess
    import sys

    def run(src):
        bad = tmp_path / "engine_bad.py"
        bad.write_text(src)
        return subprocess.run(
            [sys.executable, "scripts/check_backend_maps.py", str(bad)],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )

    base = '_BACKENDS = ("xla", "dist", "dist_ar", "mega")\n'
    ok_maps = (
        'PREFILL_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", "mega": "dist_ar"}\n'
        'DECODE_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", "mega": "mega"}\n'
        'CHUNK_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", "mega": "dist_ar"}\n'
        'VERIFY_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", "mega": "mega"}\n'
    )
    r = run(base + ok_maps)
    assert r.returncode == 0, r.stdout + r.stderr

    # A backend added to _BACKENDS but forgotten in one map.
    r = run(base + ok_maps.replace(', "mega": "dist_ar"}\nDECODE', '}\nDECODE', 1))
    assert r.returncode == 1
    assert "PREFILL_MODE missing backend" in r.stdout

    # A stale entry no longer in _BACKENDS.
    r = run(base + ok_maps.replace(
        'CHUNK_MODE = {"xla": "xla"', 'CHUNK_MODE = {"legacy": "xla", "xla": "xla"'))
    assert r.returncode == 1
    assert "CHUNK_MODE has unknown backend" in r.stdout

    # The hard routing invariants: neither decode nor the speculative
    # verify step may demote mega off the fused path.
    r = run(base + ok_maps.replace('"mega": "mega"', '"mega": "dist_ar"', 1))
    assert r.returncode == 1
    assert "DECODE_MODE must route 'mega' to 'mega'" in r.stdout

    r = run(base + ok_maps.replace(
        'VERIFY_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", '
        '"mega": "mega"}',
        'VERIFY_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", '
        '"mega": "dist_ar"}'))
    assert r.returncode == 1
    assert "VERIFY_MODE must route 'mega' to 'mega'" in r.stdout

    # Non-literal maps defeat static linting and are rejected outright.
    r = run(base + ok_maps.replace(
        'PREFILL_MODE = {"xla": "xla"', 'PREFILL_MODE = {"xla": some_mode()'))
    assert r.returncode == 1
    assert "pure literal" in r.stdout


def test_tuned_defaults_lint_flags_violations(tmp_path):
    """A resolver that reads the cache rank-locally, a getter that skips
    ``agreed_cfg_value``, and an AUTO resolver that never reaches it are
    each flagged with file:line diagnostics."""
    import subprocess
    import sys

    bad = tmp_path / "bad_resolver.py"
    bad.write_text(
        "DEFAULT_FOO_CROSSOVER_M = 8\n"
        "\n"
        "def foo_crossover_m(world):\n"
        "    cache = get_cache()\n"
        "    return cache.get('foo_crossover|world=8', DEFAULT_FOO_CROSSOVER_M)\n"
        "\n"
        "def get_auto_foo_method(m, world):\n"
        "    return 'fused' if m > foo_crossover_m(world) else 'll'\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py", str(bad)],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 1
    assert "rank-local cache read" in r.stdout
    assert "foo_crossover_m" in r.stdout
    assert "get_auto_foo_method" in r.stdout

    # The blessed shape passes: getter calls agreed_cfg_value, resolver
    # reaches it through the getter.
    good = tmp_path / "good_resolver.py"
    good.write_text(
        "DEFAULT_FOO_CROSSOVER_M = 8\n"
        "\n"
        "def foo_crossover_m(world):\n"
        "    from triton_dist_tpu.tools.tune import agreed_cfg_value\n"
        "    return agreed_cfg_value(\n"
        "        f'foo_crossover|world={world}', 'crossover_m',\n"
        "        DEFAULT_FOO_CROSSOVER_M)\n"
        "\n"
        "def get_auto_foo_method(m, world):\n"
        "    return 'fused' if m > foo_crossover_m(world) else 'll'\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py", str(good)],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_tuned_defaults_lint_ep_resolver_fixture(tmp_path):
    """The EP-MoE resolver shape specifically: a rank-local read of the
    ``ep_a2a_crossover|world=N`` key is flagged; the blessed
    ``agreed_cfg_value`` read (the shape ``low_latency_a2a.py`` ships)
    passes."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = tmp_path / "bad_ep_resolver.py"
    bad.write_text(
        "DEFAULT_EP_A2A_CROSSOVER_T = 32\n"
        "\n"
        "def get_auto_ep_moe_method(tokens, world):\n"
        "    cache = get_cache()\n"
        "    t = cache.get('ep_a2a_crossover|world=4', DEFAULT_EP_A2A_CROSSOVER_T)\n"
        "    return 'low_latency' if tokens <= t else 'fused'\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py", str(bad)],
        capture_output=True, text=True, cwd=repo,
    )
    assert r.returncode == 1
    assert "rank-local cache read" in r.stdout
    assert "get_auto_ep_moe_method" in r.stdout

    good = tmp_path / "good_ep_resolver.py"
    good.write_text(
        "DEFAULT_EP_A2A_CROSSOVER_T = 32\n"
        "\n"
        "def ep_a2a_crossover_tokens(world):\n"
        "    from triton_dist_tpu.tools.tune import agreed_cfg_value\n"
        "    return agreed_cfg_value(\n"
        "        f'ep_a2a_crossover|world={world}', 'crossover_t',\n"
        "        DEFAULT_EP_A2A_CROSSOVER_T)\n"
        "\n"
        "def get_auto_ep_moe_method(tokens, world):\n"
        "    return ('low_latency' if tokens <= ep_a2a_crossover_tokens(world)\n"
        "            else 'fused')\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py", str(good)],
        capture_output=True, text=True, cwd=repo,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_tuned_defaults_lint_wire_keyed_resolver_fixture(tmp_path):
    """The dtype-aware resolver shape the quantized collectives ship: a
    ``wire``-keyed crossover getter (``…|world=N|wire=fp8``) must still call
    ``agreed_cfg_value`` itself, and the AUTO resolver must reach it through
    the getter; a wire-keyed rank-local ``cache.get`` is flagged."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    good = tmp_path / "good_wire_resolver.py"
    good.write_text(
        "DEFAULT_AG_GEMM_CROSSOVER_M = 256\n"
        "\n"
        "def ag_gemm_crossover_m(world, wire=None):\n"
        "    from triton_dist_tpu.tools.tune import agreed_cfg_value\n"
        "    key = f'ag_gemm_crossover|world={world}'\n"
        "    if wire is not None:\n"
        "        key += f'|wire={wire}'\n"
        "    return agreed_cfg_value(key, 'crossover_m',\n"
        "                            DEFAULT_AG_GEMM_CROSSOVER_M)\n"
        "\n"
        "def get_auto_ag_gemm_method(m, world, wire=None):\n"
        "    return ('fused' if m > ag_gemm_crossover_m(world, wire)\n"
        "            else 'xla_ring')\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py", str(good)],
        capture_output=True, text=True, cwd=repo,
    )
    assert r.returncode == 0, r.stdout + r.stderr

    bad = tmp_path / "bad_wire_resolver.py"
    bad.write_text(
        "def get_auto_ag_gemm_method(m, world, wire=None):\n"
        "    cache = get_cache()\n"
        "    t = cache.get('ag_gemm_crossover|world=8|wire=fp8', 256)\n"
        "    return 'fused' if m > t else 'xla_ring'\n"
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_tuned_defaults.py", str(bad)],
        capture_output=True, text=True, cwd=repo,
    )
    assert r.returncode == 1
    assert "rank-local cache read" in r.stdout


def test_tuned_defaults_required_resolver_drift_guard(capsys, monkeypatch):
    """The default sweep pins the EP resolver by NAME: renaming or deleting
    ``get_auto_ep_moe_method`` (dodging the per-function reach check
    entirely) must fail the lint, and the guard set must actually contain
    both shipped resolvers."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_ctd_drift", os.path.join(repo, "scripts", "check_tuned_defaults.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    assert "get_auto_ep_moe_method" in mod.REQUIRED_RESOLVERS
    assert "get_auto_gemm_ar_method" in mod.REQUIRED_RESOLVERS
    # The wire-dtype-aware resolvers (quantized operand AUTO routing) are
    # pinned too: their |wire=fp8 crossovers must stay cross-rank agreed.
    assert "get_auto_ag_gemm_method" in mod.REQUIRED_RESOLVERS
    assert "get_auto_gemm_rs_method" in mod.REQUIRED_RESOLVERS
    assert mod.main([]) == 0

    monkeypatch.setattr(
        mod, "REQUIRED_RESOLVERS",
        set(mod.REQUIRED_RESOLVERS) | {"get_auto_vanished_method"},
    )
    capsys.readouterr()
    assert mod.main([]) == 1
    out = capsys.readouterr().out
    assert "get_auto_vanished_method" in out
    assert "REQUIRED_RESOLVERS" in out


# ---------------------------------------------------- bench regression gate


def _run_gate(*args):
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, "scripts/check_bench_regression.py", *args],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def _write_bench(tmp_path, name, shape, metrics):
    """One BENCH fixture in any of the three accepted shapes."""
    primary_name, primary_value = "flash_attn_causal_bf16_tflops", metrics.pop(
        "flash_attn_causal_bf16_tflops"
    )
    if shape == "snapshot":
        doc = {"schema": 1,
               "primary": {"metric": primary_name, "value": primary_value},
               "extra": metrics}
    elif shape == "driver":
        doc = {"n": 6, "cmd": "python bench.py", "rc": 0, "tail": "...",
               "parsed": {"metric": primary_name, "value": primary_value,
                          "extra": metrics}}
    else:  # raw BENCH line
        doc = {"metric": primary_name, "value": primary_value,
               "unit": "TFLOP/s", "extra": metrics}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


BASE_METRICS = {
    "flash_attn_causal_bf16_tflops": 100.0,
    "serving_burst_tokens_per_s": 50.0,
    "serving_burst_ttft_p99_ms": 20.0,
    "gdn_speedup_vs_scan": 3.0,
    "dead_section_tflops": 0.0,   # a section that did not run: never gated
    "serving_requests": 16,        # informational: never gated
}


def test_bench_regression_gate_passes_unchanged_pair(tmp_path):
    """Acceptance: an unchanged pair exits 0 — across all three accepted
    input shapes, including a shape-mixed comparison."""
    a = _write_bench(tmp_path, "a.json", "snapshot", dict(BASE_METRICS))
    b = _write_bench(tmp_path, "b.json", "driver", dict(BASE_METRICS))
    c = _write_bench(tmp_path, "c.json", "raw", dict(BASE_METRICS))
    for base, cand in ((a, a), (a, b), (b, c)):
        r = _run_gate(base, cand)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "0 regression(s)" in r.stdout


def test_bench_regression_gate_catches_tokens_per_s_drop(tmp_path):
    """Acceptance: a >10% tokens/s regression exits non-zero and names the
    regressed metric; jitter inside the band stays green."""
    base = _write_bench(tmp_path, "base.json", "snapshot", dict(BASE_METRICS))
    regressed = dict(BASE_METRICS)
    regressed["serving_burst_tokens_per_s"] = 40.0   # -20% < -10% band
    cand = _run_gate(
        base, _write_bench(tmp_path, "regr.json", "driver", regressed)
    )
    assert cand.returncode == 1, cand.stdout + cand.stderr
    assert "REGRESSION" in cand.stdout
    assert "serving_burst_tokens_per_s" in cand.stdout

    jitter = dict(BASE_METRICS)
    jitter["serving_burst_tokens_per_s"] = 46.0      # -8% inside the band
    jitter["flash_attn_causal_bf16_tflops"] = 108.0  # +8% improvement
    r = _run_gate(base, _write_bench(tmp_path, "jit.json", "snapshot", jitter))
    assert r.returncode == 0, r.stdout


def test_bench_regression_gate_directions_and_skips(tmp_path):
    """Lower-is-better metrics gate on INCREASES; zero-baseline and
    informational metrics never gate."""
    base = _write_bench(tmp_path, "base.json", "snapshot", dict(BASE_METRICS))
    worse = dict(BASE_METRICS)
    worse["serving_burst_ttft_p99_ms"] = 40.0   # latency doubled -> bad
    worse["dead_section_tflops"] = 999.0        # 0.0 baseline: skipped
    worse["serving_requests"] = 99              # informational: skipped
    r = _run_gate(base, _write_bench(tmp_path, "w.json", "snapshot", worse))
    assert r.returncode == 1
    assert "serving_burst_ttft_p99_ms" in r.stdout
    assert "zero-baseline" in r.stdout


def test_bench_regression_gate_traffic_bytes_lower_is_better(tmp_path):
    """``*_wire_bytes*``/``*_hbm_bytes*`` are traffic volumes the quantized
    collectives exist to shrink: growth gates as a regression, shrink is an
    improvement — and the bare-suffix and ``_total`` spellings both match."""
    metrics = dict(BASE_METRICS)
    metrics["serving_quant_ag_wire_bytes"] = 1.0e6
    metrics["decode_kv_hbm_bytes_total"] = 4.0e6
    base = _write_bench(tmp_path, "base.json", "snapshot", dict(metrics))

    worse = dict(metrics)
    worse["serving_quant_ag_wire_bytes"] = 2.0e6   # wire doubled -> bad
    r = _run_gate(base, _write_bench(tmp_path, "w.json", "snapshot", worse))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "serving_quant_ag_wire_bytes" in r.stdout

    better = dict(metrics)
    better["serving_quant_ag_wire_bytes"] = 0.25e6  # fp8 wire: -75%
    better["decode_kv_hbm_bytes_total"] = 1.0e6     # int8 KV walk: -75%
    r = _run_gate(base, _write_bench(tmp_path, "b.json", "snapshot", better))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("improved") >= 2
    out_lines = [l for l in r.stdout.splitlines() if "REGRESSION" in l]
    assert not any("dead_section" in l or "serving_requests" in l
                   for l in out_lines)


def test_bench_regression_gate_slo_direction_rules(tmp_path):
    """The SLO engine's metric families are gated, not informational:
    *_goodput* gates on drops (higher is better), *_p999_* gates on
    increases (lower is better)."""
    # _write_bench pops the primary key from its dict — build each fresh.
    slo = {"serving_burst_goodput_frac": 1.0, "digest_oracle_p999_ms": 100.0}
    base = _write_bench(tmp_path, "base.json", "snapshot",
                        {**BASE_METRICS, **slo})

    worse = {**BASE_METRICS, **slo}
    worse["serving_burst_goodput_frac"] = 0.5    # goodput halved -> bad
    worse["digest_oracle_p999_ms"] = 200.0       # tail doubled -> bad
    r = _run_gate(base, _write_bench(tmp_path, "w.json", "snapshot", worse))
    assert r.returncode == 1
    assert "serving_burst_goodput_frac" in r.stdout
    assert "digest_oracle_p999_ms" in r.stdout

    better = {**BASE_METRICS, **slo}
    better["serving_burst_goodput_frac"] = 2.0   # improvements never gate
    better["digest_oracle_p999_ms"] = 50.0
    r = _run_gate(base, _write_bench(tmp_path, "b.json", "snapshot", better))
    assert r.returncode == 0, r.stdout


def test_bench_regression_gate_tolerance_flags(tmp_path):
    base = _write_bench(tmp_path, "base.json", "snapshot", dict(BASE_METRICS))
    cand_metrics = dict(BASE_METRICS)
    cand_metrics["serving_burst_tokens_per_s"] = 42.0  # -16%
    cand = _write_bench(tmp_path, "cand.json", "snapshot", cand_metrics)
    # Default band (10%): regression. Widened band: green — globally or
    # for that one metric.
    assert _run_gate(base, cand).returncode == 1
    assert _run_gate(base, cand, "--tol", "0.25").returncode == 0
    assert _run_gate(
        base, cand, "--tol-metric", "serving_burst_tokens_per_s=0.25"
    ).returncode == 0


def test_bench_regression_gate_error_paths(tmp_path):
    base = _write_bench(tmp_path, "base.json", "snapshot", dict(BASE_METRICS))
    assert _run_gate().returncode == 2                      # usage
    assert _run_gate(base).returncode == 2                  # one file only
    bad = tmp_path / "bad.json"
    bad.write_text("not json {")
    assert _run_gate(base, str(bad)).returncode == 2        # parse error
    assert _run_gate(base, str(tmp_path / "nope.json")).returncode == 2
    # Vacuous diffs can be rejected: no common gateable metrics.
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"schema": 1, "primary": {}, "extra": {}}))
    assert _run_gate(base, str(empty), "--require-common", "1").returncode == 2


# ------------------------------------------------------- env-knob lint


def _run_knob_lint(*args):
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, "scripts/check_env_knobs.py", *args],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def test_env_knob_lint_repo_is_clean():
    r = _run_knob_lint()
    assert r.returncode == 0, r.stdout + r.stderr


def test_env_knob_lint_flags_undocumented_and_dynamic(tmp_path):
    """Every read shape is recognized (helpers, environ.get, subscript,
    membership), undocumented knobs are flagged with the read site, and a
    dynamic knob name is rejected unless waived."""
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "knobs.md").write_text(
        "| knob | meaning |\n|---|---|\n"
        "| `TDT_DOCUMENTED_A` | present |\n"
        "| `TDT_DOCUMENTED_B` | present |\n"
    )
    bad = tmp_path / "bad_knobs.py"
    bad.write_text(
        "import os\n"
        "from triton_dist_tpu.runtime.utils import get_int_env\n"
        "def f(name):\n"
        "    a = get_int_env('TDT_DOCUMENTED_A', 1)\n"          # OK
        "    b = os.environ.get('TDT_DOCUMENTED_B')\n"          # OK
        "    c = os.environ['TDT_MISSING_SUBSCRIPT']\n"         # undocumented
        "    d = 'TDT_MISSING_MEMBER' in os.environ\n"          # undocumented
        "    e = os.getenv('TDT_MISSING_GETENV')\n"             # undocumented
        "    f = get_int_env(name, 0)\n"                        # dynamic
        "    g = get_int_env(name, 0)  # env-knob-ok: waived\n"  # waived
        "    return a, b, c, d, e, f, g\n"
    )
    r = _run_knob_lint(str(bad), "--docs", str(docs))
    assert r.returncode == 1, r.stdout + r.stderr
    for knob in ("TDT_MISSING_SUBSCRIPT", "TDT_MISSING_MEMBER",
                 "TDT_MISSING_GETENV"):
        assert knob in r.stdout, r.stdout
    assert "dynamic env-knob name" in r.stdout
    assert r.stdout.count("bad_knobs.py:9") == 1, r.stdout   # dynamic flagged
    assert "bad_knobs.py:10" not in r.stdout, r.stdout       # waiver honored
    for knob in ("TDT_DOCUMENTED_A", "TDT_DOCUMENTED_B"):
        assert knob not in r.stdout, r.stdout

    # Documenting the stragglers turns the same tree green.
    (docs / "knobs.md").write_text(
        "| `TDT_DOCUMENTED_A` | `TDT_DOCUMENTED_B` |\n"
        "| `TDT_MISSING_SUBSCRIPT` | `TDT_MISSING_MEMBER` |\n"
        "| `TDT_MISSING_GETENV` | |\n"
    )
    bad.write_text(bad.read_text().replace(
        "    f = get_int_env(name, 0)\n", ""
    ))
    r = _run_knob_lint(str(bad), "--docs", str(docs))
    assert r.returncode == 0, r.stdout + r.stderr
