"""Resilience layer tests: fault injection, bounded waits, degraded fallback.

Two tiers:

* Host tests — watchdog, degradation registry, sticky AUTO routing, env-var
  hardening, tune-cache atomicity, coordinator-connect retry, and the
  bounded-wait lint. No device kernels; these run anywhere.
* ``@pytest.mark.chaos`` tests — interpret-mode collective kernels driven
  under each :class:`FaultPlan` kind on the ctx4 mesh: a delayed rank must
  complete correctly, a dropped rank must produce a bounded-wait abort (no
  hang) naming the stalled phase — and, for the fused GEMM+AR ring, the
  exact peer rank — and the NEXT call must transparently serve correct
  results through the sticky XLA fallback.
"""

import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.runtime import resilience
from triton_dist_tpu.runtime.resilience import (
    CollectiveAbortError,
    CollectiveTimeoutError,
    CollectiveWatchdog,
    FaultKind,
    FaultPlan,
)

LINT = "scripts/check_bounded_waits.py"


@pytest.fixture(autouse=True)
def _clean_resilience():
    """Every test starts and ends with no sticky degradation; clear caches on
    the way out so a degraded trace from one test can't leak into the next."""
    resilience.reset_degradation()
    yield
    resilience.reset_degradation()
    jax.clear_caches()


def shard(ctx, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
    )


# ------------------------------------------------------------- phase registry


def test_phase_registry():
    assert resilience.phase_id("rs_recv") == resilience.phase_id("rs_recv")
    new = resilience.phase_id("some_new_phase")
    assert resilience.phase_name(new) == "some_new_phase"
    assert resilience.phase_name(10_000) == "unknown"


def test_describe_status():
    ok = [resilience.STATUS_OK, 0, -1, 0]
    assert resilience.describe_status(ok) is None
    bad = [resilience.STATUS_ABORT, resilience.phase_id("rs_recv"), 2, 77]
    msg = resilience.describe_status(bad)
    assert "rs_recv" in msg and "peer rank 2" in msg and "77 polls" in msg
    anon = [resilience.STATUS_ABORT, resilience.phase_id("barrier"), -1, 5]
    assert "unattributable" in resilience.describe_status(anon)


def test_record_status_registers_and_raises():
    words = [resilience.STATUS_ABORT, resilience.phase_id("ag_recv"), 3, 123]
    with pytest.raises(CollectiveAbortError, match="peer rank 3"):
        resilience.record_status(words, feature="allgather", kernel="_ring_ag_kernel")
    ab = resilience.last_abort()
    assert ab.feature == "allgather" and ab.phase == "ag_recv" and ab.peer == 3
    assert resilience.is_degraded("allgather")
    # OK status is a no-op.
    resilience.record_status([0, 0, -1, 0], feature="x", kernel="k")


def test_consume_status_eager_abort():
    status = jnp.array(
        [resilience.STATUS_ABORT, resilience.phase_id("rs_recv"), 1, 9], jnp.int32
    )
    with pytest.raises(Exception, match="peer rank 1"):
        resilience.consume_status(status, feature="reduce_scatter", kernel="k")
    assert resilience.is_degraded("reduce_scatter")


# --------------------------------------------------------------- fault plans


def test_fault_plan_context_and_wait_bound():
    assert resilience.active_plan() is None
    with resilience.fault_plan("drop_peer", rank=2, wait_bound=500) as plan:
        assert resilience.active_plan() is plan
        assert plan.kind is FaultKind.DROP_PEER  # str coerced to enum
        assert resilience.wait_bound() == 500  # plan override
        assert resilience.wait_bound(7) == 7  # explicit arg wins
    assert resilience.active_plan() is None


def test_wait_bound_env(monkeypatch):
    monkeypatch.setenv("TDT_WAIT_BOUND_ITERS", "1234")
    assert resilience.wait_bound() == 1234
    monkeypatch.setenv("TDT_WAIT_BOUND_ITERS", "0")  # 0 = unbounded waits
    assert resilience.wait_bound() == 0


def test_wait_bound_follows_the_launch_mode(monkeypatch):
    """Interpret mode (no ``semaphore_read`` rule in the interpreter): the
    default is 0, the plain blocking wait. Compiled through Mosaic — here a
    deviceless compile under ``force_mosaic`` — the hardware poll cap."""
    from triton_dist_tpu.runtime.platform import force_mosaic

    monkeypatch.delenv("TDT_WAIT_BOUND_ITERS", raising=False)
    assert resilience.wait_bound() == resilience.DEFAULT_WAIT_BOUND_SIM == 0
    with force_mosaic():
        assert resilience.wait_bound() == resilience.DEFAULT_WAIT_BOUND_HW
        assert resilience.wait_bound(9) == 9
    assert resilience.wait_bound() == 0


def test_dma_semaphore_poll_target_unit():
    """The chip counts a DMA semaphore in 32-byte units, the interpreter in
    bytes; a poll target in the wrong unit never succeeds."""
    from triton_dist_tpu.runtime.platform import force_mosaic
    from triton_dist_tpu.shmem.kernel import DMA_SEM_UNIT_BYTES, _dma_sem_count

    assert _dma_sem_count(4096) == 4096
    with force_mosaic():
        assert DMA_SEM_UNIT_BYTES == 32
        assert _dma_sem_count(4096) == 128  # as read on the chip
        assert _dma_sem_count(1 << 20) == 32768
        with pytest.raises(ValueError):
            _dma_sem_count(100)


# ----------------------------------------------------- degradation + routing


def test_degradation_registry():
    assert not resilience.any_degraded()
    resilience.mark_degraded("gemm_ar", "test reason")
    resilience.mark_degraded("gemm_ar", "second reason ignored")
    assert resilience.is_degraded("gemm_ar")
    assert not resilience.is_degraded("allgather")
    assert resilience.degraded_reasons() == {"gemm_ar": "test reason"}
    resilience.reset_degradation()
    assert not resilience.any_degraded()


def test_global_collectives_flag_degrades_everything():
    resilience.mark_degraded("collectives", "watchdog tripped")
    assert resilience.is_degraded("gemm_ar")
    assert resilience.is_degraded("allgather")


def test_auto_routing_goes_sticky_xla():
    from triton_dist_tpu.kernels.allgather import AllGatherMethod, get_auto_all_gather_method
    from triton_dist_tpu.kernels.allreduce import AllReduceMethod, get_auto_all_reduce_method
    from triton_dist_tpu.kernels.gemm_allreduce import GemmARMethod, get_auto_gemm_ar_method

    # Healthy process: AUTO picks kernels.
    assert get_auto_gemm_ar_method(8, 4) is not GemmARMethod.XLA
    assert get_auto_all_gather_method(1024, 4) is not AllGatherMethod.XLA
    assert get_auto_all_reduce_method(1024, 4) is not AllReduceMethod.XLA

    resilience.mark_degraded("gemm_ar", "chaos")
    assert get_auto_gemm_ar_method(8, 4) is GemmARMethod.XLA
    assert get_auto_gemm_ar_method(4096, 4) is GemmARMethod.XLA

    resilience.mark_degraded("allgather", "chaos")
    assert get_auto_all_gather_method(1024, 4) is AllGatherMethod.XLA
    # Two-shot AR composes RS+AG, so the allgather trip routes AR too.
    assert get_auto_all_reduce_method(1024, 4) is AllReduceMethod.XLA

    resilience.reset_degradation()
    assert get_auto_gemm_ar_method(8, 4) is not GemmARMethod.XLA


def test_tp_layer_mode_remap():
    from triton_dist_tpu.layers.tp import _tp_mode

    assert _tp_mode("dist_ar") == "dist_ar"
    resilience.mark_degraded("gemm_ar", "chaos")
    assert _tp_mode("dist_ar") == "xla"
    # "dist" is seq-sharded (different data contract): not remapped here —
    # its kernels degrade individually through the AUTO gates.
    assert _tp_mode("dist") == "dist"
    assert _tp_mode("xla") == "xla"


# ------------------------------------------------------------------ watchdog


def test_watchdog_disabled_is_direct_call():
    wd = CollectiveWatchdog(timeout_ms=0)
    assert wd.call(lambda a, b: a + b, 1, 2) == 3
    assert not resilience.any_degraded()


def test_watchdog_env_defaults(monkeypatch):
    monkeypatch.setenv("TDT_COLL_TIMEOUT_MS", "150")
    monkeypatch.setenv("TDT_COLL_RETRIES", "5")
    wd = CollectiveWatchdog()
    assert wd.timeout_ms == 150 and wd.retries == 5


def test_watchdog_fast_fn_passes_through():
    wd = CollectiveWatchdog(timeout_ms=5_000, retries=0)
    assert wd.call(lambda: 42) == 42
    assert not resilience.any_degraded()


def test_watchdog_propagates_fn_errors():
    wd = CollectiveWatchdog(timeout_ms=5_000, retries=0)
    with pytest.raises(ValueError, match="boom"):
        wd.call(lambda: (_ for _ in ()).throw(ValueError("boom")))


def test_watchdog_timeout_raises_and_degrades():
    wd = CollectiveWatchdog(timeout_ms=30, retries=1, backoff=1.0, feature="collectives")
    with pytest.raises(CollectiveTimeoutError, match="watchdog"):
        wd.call(time.sleep, 0.5)
    assert resilience.is_degraded("gemm_ar")  # global flag covers everything


def test_watchdog_timeout_runs_fallback():
    wd = CollectiveWatchdog(timeout_ms=30, retries=0, feature="collectives")
    assert wd.call(lambda s: time.sleep(s), 0.5, fallback=lambda s: "fell back") == "fell back"
    assert resilience.any_degraded()


# ------------------------------------------------------------ engine fallback


def _stub_engine():
    from triton_dist_tpu.models.engine import Engine

    eng = Engine.__new__(Engine)
    eng.backend = "dist"
    builds = []

    def fake_build(backend):
        builds.append(backend)
        eng.backend = backend

    eng._build = fake_build
    return eng, builds


def test_engine_serve_retries_on_xla_after_abort():
    eng, builds = _stub_engine()

    def serve_once(ids, n, key):
        if eng.backend != "xla":
            resilience.mark_degraded("gemm_ar", "injected abort")
            raise RuntimeError("collective aborted mid-serve")
        return "served-on-xla"

    eng._serve_once = serve_once
    assert eng.serve("ids", 4) == "served-on-xla"
    assert builds == ["xla"]


def test_engine_serve_reraises_when_not_degraded():
    eng, builds = _stub_engine()

    def serve_once(ids, n, key):
        raise ValueError("unrelated bug")

    eng._serve_once = serve_once
    with pytest.raises(ValueError, match="unrelated bug"):
        eng.serve("ids", 4)
    assert builds == []


def test_engine_serve_watchdog_fallback(monkeypatch):
    monkeypatch.setenv("TDT_COLL_TIMEOUT_MS", "30")
    monkeypatch.setenv("TDT_COLL_RETRIES", "0")
    eng, builds = _stub_engine()

    def serve_once(ids, n, key):
        if eng.backend != "xla":
            time.sleep(5)  # wedged collective dispatch
            return "wedged"
        return "served-on-xla"

    eng._serve_once = serve_once
    assert eng.serve("ids", 4) == "served-on-xla"
    assert builds == ["xla"]
    assert resilience.is_degraded("gemm_ar")  # watchdog set the global flag


# ----------------------------------------------------------- env hardening


def test_get_int_env_garbage_warns_once(monkeypatch, capsys):
    from triton_dist_tpu.runtime import utils

    monkeypatch.setattr(utils, "_warned_env", set())
    monkeypatch.setenv("TDT_TEST_INT", "not-a-number")
    assert utils.get_int_env("TDT_TEST_INT", 7) == 7
    assert utils.get_int_env("TDT_TEST_INT", 7) == 7  # warning is one-time
    out = capsys.readouterr().out
    assert out.count("TDT_TEST_INT") == 1
    monkeypatch.setenv("TDT_TEST_INT", " 12 ")
    assert utils.get_int_env("TDT_TEST_INT", 7) == 12


def test_get_bool_env_garbage_warns(monkeypatch, capsys):
    from triton_dist_tpu.runtime import utils

    monkeypatch.setattr(utils, "_warned_env", set())
    monkeypatch.setenv("TDT_TEST_BOOL", "maybe?")
    assert utils.get_bool_env("TDT_TEST_BOOL", True) is True
    assert "TDT_TEST_BOOL" in capsys.readouterr().out
    for truthy in ("1", "true", "YES", " on "):
        monkeypatch.setenv("TDT_TEST_BOOL", truthy)
        assert utils.get_bool_env("TDT_TEST_BOOL") is True
    for falsy in ("0", "false", "No", "off"):
        monkeypatch.setenv("TDT_TEST_BOOL", falsy)
        assert utils.get_bool_env("TDT_TEST_BOOL", True) is False
    monkeypatch.delenv("TDT_TEST_BOOL")
    assert utils.get_bool_env("TDT_TEST_BOOL", True) is True


# ------------------------------------------------------------ tune cache


def test_tune_cache_atomic_save_roundtrip(tmp_path):
    from triton_dist_tpu.tools.tune import TuneCache

    p = tmp_path / "cache.json"
    c = TuneCache(p)
    c.put("op|8x8:float32", {"cfg": {"block": 8}, "time_s": 0.1, "version": "t"})
    c.save()
    assert list(tmp_path.glob("*.tmp")) == []  # no stray temp files
    assert TuneCache(p).get("op|8x8:float32")["cfg"] == {"block": 8}


def test_tune_cache_corrupt_file_loads_empty(tmp_path, capsys):
    from triton_dist_tpu.tools.tune import TuneCache

    p = tmp_path / "cache.json"
    p.write_text('{"op|8x8:float32": {"cfg": {"blo')  # torn mid-write
    c = TuneCache(p)
    assert c.get("op|8x8:float32") is None
    assert "corrupt" in capsys.readouterr().out
    # And a save() from the empty cache repairs the file in place.
    c.put("k|s", {"cfg": {"a": 1}, "time_s": 0.0, "version": "t"})
    c.save()
    assert TuneCache(p).get("k|s")["cfg"] == {"a": 1}


# -------------------------------------------------------- coordinator retry


def _patch_mesh_connect(monkeypatch, fail_times):
    from triton_dist_tpu.runtime import mesh

    calls = {"init": 0, "sleeps": []}

    def fake_init(**kwargs):
        calls["init"] += 1
        if calls["init"] <= fail_times:
            raise ConnectionError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(mesh.time, "sleep", lambda s: calls["sleeps"].append(s))
    monkeypatch.setattr(mesh, "_JAX_DISTRIBUTED_INITIALIZED", False)
    return mesh, calls


def test_mesh_connect_retries_then_succeeds(monkeypatch):
    from triton_dist_tpu.runtime import telemetry

    telemetry.reset()
    mesh, calls = _patch_mesh_connect(monkeypatch, fail_times=2)
    ctx = mesh.initialize_distributed(
        coordinator_address="198.51.100.7:1234", num_processes=1, process_id=0,
        set_default=False,
    )
    assert ctx.world_size >= 1
    assert calls["init"] == 3
    # Exponential backoff with full jitter: each sleep lands in 0.5–1x of
    # its capped base (0.5, then 1.0) — never the deterministic lockstep
    # that stampedes a coordinator on gang restarts.
    assert len(calls["sleeps"]) == 2
    for s, base in zip(calls["sleeps"], (0.5, 1.0)):
        assert 0.5 * base <= s <= base, (s, base)
    assert telemetry.counter_total("tdt_mesh_connect_retries_total") == 2
    assert mesh._JAX_DISTRIBUTED_INITIALIZED


def test_mesh_connect_exhausted_names_coordinator(monkeypatch):
    mesh, calls = _patch_mesh_connect(monkeypatch, fail_times=99)
    with pytest.raises(RuntimeError, match="could not reach coordinator at 198.51.100.7:1234"):
        mesh.initialize_distributed(
            coordinator_address="198.51.100.7:1234", num_processes=1, process_id=0,
            set_default=False,
        )
    assert calls["init"] == 3
    assert not mesh._JAX_DISTRIBUTED_INITIALIZED


def test_mesh_connect_backoff_hard_cap(monkeypatch):
    # With a long retry ladder the base doubles but never exceeds the cap.
    monkeypatch.setenv("TDT_CONNECT_RETRIES", "6")
    monkeypatch.setenv("TDT_CONNECT_BACKOFF_CAP_S", "2.0")
    mesh, calls = _patch_mesh_connect(monkeypatch, fail_times=99)
    with pytest.raises(RuntimeError, match="after 6 attempts"):
        mesh.initialize_distributed(
            coordinator_address="198.51.100.7:1234", num_processes=1, process_id=0,
            set_default=False,
        )
    assert calls["init"] == 6 and len(calls["sleeps"]) == 5
    assert all(s <= 2.0 for s in calls["sleeps"]), calls["sleeps"]
    # The last rungs would be 4s/8s uncapped — they must sit in the
    # jittered band of the 2s cap instead.
    assert all(1.0 <= s <= 2.0 for s in calls["sleeps"][2:]), calls["sleeps"]


# ------------------------------------------------------- bounded-wait lint


def test_bounded_wait_lint_repo_clean():
    r = subprocess.run([sys.executable, LINT], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bounded_wait_lint_flags_raw_wait(tmp_path):
    bad = tmp_path / "bad_kernel.py"
    bad.write_text(
        "def k(sem, out_ref, recv_sem):\n"
        "    tpl.wait(sem, 1)\n"
        "    tpl.wait_recv(recv_sem, out_ref)\n"
        "    tpl.wait_send(sem)\n"  # send drains are allowed
        "    tpl.barrier_all('tp')  # unbounded-wait-ok: test waiver\n"
    )
    r = subprocess.run([sys.executable, LINT, str(bad)], capture_output=True, text=True)
    assert r.returncode == 1
    assert "bad_kernel.py:2" in r.stdout and "bad_kernel.py:3" in r.stdout
    assert "bad_kernel.py:4" not in r.stdout and "bad_kernel.py:5" not in r.stdout


# =========================================================== chaos (device)
#
# Interpret-mode kernels under injected faults, world 4. Shapes stay tiny
# (see conftest: per-kernel buffers ≤ 64 KB on the sim substrate). A small
# plan wait_bound makes dropped-peer aborts fire in milliseconds.

CHAOS_BOUND = 2_000
VICTIM = 1
W4 = 4


def _gemm_ar_operands(rng):
    m, k, n = 8, W4 * 8, 32
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    return a, b


def _gemm_ar_fused(ctx):
    from triton_dist_tpu.kernels import GemmARMethod, gemm_ar_shard

    return shard(
        ctx,
        lambda a_s, b_s: gemm_ar_shard(
            a_s, b_s, axis="tp", method=GemmARMethod.PALLAS_FUSED
        )[None],
        (P(None, "tp"), P("tp")),
        P("tp"),
    )


def _gemm_ar_auto_with_ref(ctx):
    from triton_dist_tpu.kernels import GemmARMethod, gemm_ar_shard

    def fn(a_s, b_s):
        ref = jax.lax.psum(
            jax.lax.dot_general(
                a_s, b_s, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
            "tp",
        )
        out = gemm_ar_shard(a_s, b_s, axis="tp", method=GemmARMethod.AUTO)
        return out[None], ref[None]

    return shard(ctx, fn, (P(None, "tp"), P("tp")), (P("tp"), P("tp")))


@pytest.mark.chaos
def test_chaos_gemm_ar_delayed_rank_completes(ctx4, rng):
    """A delayed rank is drift, not death: the fused ring must absorb it and
    produce exact results, with no abort recorded."""
    a, b = _gemm_ar_operands(rng)
    expect = np.asarray(a) @ np.asarray(b)
    with resilience.fault_plan(
        "delay_rank", rank=VICTIM, delay_iters=2_000, wait_bound=50_000, axis="tp"
    ):
        out = np.asarray(_gemm_ar_fused(ctx4)(a, b))
    for r in range(W4):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-4, err_msg=f"rank {r}")
    assert resilience.last_abort() is None
    assert not resilience.any_degraded()


@pytest.mark.chaos
def test_chaos_gemm_ar_drop_peer_aborts_then_xla_fallback(ctx4, rng):
    """The acceptance scenario: a dead peer makes the fused GEMM+AR abort
    within the configured bound (no hang), the error names the stalled phase
    (a rank that never arrives stalls the entry barrier, peer -1; one that
    dies later stalls rs_recv, which names its exact left neighbor), and
    the NEXT call serves correct results via the sticky XLA fallback."""
    a, b = _gemm_ar_operands(rng)
    with resilience.fault_plan("drop_peer", rank=VICTIM, wait_bound=CHAOS_BOUND, axis="tp"):
        with pytest.raises(Exception) as ei:
            jax.block_until_ready(_gemm_ar_fused(ctx4)(a, b))
    msg = str(ei.value)
    assert "stalled in phase" in msg, msg
    ab = resilience.last_abort()
    assert ab is not None and ab.feature == "gemm_ar"
    # Past the entry barrier every fused-ring wait names a concrete neighbor.
    assert ab.phase == "barrier" or ab.peer >= 0
    assert ab.polls <= CHAOS_BOUND  # aborted within the configured bound
    assert resilience.is_degraded("gemm_ar")

    # Next call: AUTO transparently routes XLA dot+psum, parity vs the
    # fp32-accum psum reference computed inside the same shard_map.
    out, ref = _gemm_ar_auto_with_ref(ctx4)(a, b)
    out, ref = np.asarray(out), np.asarray(ref)
    for r in range(W4):
        np.testing.assert_allclose(out[r], ref[r], rtol=1e-6, atol=1e-6, err_msg=f"rank {r}")


@pytest.mark.chaos
def test_chaos_gemm_ar_corrupt_flag_surfaces(ctx4, rng):
    """A poisoned status flag must surface as an abort (the victim's waits
    short-circuit; its skipped signals cascade bounded aborts to peers)."""
    a, b = _gemm_ar_operands(rng)
    with resilience.fault_plan("corrupt_flag", rank=VICTIM, wait_bound=CHAOS_BOUND, axis="tp"):
        with pytest.raises(Exception):
            jax.block_until_ready(_gemm_ar_fused(ctx4)(a, b))
    assert resilience.aborts()
    assert resilience.is_degraded("gemm_ar")


def _allgather_ring(ctx):
    from triton_dist_tpu.kernels import AllGatherMethod, all_gather_shard

    return shard(
        ctx,
        lambda xs: all_gather_shard(xs, axis="tp", method=AllGatherMethod.RING_1D)
        .reshape(-1, xs.shape[-1]),
        (P("tp"),),
        P(),
    )


@pytest.mark.chaos
@pytest.mark.parametrize("kind", ["delay_rank", "drop_peer", "corrupt_flag"])
def test_chaos_allgather_ring(ctx4, rng, kind):
    x = jnp.asarray(rng.standard_normal((W4 * 8, 64)), jnp.float32)
    if kind == "delay_rank":
        with resilience.fault_plan(kind, rank=VICTIM, delay_iters=2_000, wait_bound=50_000):
            out = np.asarray(_allgather_ring(ctx4)(x))
        np.testing.assert_allclose(out, np.asarray(x), rtol=0, atol=0)
        assert not resilience.any_degraded()
        return
    with resilience.fault_plan(kind, rank=VICTIM, wait_bound=CHAOS_BOUND):
        with pytest.raises(Exception) as ei:
            jax.block_until_ready(_allgather_ring(ctx4)(x))
    assert "stalled in phase" in str(ei.value)
    ab = resilience.last_abort()
    assert ab is not None and ab.feature == "allgather"
    # The ring opens with a barrier, so a dropped peer usually times the
    # barrier out (unattributable); a late stall names the left neighbor.
    assert ab.phase in ("barrier", "ag_recv", "injected_corrupt")
    assert resilience.is_degraded("allgather")
    # Sticky fallback: AUTO now routes XLA and serves exact results.
    from triton_dist_tpu.kernels import AllGatherMethod, all_gather_shard

    f = shard(
        ctx4,
        lambda xs: all_gather_shard(xs, axis="tp", method=AllGatherMethod.AUTO)
        .reshape(-1, xs.shape[-1]),
        (P("tp"),),
        P(),
    )
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x), rtol=0, atol=0)


def _reduce_scatter(ctx):
    from triton_dist_tpu.kernels import reduce_scatter_shard

    return shard(
        ctx,
        lambda x_local: reduce_scatter_shard(x_local[0], axis="tp"),
        (P("tp"),),
        P("tp"),
    )


@pytest.mark.chaos
@pytest.mark.parametrize("kind", ["delay_rank", "drop_peer", "corrupt_flag"])
def test_chaos_reduce_scatter(ctx4, rng, kind):
    per_rank = jnp.asarray(rng.standard_normal((W4, 16, 32)), jnp.float32)
    expect = np.asarray(per_rank).sum(axis=0)
    if kind == "delay_rank":
        with resilience.fault_plan(kind, rank=VICTIM, delay_iters=2_000, wait_bound=50_000):
            out = np.asarray(_reduce_scatter(ctx4)(per_rank))
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)
        assert not resilience.any_degraded()
        return
    with resilience.fault_plan(kind, rank=VICTIM, wait_bound=CHAOS_BOUND):
        with pytest.raises(Exception) as ei:
            jax.block_until_ready(_reduce_scatter(ctx4)(per_rank))
    assert "stalled in phase" in str(ei.value)
    ab = resilience.last_abort()
    assert ab is not None and ab.feature == "reduce_scatter"
    assert ab.phase in (
        "barrier", "rs_recv", "rs_credit", "rs_credit_drain", "injected_corrupt"
    )
    assert resilience.is_degraded("reduce_scatter")
    # Sticky fallback parity: reduce_scatter_shard routes psum_scatter now.
    out = np.asarray(_reduce_scatter(ctx4)(per_rank))
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)
