"""Platform / backend selection helpers.

The reference emulates multi-node on one node by shrinking ``LOCAL_WORLD_SIZE``
(SURVEY §4, ``test/nvidia/test_ag_gemm.py``) and uses ``TRITON_INTERPRET=1``
for pure-python kernel emulation. The TPU build does better: an N-device
virtual CPU mesh (``--xla_force_host_platform_device_count``) plus Pallas TPU
*interpret mode* (``pltpu.InterpretParams``) simulates HBM/VMEM, local+remote
DMAs and semaphores on CPU — including optional race detection
(``detect_races=True``), which subsumes the reference's compute-sanitizer hook
(``scripts/launch.sh:164-166``).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from functools import lru_cache

_CPU_DEVICE_ENV = "--xla_force_host_platform_device_count"


def _ensure_cpu_device_flag(n: int) -> None:
    """Set (or update) the host-device-count XLA flag. Must run before the
    CPU backend is initialized to have any effect."""
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    new = f"{_CPU_DEVICE_ENV}={n}"
    if _CPU_DEVICE_ENV in flags:
        flags = re.sub(rf"{_CPU_DEVICE_ENV}=\d+", new, flags)
    else:
        flags = f"{flags} {new}".strip()
    os.environ["XLA_FLAGS"] = flags


def use_cpu_devices(n: int = 8) -> None:
    """Force JAX onto N virtual CPU devices (test / simulation substrate).

    Call before any JAX computation. Safe to call multiple times.
    """
    _ensure_cpu_device_flag(n)
    # XLA sizes the CPU client's thread pools from NPROC where it is set
    # (xla/pjrt/utils.cc, DefaultThreadPoolSize), else from the core count —
    # 8 threads on 8 cores for 8 devices. In the simulation a thread does not
    # compute, it WAITS: every device thread parks in the Pallas interpreter's
    # barrier or in a collective's rendezvous, and what XLA's executor hands
    # to the pool meanwhile (a ring hop issued early, an op the interpreter's
    # callbacks dispatch) queues behind them for ever. That was the wedge of
    # the 2-D ring tests and the 40 s rendezvous abort under load; with
    # threads to spare neither happens.
    os.environ.setdefault("NPROC", str(8 * n))
    import jax

    jax.config.update("jax_platforms", "cpu")


@lru_cache(maxsize=None)
def is_cpu_platform() -> bool:
    import jax

    return jax.devices()[0].platform == "cpu"


_RACE_DETECTION = False


def race_detection(enable: bool = True):
    """Context manager turning on the interpret-mode race detector for every
    ``pallas_call`` traced inside (the compute-sanitizer analog — reference
    ``scripts/launch.sh:164-166``). CPU-sim only; a no-op on hardware.

    The flag is read at TRACE time and does not participate in jit cache
    keys, so entry/exit clears jax's compilation caches: functions re-trace
    with the detector on inside the context, and re-trace without it after
    — a cached pre-context executable would otherwise silently run
    unchecked (and vice versa). Intended for tests, not hot loops."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        import jax

        global _RACE_DETECTION
        prev = _RACE_DETECTION
        _RACE_DETECTION = enable
        jax.clear_caches()
        try:
            yield
        finally:
            _RACE_DETECTION = prev
            jax.clear_caches()

    return _ctx()


_FORCE_MOSAIC = False


@contextmanager
def force_mosaic():
    """Context manager forcing ``interpret_mode_default`` to False even on a
    CPU host — for deviceless TPU-topology compiles (tests/test_tpu_lowering):
    without it, tracing on a CPU default backend picks InterpretParams and
    the topology compile silently exercises the pure-HLO interpret EMULATION
    instead of Mosaic (found r5: the lowered module had zero
    ``tpu_custom_call``s — the compile proved nothing about Mosaic)."""
    global _FORCE_MOSAIC
    prev = _FORCE_MOSAIC
    _FORCE_MOSAIC = True
    try:
        yield
    finally:
        _FORCE_MOSAIC = prev


def interpret_mode_default(detect_races: bool = False):
    """Return the value for ``pallas_call(interpret=...)`` on this platform.

    On CPU returns ``pltpu.InterpretParams`` (full TPU simulation, incl. remote
    DMA + semaphores); on real TPU returns ``False`` (compile via Mosaic).
    Under ``force_mosaic()`` always returns False (deviceless TPU compiles).
    """
    if _FORCE_MOSAIC:
        return False
    if is_cpu_platform():
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.InterpretParams(
            detect_races=detect_races or _RACE_DETECTION
        )
    return False


def enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache for this process. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and nothing is
    done; else the cache lives at ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of what a later process must find again."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import pathlib

    import jax

    checkout = pathlib.Path(__file__).resolve().parents[2]
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))


def cpu_mesh(shape, axis_names):
    """Build a Mesh of virtual CPU devices (row-major) for tests."""
    import math

    import jax
    import numpy as np
    from jax.sharding import Mesh

    n = math.prod(shape)
    devs = jax.devices("cpu")[:n]
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} CPU devices, have {len(devs)}; call use_cpu_devices({n}) "
            "before any JAX computation"
        )
    return Mesh(np.asarray(devs).reshape(shape), axis_names)
