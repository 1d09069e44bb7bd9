"""Per-iteration device time by chained-loop differencing.

Dispatch is asynchronous and costs host time of its own, so every
measurement here jits a ``fori_loop`` chain of N dependent steps, forces one
scalar readback (which fences the device work), and differences a long chain
against a short one: dispatch and readback costs cancel, leaving
per-iteration device time.

The chain feeds each step's output back into the next step's input (caller
supplies ``chain`` saying how), which keeps every iteration's full output
live — XLA cannot DCE or algebraically narrow the work the way it could if
we only read one element.
"""

from __future__ import annotations

import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp


def _walltime(thunk) -> float:
    t0 = time.perf_counter()
    thunk()
    return time.perf_counter() - t0


def bench_chain_diff(
    run_of_n: Callable[[int], Callable[[], None]],
    *,
    iters: int = 256,
    base: int = 64,
    reps: int = 5,
    max_iters: int = 16384,
) -> float:
    """Generic escalating paired-difference timer: ``run_of_n(n)`` returns a
    thunk executing n chained device iterations and fencing completion; the
    per-iteration time is (long - short)/extra with PAIRED differences,
    alternating measurement order, median-combined — a same-moment pair
    cancels drift in the host's clock and the median rejects outlier pairs.
    A median difference that is not positive is noise: the chain length
    escalates ×4 (up to ``max_iters``), and a measurement that never turns
    positive returns +inf so autotune sweeps can never pick it.
    """
    short = run_of_n(base)
    short()  # compile + warm once; base never changes
    while True:
        long_ = run_of_n(base + iters)
        long_()
        diffs = []
        for r in range(reps):
            if r % 2 == 0:
                t_l = _walltime(long_)
                t_s = _walltime(short)
            else:
                t_s = _walltime(short)
                t_l = _walltime(long_)
            diffs.append(t_l - t_s)
        diffs.sort()
        diff = diffs[len(diffs) // 2]
        if diff > 0:
            return diff / iters
        if iters >= max_iters:
            return float("inf")
        iters *= 4


def bench_device_time(
    step: Callable,
    args: Sequence[jax.Array],
    *,
    chain: Callable | None = None,
    iters: int = 256,
    base: int = 64,
    reps: int = 5,
    max_iters: int = 16384,
) -> float:
    """Per-iteration device seconds of ``step(*args)``.

    ``chain(out, args) -> args`` threads step N's output into step N+1's
    inputs (default: replace ``args[0]`` with ``clip(out, -1, 1)``, which fits
    self-shaped ops like square GEMMs and attention; the clip keeps chained
    values finite). Pass a custom ``chain`` when shapes differ. See
    :func:`bench_chain_diff` for the measurement discipline.
    """
    if chain is None:
        chain = lambda out, a: (jnp.clip(out, -1, 1).astype(a[0].dtype),) + tuple(a[1:])

    def make(n):
        @jax.jit
        def run(*xs):
            def body(_, carry):
                out = step(*carry)
                return tuple(chain(out, carry))

            final = jax.lax.fori_loop(0, n, body, tuple(xs))
            return final[0].astype(jnp.float32).sum()

        return run

    def run_of_n(n):
        f = make(n)
        return lambda: float(f(*args))

    return bench_chain_diff(
        run_of_n, iters=iters, base=base, reps=reps, max_iters=max_iters
    )
