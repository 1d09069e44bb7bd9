"""Distributed Pallas launch wrapper — the ``@triton_dist.jit`` analog.

Reference (``python/triton_dist/jit.py``): wraps ``triton.jit`` to (a) link the
NVSHMEM device library into every kernel (:91-121), (b) run module init hooks
post-compile (:43-88), (c) rewrite the cubin when shmem symbols are present
(:151-235). On TPU none of that machinery is needed — Mosaic lowers semaphore
and remote-DMA ops natively — so the wrapper's job reduces to launch hygiene:

* pick ``interpret=pltpu.InterpretParams(...)`` automatically on CPU (the
  simulation/test substrate, SURVEY §4) and compile on real TPU;
* mark communication kernels ``has_side_effects`` so XLA cannot DCE a launch
  whose only effect is a DMA (pitfall #6 in the Pallas guide);
* allocate a process-unique ``collective_id`` per kernel *site* so barrier
  semaphores of different kernels never alias;
* thread the active ``runtime.resilience.FaultPlan`` (if any) around the
  kernel body in interpret mode, so any distributed kernel can run under an
  injected fault without opting in;
* provide the bounded-wait helpers (:func:`bounded_wait`,
  :func:`bounded_wait_recv`, :func:`bounded_barrier_all`) and the status
  buffer protocol (:func:`status_out_shape` / :func:`init_status`) that
  collective kernels adopt instead of raw unbounded semaphore waits.
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import triton_dist_tpu.language as tpl
from triton_dist_tpu.runtime import resilience, telemetry
from triton_dist_tpu.runtime.platform import interpret_mode_default

_collective_ids = itertools.count(0)
_collective_id_registry: dict[str, int] = {}


def next_collective_id() -> int:
    """Process-unique collective id for barrier-semaphore-using kernels.

    Allocates from the same checked registry as :func:`collective_id_for`
    (under a synthetic unique name), so anonymous and named allocations share
    one id space and the 32-id aliasing guard applies to both.
    """
    return collective_id_for(f"__anon_{next(_collective_ids)}")


#: Mosaic's barrier-semaphore pool size — ids past this would alias another
#: kernel's barrier semaphore, a silent cross-talk correctness hazard.
MAX_COLLECTIVE_IDS = 32


def reset_collective_ids() -> None:
    """Clear the registry. For long-lived processes that run many *separate*
    compiled programs: ids only need uniqueness within one program, so a
    process cycling through >32 distinct collective kernels across jobs can
    reset between them instead of dying on the aliasing guard."""
    _collective_id_registry.clear()


def kernel_key(kernel) -> str:
    """Stable registry key for a kernel callable. ``functools.partial``
    objects have no ``__qualname__`` and their ``repr`` embeds an object
    address — using that would burn a fresh id slot on EVERY retrace.
    Unwrap to the underlying function plus a repr of the bound static args
    (axis names, tile sizes… — stable across traces), so retraces reuse
    their slot while genuinely different configurations stay distinct."""
    if isinstance(kernel, functools.partial):
        args = ",".join(map(repr, kernel.args))
        kw = ",".join(f"{k}={v!r}" for k, v in sorted(kernel.keywords.items()))
        return f"{kernel_key(kernel.func)}({args};{kw})"
    return getattr(kernel, "__qualname__", None) or repr(kernel)


def kernel_base_name(kernel) -> str:
    """Bare function name of a (possibly ``functools.partial``-wrapped)
    kernel — the bounded-cardinality label for per-collective telemetry
    (``kernel_key`` embeds bound-arg reprs, whose shape/config variety
    would explode a metric's label space)."""
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", None) or repr(kernel)


def collective_id_for(name: str) -> int:
    """Stable collective id keyed by kernel name.

    Re-tracing the same kernel (new shapes) reuses its id, so ids are not
    burned per trace; distinct kernel names get distinct ids while fewer than
    32 collective kernels exist in the program (Mosaic's barrier-semaphore
    pool). Registration order is trace order, identical across SPMD processes.

    Raises ``RuntimeError`` on the 33rd distinct kernel instead of wrapping:
    an aliased barrier semaphore deadlocks or corrupts silently, which is far
    worse than a loud registration failure.
    """
    if name not in _collective_id_registry:
        if len(_collective_id_registry) >= MAX_COLLECTIVE_IDS:
            raise RuntimeError(
                f"collective_id_for({name!r}): {MAX_COLLECTIVE_IDS} distinct "
                "collective kernels already registered; a new id would alias "
                "an existing kernel's barrier semaphore. Pass an explicit "
                "collective_id to dist_pallas_call to reuse one safely, or — "
                "if the earlier kernels belong to already-finished compiled "
                "programs — call shmem.kernel.reset_collective_ids() between "
                "jobs (ids only need uniqueness within one program)."
            )
        _collective_id_registry[name] = len(_collective_id_registry)
    return _collective_id_registry[name]


def dist_pallas_call(
    kernel,
    *,
    out_shape,
    collective: bool = True,
    collective_id: int | None = None,
    interpret: Any | None = None,
    detect_races: bool = False,
    compiler_params: pltpu.CompilerParams | None = None,
    **kwargs,
):
    """``pl.pallas_call`` with distributed launch defaults (see module doc).

    ``collective=True`` marks a kernel that performs remote DMA / semaphore
    signalling: it forces ``has_side_effects`` and assigns a collective id.
    """
    if collective:
        # Dead-peer fail-fast: a launch whose membership includes a dead
        # rank is refused at TRACE time — one DeadPeerError here instead of
        # a bounded-wait timeout per collective per step. Raised before any
        # id is allocated or counter ticked, so a refused launch leaves no
        # trace-side state behind.
        resilience.check_dead_peers(kernel=kernel_base_name(kernel))
        # Trace-time launch counter per collective name: one tick per traced
        # launch site (retraces included), the signal that shows WHICH
        # collective kernels a program actually routed into (AUTO flips,
        # degraded-mode reroutes) without per-step device overhead.
        telemetry.inc(
            "tdt_shmem_collective_calls_total", kernel=kernel_base_name(kernel)
        )
    if compiler_params is None:
        if collective_id is None and collective:
            # Stable id per kernel so barrier semaphores of different kernels
            # traced into the same program never alias, while retraces of the
            # same kernel reuse their id. SPMD tracing is identical on every
            # process, so the registry stays consistent across ranks.
            collective_id = collective_id_for(kernel_key(kernel))
        compiler_params = pltpu.CompilerParams(
            has_side_effects=collective,
            collective_id=collective_id,
        )
    if interpret is None:
        interpret = interpret_mode_default(detect_races=detect_races)
    # The kernel's own name on the device timeline (a partial or a fault
    # wrapper has none, and XLA would make one up).
    kwargs.setdefault("name", kernel_base_name(kernel))
    # Fault injection is a simulation feature: apply the active FaultPlan
    # only in interpret mode, and only after the collective id was derived
    # from the ORIGINAL kernel above (a wrapper has no stable key and would
    # burn a fresh id slot on every trace).
    plan = resilience.active_plan()
    if plan is not None and interpret:
        kernel = resilience.apply_fault_plan(kernel, plan)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        compiler_params=compiler_params,
        interpret=interpret,
        **kwargs,
    )


# --------------------------------------------------- status buffer protocol
#
# Every adopted collective kernel appends one small SMEM int32 output (LAST
# in its out_shape tuple, except that a TDT_KERNEL_TRACE event buffer — when
# threaded — follows it as the final output) holding [0]=code
# (STATUS_OK/STATUS_ABORT), [1]=phase id (resilience.phase_name), [2]=peer
# rank along the collective axis (-1 when unattributable, e.g. a barrier),
# [3]=polls spent, [4]=mesh epoch the kernel was traced at (the fence: the
# host aborts with stale_epoch when it no longer matches the live epoch).
# Bounded waits write an abort record instead of spinning forever; the host
# surfaces it via resilience.consume_status. SMEM outputs start
# uninitialized — call init_status() first thing in the kernel (once per
# launch under a grid). Adopters: allgather / allreduce / reduce_scatter
# / gemm_allreduce / ep_a2a (PR 2) + allgather_gemm / gemm_reduce_scatter /
# ag_attention (prefill overlap v2).

#: Number of int32 words in a collective status buffer.
STATUS_WORDS = 5
STATUS_OK = resilience.STATUS_OK
STATUS_ABORT = resilience.STATUS_ABORT


def status_out_shape() -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct for a collective's status output."""
    return jax.ShapeDtypeStruct((STATUS_WORDS,), jnp.int32)


def status_out_spec() -> pl.BlockSpec:
    """BlockSpec placing the status output in SMEM (scalar words)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def init_status(status_ref, *, axis: str | Sequence[str] = "tp") -> None:
    """Initialize a status buffer to OK inside the kernel body.

    Also the CORRUPT_FLAG injection point: when a FaultPlan of that kind is
    active (trace time), the victim rank's buffer is initialized already
    aborted, so its bounded waits short-circuit and the poisoned flag must
    surface host-side. ``axis`` is the collective's axis (used to identify
    the victim rank).
    """
    status_ref[0] = jnp.int32(STATUS_OK)
    status_ref[1] = jnp.int32(-1)
    status_ref[2] = jnp.int32(-1)
    status_ref[3] = jnp.int32(0)
    # Epoch fence: the LIVE epoch at trace time becomes a compile-time
    # constant in the executable. A cached executable replayed after a
    # membership reconfiguration carries the old value, and the host-side
    # consume_status aborts it deterministically (stale_epoch).
    status_ref[4] = jnp.int32(resilience.mesh_epoch())
    plan = resilience.active_plan()
    if plan is not None and plan.kind is resilience.FaultKind.CORRUPT_FLAG:
        me = tpl.rank(axis)

        @pl.when(me == jnp.int32(plan.rank))
        def _():
            status_ref[0] = jnp.int32(STATUS_ABORT)
            status_ref[1] = jnp.int32(resilience.phase_id("injected_corrupt"))


def _bounded_poll(read_done, consume, status_ref, *, phase, peer, bound) -> None:
    """Shared core: poll ``read_done()`` up to ``bound`` times, then either
    ``consume()`` the semaphore for real (blocking wait with acquire
    semantics) or write an abort record. A buffer already aborted (earlier
    phase, or injected corruption) skips polling entirely and never
    consumes — cascading the abort forward is intended; post-abort
    semaphore state is undefined and the sticky XLA fallback never reuses
    the kernel."""
    pid = resilience.phase_id(phase)
    pre_ok = status_ref[0] == jnp.int32(STATUS_OK)
    eff_bound = jnp.where(pre_ok, jnp.int32(bound), jnp.int32(0))

    def cond(carry):
        it, done = carry
        return jnp.logical_and(it < eff_bound, jnp.logical_not(done))

    def body(carry):
        it, _ = carry
        return it + 1, read_done()

    polls, done = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.bool_(False)))

    @pl.when(jnp.logical_and(pre_ok, done))
    def _():
        consume()

    peer_val = jnp.int32(-1) if peer is None else jnp.asarray(peer, dtype=jnp.int32)

    @pl.when(jnp.logical_and(pre_ok, jnp.logical_not(done)))
    def _():
        status_ref[0] = jnp.int32(STATUS_ABORT)
        status_ref[1] = jnp.int32(pid)
        status_ref[2] = peer_val
        status_ref[3] = polls


def bounded_wait(
    sem,
    status_ref,
    *,
    value: int | jax.Array = 1,
    phase: str,
    peer=None,
    bound: int | None = None,
) -> None:
    """Iteration-capped ``tpl.wait``: poll the semaphore up to ``bound``
    times; on success consume ``value`` via the real blocking wait, on
    timeout record an abort (phase + peer) in ``status_ref`` instead of
    spinning forever. ``bound`` resolves through ``resilience.wait_bound``
    (explicit > FaultPlan override > ``TDT_WAIT_BOUND_ITERS`` > platform
    default); a resolved bound of 0 emits the plain unbounded wait."""
    bound = resilience.wait_bound(bound)
    if bound == 0:
        tpl.wait(sem, value)
        return
    target = jnp.asarray(value, dtype=jnp.int32)
    _bounded_poll(
        lambda: pltpu.semaphore_read(sem) >= target,
        lambda: pltpu.semaphore_wait(sem, value),
        status_ref,
        phase=phase,
        peer=peer,
        bound=bound,
    )


#: The unit in which the chip counts a DMA semaphore, as ``semaphore_read``
#: sees it. Measured on TPU v5e (PR 21's chip run): a 4096-byte copy reads
#: 128 and a 1 MiB copy 32768, local and remote alike, float32 and bfloat16.
#: The interpreter counts bytes. A poll against the wrong unit never
#: succeeds: the wait gives up at its bound, the semaphore is left nonzero,
#: and the chip halts the core on the kernel's exit.
DMA_SEM_UNIT_BYTES = 32


def _dma_sem_count(nbytes: int) -> int:
    """What a DMA semaphore reads once ``nbytes`` have landed."""
    if interpret_mode_default():
        return nbytes
    if nbytes % DMA_SEM_UNIT_BYTES:
        raise ValueError(
            f"a {nbytes}-byte message is no whole number of the "
            f"{DMA_SEM_UNIT_BYTES}-byte units a DMA semaphore counts"
        )
    return nbytes // DMA_SEM_UNIT_BYTES


def bounded_wait_recv(
    recv_sem,
    ref,
    status_ref,
    *,
    phase: str,
    peer=None,
    bound: int | None = None,
) -> None:
    """Iteration-capped ``tpl.wait_recv``: DMA semaphores count the data
    that landed (see ``DMA_SEM_UNIT_BYTES``), so poll for ``ref``'s size
    before consuming via the blocking DMA wait. Same bound resolution and
    abort protocol as :func:`bounded_wait`.
    """
    bound = resilience.wait_bound(bound)
    if bound == 0:
        tpl.wait_recv(recv_sem, ref)
        return
    nbytes = int(np.prod(ref.shape)) * np.dtype(ref.dtype).itemsize
    target = _dma_sem_count(nbytes)
    _bounded_poll(
        lambda: pltpu.semaphore_read(recv_sem) >= jnp.int32(target),
        lambda: pltpu.make_async_copy(ref, ref, recv_sem).wait(),
        status_ref,
        phase=phase,
        peer=peer,
        bound=bound,
    )


def bounded_barrier_all(
    status_ref,
    axis: str | Sequence[str] = "tp",
    mesh_axes: Sequence[str] | None = None,
    *,
    phase: str = "barrier",
    bound: int | None = None,
) -> None:
    """Iteration-capped ``tpl.barrier_all``. An already-aborted rank skips
    both the signal and the wait half (its peers' bounded barrier waits
    then time out too — the cascade is how an abort propagates without any
    extra control channel). Barrier arrivals carry no sender identity, so
    a barrier abort always reports peer -1."""
    bound = resilience.wait_bound(bound)
    if bound == 0:
        tpl.barrier_all(axis, mesh_axes)
        return
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    barrier_sem = pltpu.get_barrier_semaphore()
    world = tpl.num_ranks(axes)
    pre_ok = status_ref[0] == jnp.int32(STATUS_OK)

    @pl.when(pre_ok)
    def _():
        tpl.barrier_signal_all(axes, mesh_axes)

    _bounded_poll(
        lambda: pltpu.semaphore_read(barrier_sem) >= jnp.int32(world),
        lambda: pltpu.semaphore_wait(barrier_sem, world),
        status_ref,
        phase=phase,
        peer=None,
        bound=bound,
    )
