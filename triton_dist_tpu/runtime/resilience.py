"""Resilience layer: fault-injection plans, abort bookkeeping, watchdogs.

The reference ships straggler injection (``sleep_async``, ``utils.py:650``)
and otherwise leans on vendor SHMEM timeouts. This module is the TPU port's
production counterpart, spanning four layers:

* **FaultPlan** — a trace-time fault-injection registry threaded through
  ``shmem.kernel.dist_pallas_call``: any distributed kernel can run under a
  delayed rank, a dropped (dead) peer, or a corrupted status flag in CPU
  interpret mode, without the kernel opting in.
* **Status-buffer protocol** — every adopted collective kernel carries a
  small SMEM status output (see ``shmem.kernel.STATUS_WORDS``); bounded
  semaphore waits write an abort record (code, phase, peer, polls) into it
  instead of spinning forever. :func:`consume_status` surfaces that record
  host-side as a :class:`CollectiveAbortError` naming the stalled phase and
  peer rank, and marks the collective degraded.
* **Degradation registry** — per-feature circuit breakers consulted at
  trace time by the AUTO routing in ``kernels/gemm_allreduce``/
  ``allreduce``/``allgather``/``reduce_scatter``/``ep_a2a`` and by
  ``layers/tp``: once a collective has aborted (or a watchdog tripped) its
  breaker OPENs and subsequent traces route the plain XLA collective path
  with a logged reason. Unlike the original one-way flag, an OPEN breaker
  becomes probe-eligible after a ``TDT_DEGRADE_PROBE_S`` backoff
  (HALF_OPEN); a successful sandboxed probe dispatch CLOSEs it and fused
  routing returns, while a failed probe re-opens with exponential backoff.
  State changes take effect at the next trace — exiting a
  :func:`fault_plan`/:func:`probe_scope` context or an ``Engine._build``
  rebuild clears the jit caches that would otherwise replay the cached
  executable.
* **Chaos schedule** — the multi-fault extension of FaultPlan: a
  deterministic program of host-side fault injections
  (``TDT_CHAOS_SCHEDULE`` or :func:`chaos_schedule`, e.g.
  ``"abort@decode:1,abort@recovery,heal"``) consumed in order by
  :func:`chaos_check` call sites in the serving loop, so tests can script
  double-fault recovery and probe-driven un-degrade arcs. ``die@<rank>`` /
  ``revive@<rank>`` steps script whole-rank loss against the dead-rank
  registry below.
* **Dead-rank registry + mesh epoch** — the rank-death tier above the
  per-feature breakers: :func:`declare_rank_dead` (fed by
  ``mesh.HealthBoard`` lease expiry or a chaos ``die@<rank>``) records the
  rank, bumps the **mesh epoch** (``tdt_mesh_epoch``), and OPENs the
  'collectives' breaker, after which every fused collective launched via
  ``dist_pallas_call`` fails fast with :class:`DeadPeerError` at trace time
  — no per-collective bounded-wait timeout storm. The epoch is stamped into
  word [4] of the status-buffer protocol (``shmem.kernel.init_status``) so
  an executable traced before a reconfiguration aborts deterministically
  with ``stale_epoch`` instead of touching a reassigned peer.
* **CollectiveWatchdog** — host-side wall-time bound on collective dispatch
  with retry/backoff (``TDT_COLL_TIMEOUT_MS``, ``TDT_COLL_RETRIES``); on
  final timeout it marks the feature degraded and either runs the caller's
  fallback or raises :class:`CollectiveTimeoutError`: it keeps the serving
  process alive on the XLA fallback.

Env flags::

    TDT_COLL_TIMEOUT_MS    watchdog per-attempt budget (0 = disabled, default)
    TDT_COLL_RETRIES       extra watchdog attempts after the first (default 2)
    TDT_WAIT_BOUND_ITERS   device-side wait poll cap (0 = unbounded waits)
    TDT_DEGRADE_PROBE_S    breaker probe backoff base, seconds (default 30;
                           <= 0 disables probing = the old sticky behavior)
    TDT_CHAOS_SCHEDULE     scripted fault schedule (see ChaosSchedule)
    TDT_LOG                log verbosity: silent / warn (default) / debug

Every degradation, abort, fallback, and watchdog trip is also recorded as a
``runtime.telemetry`` counter + structured event (``docs/observability.md``)
— the log lines are the human echo, telemetry is the record.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import os
import threading
import time

import numpy as np

from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.utils import get_float_env, get_int_env, tdt_log

# ------------------------------------------------------------- status protocol

#: Status-word layout (int32): [0]=code, [1]=phase id, [2]=peer rank along the
#: collective's axis (-1 = unattributable, e.g. a barrier or a shared fan-in
#: semaphore), [3]=polls spent before giving up.
STATUS_OK = 0
STATUS_ABORT = 1

#: Device-side wait poll caps when ``TDT_WAIT_BOUND_ITERS`` is unset. Each
#: poll is a ``semaphore_read`` + compare: nanoseconds compiled through
#: Mosaic, far above any legitimate wait so production traffic never trips
#: it. The Pallas TPU interpreter has no rule for ``semaphore_read``, so the
#: simulation default is 0 (plain blocking wait); a test that wants the
#: timeout itself asks for a bound explicitly (FaultPlan / env / argument).
DEFAULT_WAIT_BOUND_HW = 100_000_000
DEFAULT_WAIT_BOUND_SIM = 0

# Phase names are registered at trace time; SPMD tracing is identical on
# every process, so ids agree across ranks without any exchange.
_PHASES: list[str] = [
    "barrier",
    "exit_barrier",
    "rs_recv",
    "rs_credit",
    "rs_credit_drain",
    "ag_recv",
    "fanin_recv",
    "a2a_recv",
    "injected_corrupt",
    "dead_peer",
    "stale_epoch",
]


def phase_id(name: str) -> int:
    """Stable small-int id for a wait-phase name (registers new names)."""
    if name not in _PHASES:
        _PHASES.append(name)
    return _PHASES.index(name)


def phase_name(pid: int) -> str:
    return _PHASES[pid] if 0 <= pid < len(_PHASES) else "unknown"


def wait_bound(explicit: int | None = None) -> int:
    """Resolve the device-side wait poll cap at TRACE time (static in the
    kernel). Priority: explicit arg > active FaultPlan override >
    ``TDT_WAIT_BOUND_ITERS`` > platform default. 0 means unbounded (the
    helpers emit the plain blocking wait)."""
    if explicit is not None:
        return int(explicit)
    plan = _ACTIVE_PLAN
    if plan is not None and plan.wait_bound is not None:
        return int(plan.wait_bound)
    env = get_int_env("TDT_WAIT_BOUND_ITERS", -1)
    if env >= 0:
        return env
    from triton_dist_tpu.runtime.platform import interpret_mode_default

    # Follows the launch mode, not the platform name, so a deviceless Mosaic
    # compile under platform.force_mosaic() gets the hardware polls.
    return DEFAULT_WAIT_BOUND_SIM if interpret_mode_default() else DEFAULT_WAIT_BOUND_HW


# ------------------------------------------------------------------ exceptions


class CollectiveAbortError(RuntimeError):
    """A bounded device-side wait gave up: the status buffer reported an
    abort, naming the stalled phase and (when attributable) the peer rank."""


class CollectiveTimeoutError(RuntimeError):
    """The host-side CollectiveWatchdog exhausted its attempts."""


class DeadPeerError(CollectiveAbortError):
    """A collective was refused (or aborted) because a participating rank is
    on the dead-rank registry. Subclasses :class:`CollectiveAbortError` so
    every existing recovery path (serving ``_guarded``, probe verdicts)
    treats rank death as a recoverable collective failure."""


class StaleEpochError(CollectiveAbortError):
    """A kernel's status buffer carried a mesh epoch older than the live
    one: the executable was traced before a reconfiguration and its peer
    assignments can no longer be trusted. Deterministic fencing — the abort
    fires on the epoch comparison alone, never on payload corruption."""


# ----------------------------------------------- mesh epoch + dead ranks

# The mesh epoch is owned here (not in runtime.mesh) so shmem/kernels/serving
# can consult it without importing the mesh layer: mesh imports resilience,
# never the reverse. It bumps on every membership reconfiguration (death OR
# revival) — an epoch identifies one stable membership view, so any cached
# executable stamped with an older value must be fenced out.
_MESH_EPOCH = 0
_DEAD_RANKS: dict[int, str] = {}


def mesh_epoch() -> int:
    """Current mesh epoch (monotonic within the process; 0 = initial)."""
    with _LOCK:
        return _MESH_EPOCH


def _bump_epoch_locked(why: str) -> int:
    global _MESH_EPOCH
    _MESH_EPOCH += 1
    telemetry.set_gauge("tdt_mesh_epoch", float(_MESH_EPOCH))
    telemetry.emit("mesh_epoch", epoch=_MESH_EPOCH, why=why)
    return _MESH_EPOCH


def declare_rank_dead(rank: int, reason: str = "declared dead") -> int:
    """Record ``rank`` as dead, bump the mesh epoch, and OPEN the
    'collectives' breaker so fused routing drains immediately. Idempotent:
    re-declaring an already-dead rank returns the current epoch unchanged.
    Returns the (possibly new) mesh epoch."""
    with _LOCK:
        if rank in _DEAD_RANKS:
            return _MESH_EPOCH
        _DEAD_RANKS[rank] = reason
        epoch = _bump_epoch_locked(f"rank {rank} dead: {reason}")
    telemetry.inc("tdt_health_deaths_total", rank=rank)
    telemetry.set_gauge("tdt_health_rank_alive", 0.0, rank=rank)
    telemetry.emit("rank_dead", rank=rank, reason=reason, epoch=epoch)
    _log(f"[resilience] rank {rank} declared dead (epoch {epoch}): {reason}")
    # Fail fast from now on: one breaker OPEN, not one timeout per collective.
    mark_degraded("collectives", f"dead_peer: rank {rank} ({reason})")
    return epoch


def declare_rank_revived(rank: int) -> int:
    """Remove ``rank`` from the dead set and bump the mesh epoch. Does NOT
    close any breaker — the half-open probe machinery must prove the fused
    path healthy at the new epoch before traffic returns. Idempotent."""
    with _LOCK:
        if rank not in _DEAD_RANKS:
            return _MESH_EPOCH
        del _DEAD_RANKS[rank]
        epoch = _bump_epoch_locked(f"rank {rank} revived")
    telemetry.inc("tdt_health_revivals_total", rank=rank)
    telemetry.set_gauge("tdt_health_rank_alive", 1.0, rank=rank)
    telemetry.emit("rank_revived", rank=rank, epoch=epoch)
    _log(f"[resilience] rank {rank} revived (epoch {epoch})")
    return epoch


def dead_ranks() -> dict[int, str]:
    """Live view of the dead-rank registry: {rank: reason}."""
    with _LOCK:
        return dict(_DEAD_RANKS)


def check_dead_peers(*, feature: str = "collectives", kernel: str = "") -> None:
    """Fail fast with :class:`DeadPeerError` when any rank is on the dead
    registry. Called by ``dist_pallas_call`` before every collective launch
    (trace time — the error surfaces before a single device poll is spent)
    and by host paths that would otherwise discover the death one bounded
    wait at a time. Deliberately NOT probe-exempt: a half-open probe while
    the rank is still dead must fail, and succeed only after revival."""
    with _LOCK:
        if not _DEAD_RANKS:
            return
        dead = dict(_DEAD_RANKS)
        epoch = _MESH_EPOCH
    telemetry.inc(
        "tdt_resilience_dead_peer_failfast_total",
        feature=feature, kernel=kernel or "host",
    )
    ranks = ", ".join(f"{r} ({why})" for r, why in sorted(dead.items()))
    raise DeadPeerError(
        f"{feature} collective ({kernel or 'host'}) refused at epoch {epoch}: "
        f"dead_peer — rank(s) {ranks}"
    )


# ------------------------------------------------------------------ fault plans


class FaultKind(enum.Enum):
    #: Victim rank busy-waits ``delay_iters`` dependent iterations before
    #: running the kernel body — the protocol must absorb the drift.
    DELAY_RANK = "delay_rank"
    #: Victim rank skips the kernel body entirely (sends, signals, barriers):
    #: the dead-peer scenario. Peers' bounded waits must abort, not hang.
    DROP_PEER = "drop_peer"
    #: Victim rank's status buffer is initialized already-aborted (a poisoned
    #: flag): its bounded waits short-circuit and the abort must surface.
    CORRUPT_FLAG = "corrupt_flag"


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """One injected fault, applied at trace time to every kernel launched
    through ``dist_pallas_call`` while the plan is active (interpret mode
    only — fault injection is a simulation feature)."""

    kind: FaultKind
    rank: int
    axis: str = "tp"
    delay_iters: int = 20_000
    #: Override the bounded-wait poll cap while this plan is active, so
    #: chaos tests abort in milliseconds instead of the production bound.
    wait_bound: int | None = None


_ACTIVE_PLAN: FaultPlan | None = None


def active_plan() -> FaultPlan | None:
    return _ACTIVE_PLAN


@contextlib.contextmanager
def fault_plan(kind: FaultKind | str, rank: int, **kwargs):
    """Activate a :class:`FaultPlan` for every ``dist_pallas_call`` traced
    inside the context. Like ``platform.race_detection``, the plan is read
    at TRACE time and does not participate in jit cache keys, so entry and
    exit clear jax's compilation caches — functions re-trace with the fault
    inside the context and re-trace clean after it (which is also what
    makes the post-abort sticky XLA fallback take effect "transparently"
    on the next call)."""
    import jax

    global _ACTIVE_PLAN
    plan = FaultPlan(kind=FaultKind(kind), rank=rank, **kwargs)
    prev = _ACTIVE_PLAN
    _ACTIVE_PLAN = plan
    jax.clear_caches()
    try:
        yield plan
    finally:
        _ACTIVE_PLAN = prev
        jax.clear_caches()


def apply_fault_plan(kernel, plan: FaultPlan):
    """Wrap a kernel body with the plan's fault. Called by
    ``dist_pallas_call`` AFTER the collective id is derived from the
    original kernel (a wrapper key would burn a fresh id slot per plan)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def wrapped(*refs):
        me = jax.lax.axis_index(plan.axis)
        if plan.kind is FaultKind.DROP_PEER:
            @pl.when(me != jnp.int32(plan.rank))
            def _():
                kernel(*refs)
        elif plan.kind is FaultKind.DELAY_RANK:
            n = jnp.where(me == jnp.int32(plan.rank),
                          jnp.int32(plan.delay_iters), jnp.int32(0))
            spun = jax.lax.fori_loop(
                0, n, lambda i, a: a * 1.0000001 + 1e-7, jnp.float32(1.0)
            )
            # Gate the body on a data-dependent, always-true-for-finite
            # predicate so the spin cannot be dead-code-eliminated or
            # const-folded away from the kernel.
            @pl.when(spun > jnp.float32(-1.0))
            def _():
                kernel(*refs)
        else:  # CORRUPT_FLAG is injected by shmem.kernel.init_status
            kernel(*refs)

    return wrapped


# ------------------------------------------------------------ chaos schedule


@dataclasses.dataclass
class ChaosEvent:
    """One step of a :class:`ChaosSchedule`: fire ``action`` at the
    ``skip``-th-next :func:`chaos_check` call naming ``site``. For the
    rank-targeted actions (``die``/``revive``) ``site`` holds the decimal
    rank and the event fires at ANY site — rank loss is not tied to a
    particular serving phase."""

    action: str
    site: str
    skip: int = 0

    @property
    def rank(self) -> int | None:
        return int(self.site) if self.action in ("die", "revive") else None


#: Serving-loop injection sites wired through :func:`chaos_check`.
CHAOS_SITES = ("prefill", "decode", "recovery", "probe")
CHAOS_ACTIONS = ("abort", "die", "revive", "stall")


class ChaosSchedule:
    """Deterministic multi-event fault schedule — the multi-fault extension
    of :class:`FaultPlan`.

    The spec is a comma-separated program of ``<action>@<site>[:skip]``
    steps, consumed strictly in order by :func:`chaos_check` calls: the head
    event fires when a check names its site (after letting ``skip`` matching
    checks pass); checks naming other sites pass through untouched. A
    trailing ``heal`` marks the program's end — everything after the last
    injection runs clean. Example::

        abort@decode:1,abort@probe,heal

    reads "let one decode chunk through, abort the second, then fail the
    first half-open probe, then heal" — the double-fault probe arc the
    single-shot FaultPlan cannot express.

    Rank-loss steps use the same shape with a RANK in the site position:
    ``die@<rank>[:skip]`` declares the rank dead (epoch bump + fail-fast
    ``dead_peer``) at the skip-th-next check of ANY site; ``revive@<rank>``
    returns it at a later check without raising. ``die@1:1,revive@1,heal``
    scripts "kill rank 1 at the second serving-loop step, revive it at the
    next one" — the full death → degrade → rebuild → probe → restore arc.
    """

    def __init__(self, spec: str):
        self.spec = spec
        self.events: list[ChaosEvent] = []
        self._lock = threading.Lock()
        tokens = [t.strip() for t in spec.split(",") if t.strip()]
        for i, tok in enumerate(tokens):
            if tok == "heal":
                if i != len(tokens) - 1:
                    raise ValueError(f"'heal' must be last in {spec!r}")
                break
            action, sep, rest = tok.partition("@")
            if not sep or action not in CHAOS_ACTIONS:
                raise ValueError(
                    f"bad chaos step {tok!r} in {spec!r} "
                    f"(want <action>@<site>[:skip], action in {CHAOS_ACTIONS})"
                )
            site, _, skip = rest.partition(":")
            if not site:
                raise ValueError(f"bad chaos step {tok!r} in {spec!r}: empty site")
            if skip and not skip.isdigit():
                raise ValueError(f"bad chaos skip in {tok!r}: want an integer")
            if action in ("die", "revive") and not site.isdigit():
                raise ValueError(
                    f"bad chaos step {tok!r} in {spec!r}: "
                    f"'{action}' targets a rank, want {action}@<rank>[:skip]"
                )
            self.events.append(
                ChaosEvent(action=action, site=site, skip=int(skip or 0))
            )

    @property
    def exhausted(self) -> bool:
        with self._lock:
            return not self.events

    def take(self, site: str) -> ChaosEvent | None:
        """Consume-and-return the head event if this check fires it. Rank
        events (``die``/``revive``) match any site; ``abort`` only its own."""
        with self._lock:
            if not self.events:
                return None
            head = self.events[0]
            if head.rank is None and head.site != site:
                return None
            if head.skip > 0:
                head.skip -= 1
                return None
            return self.events.pop(0)


_CHAOS_CTX: ChaosSchedule | None = None
_CHAOS_ENV: ChaosSchedule | None = None
_CHAOS_ENV_SPEC: str | None = None


def _active_chaos() -> ChaosSchedule | None:
    if _CHAOS_CTX is not None:
        return _CHAOS_CTX
    global _CHAOS_ENV, _CHAOS_ENV_SPEC
    spec = os.environ.get("TDT_CHAOS_SCHEDULE", "").strip()
    if not spec:
        return None
    if spec != _CHAOS_ENV_SPEC:
        # One stateful schedule per spec per process: the program is consumed
        # once, deterministically, and stays exhausted afterwards.
        _CHAOS_ENV_SPEC = spec
        try:
            _CHAOS_ENV = ChaosSchedule(spec)
        except ValueError as e:
            _log(f"[resilience] ignoring bad TDT_CHAOS_SCHEDULE: {e}")
            _CHAOS_ENV = None
    return _CHAOS_ENV


@contextlib.contextmanager
def chaos_schedule(spec: str):
    """Activate a :class:`ChaosSchedule` for :func:`chaos_check` sites inside
    the context (takes precedence over ``TDT_CHAOS_SCHEDULE``)."""
    global _CHAOS_CTX
    sched = ChaosSchedule(spec)
    prev = _CHAOS_CTX
    _CHAOS_CTX = sched
    try:
        yield sched
    finally:
        _CHAOS_CTX = prev


def chaos_check(site: str) -> None:
    """Host-side chaos-injection hook, called by the serving loop at each
    named site. No-op unless an active schedule's head event matches; a
    fired ``abort`` marks 'collectives' degraded and raises
    :class:`CollectiveAbortError` — the same observable failure as a real
    bounded-wait abort, minus the device."""
    sched = _active_chaos()
    if sched is None:
        return
    ev = sched.take(site)
    if ev is None:
        return
    telemetry.inc("tdt_resilience_chaos_injected_total", site=site)
    telemetry.emit("chaos_inject", site=site, action=ev.action, spec=sched.spec)
    reason = f"chaos schedule injected {ev.action} at site '{site}'"
    _log(f"[resilience] {reason}")
    if ev.action == "abort":
        mark_degraded("collectives", reason)
        raise CollectiveAbortError(reason)
    if ev.action == "stall":
        # Wedge the calling thread (the serving loop) while the process —
        # including its introspection endpoint threads — stays alive: the
        # gray-failure shape the fleet progress watchdog exists to detect.
        # Bounded so an unattended schedule cannot hang a process forever.
        time.sleep(get_float_env("TDT_CHAOS_STALL_S", 600.0))
        return
    if ev.action == "die":
        # Route through the same transition real lease expiry takes (board
        # when present, registry otherwise), then surface the loss at this
        # call site exactly as a fused launch would.
        from triton_dist_tpu.runtime import mesh

        board = mesh.health_board()
        if board is not None:
            board.declare_dead(ev.rank, reason="chaos die")
        else:
            declare_rank_dead(ev.rank, reason="chaos die")
        check_dead_peers(kernel=f"chaos@{site}")
    if ev.action == "revive":
        from triton_dist_tpu.runtime import mesh

        board = mesh.health_board()
        if board is not None:
            board.revive(ev.rank)
        else:
            declare_rank_revived(ev.rank)


# ------------------------------------------------------------- wire chaos


#: Wire-level fault actions injected by the fleet router (`TDT_FLEET_CHAOS`).
WIRE_CHAOS_ACTIONS = ("delay", "reset", "hang", "drop")


def _parse_duration_s(text: str) -> float:
    """Parse ``50ms`` / ``0.5s`` / bare seconds into float seconds."""
    t = text.strip().lower()
    try:
        if t.endswith("ms"):
            return float(t[:-2]) / 1000.0
        if t.endswith("s"):
            return float(t[:-1])
        return float(t)
    except ValueError:
        raise ValueError(
            f"bad duration {text!r} (want e.g. '50ms' or '0.5s')"
        ) from None


@dataclasses.dataclass
class WireChaosEvent:
    """One wire fault: ``action`` on calls to ``path``, optionally only for
    replica index ``replica``, after letting ``skip`` matching calls pass.
    ``delay_s`` only applies to the ``delay`` action."""

    action: str
    path: str
    replica: int | None = None
    skip: int = 0
    delay_s: float = 0.0


class WireChaosSchedule:
    """Deterministic wire-fault program for the fleet router's HTTP client —
    :class:`ChaosSchedule`'s grammar, retargeted from serving-loop sites to
    ``/fleet/*`` routes.

    The spec is a comma-separated program of
    ``<action>@<path>[#<replica>][:<arg>]`` steps consumed in order by
    :meth:`take` calls from ``Router._http``:

    * ``delay@/fleet/stream:50ms`` — sleep before the call (straggler);
      the arg is a REQUIRED duration (``50ms`` / ``0.5s``).
    * ``reset@/fleet/stream[:skip]`` — raise ``ConnectionResetError``
      (flaky wire) after letting ``skip`` matching calls pass.
    * ``drop@/fleet/stream[:skip]`` — raise ``TimeoutError`` (lost packet).
    * ``hang@/fleet/stream[:skip]`` — STICKY: once fired, every later call
      matching the path/replica hangs then times out, modelling a wedged
      peer that never comes back (the progress-watchdog arc).

    ``#<replica>`` restricts a step to one replica index; a trailing
    ``heal`` marks the program's end. Example::

        reset@/fleet/stream,hang@/fleet/stream#1:2,heal

    reads "reset the first stream poll anywhere, then wedge replica 1
    starting at its third stream poll, then run clean (except the sticky
    hang)".
    """

    def __init__(self, spec: str):
        self.spec = spec
        self.events: list[WireChaosEvent] = []
        self._sticky: list[WireChaosEvent] = []
        self._lock = threading.Lock()
        tokens = [t.strip() for t in spec.split(",") if t.strip()]
        for i, tok in enumerate(tokens):
            if tok == "heal":
                if i != len(tokens) - 1:
                    raise ValueError(f"'heal' must be last in {spec!r}")
                break
            action, sep, rest = tok.partition("@")
            if not sep or action not in WIRE_CHAOS_ACTIONS:
                raise ValueError(
                    f"bad wire chaos step {tok!r} in {spec!r} (want "
                    f"<action>@<path>[#replica][:arg], action in "
                    f"{WIRE_CHAOS_ACTIONS})"
                )
            target, _, arg = rest.partition(":")
            path, rsep, rep = target.partition("#")
            if not path.startswith("/"):
                raise ValueError(
                    f"bad wire chaos step {tok!r} in {spec!r}: "
                    f"path must start with '/'"
                )
            if rsep and not rep.isdigit():
                raise ValueError(
                    f"bad wire chaos replica in {tok!r}: want an integer index"
                )
            delay_s = 0.0
            skip = 0
            if action == "delay":
                if not arg:
                    raise ValueError(
                        f"bad wire chaos step {tok!r}: 'delay' needs a "
                        f"duration arg, e.g. delay@/fleet/stream:50ms"
                    )
                delay_s = _parse_duration_s(arg)
            elif arg:
                if not arg.isdigit():
                    raise ValueError(
                        f"bad wire chaos skip in {tok!r}: want an integer"
                    )
                skip = int(arg)
            self.events.append(
                WireChaosEvent(
                    action=action,
                    path=path,
                    replica=int(rep) if rsep else None,
                    skip=skip,
                    delay_s=delay_s,
                )
            )

    @property
    def exhausted(self) -> bool:
        with self._lock:
            return not self.events and not self._sticky

    def _matches(self, ev: WireChaosEvent, path: str, replica: int | None) -> bool:
        if ev.path != path:
            return False
        return ev.replica is None or ev.replica == replica

    def take(self, path: str, replica: int | None = None) -> WireChaosEvent | None:
        """Return the fault (if any) this call fires. Sticky hangs fire on
        every matching call; the head program event fires once, in order,
        after its ``skip`` matching calls have passed."""
        with self._lock:
            for ev in self._sticky:
                if self._matches(ev, path, replica):
                    return ev
            if not self.events:
                return None
            head = self.events[0]
            if not self._matches(head, path, replica):
                return None
            if head.skip > 0:
                head.skip -= 1
                return None
            self.events.pop(0)
            if head.action == "hang":
                self._sticky.append(head)
            return head


# ------------------------------------------------------ degradation registry


@dataclasses.dataclass(frozen=True)
class AbortInfo:
    feature: str
    kernel: str
    phase: str
    peer: int
    polls: int
    reason: str


class BreakerState(enum.Enum):
    """Per-feature circuit-breaker state.

    ::

        CLOSED ──mark_degraded──► OPEN ──backoff elapsed──► probe_due()
        begin_probe():       OPEN → HALF_OPEN   (probe thread sees it healthy)
        end_probe(ok=True):  HALF_OPEN → CLOSED (fused routing restored)
        end_probe(ok=False): HALF_OPEN → OPEN   (backoff doubles, capped)
    """

    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"


#: `tdt_degrade_state` gauge encoding (dashboard-friendly ordinal).
_STATE_GAUGE = {
    BreakerState.CLOSED: 0.0,
    BreakerState.HALF_OPEN: 1.0,
    BreakerState.OPEN: 2.0,
}

DEFAULT_DEGRADE_PROBE_S = 30.0
#: Max exponential-backoff multiplier over the probe base (2^6).
PROBE_BACKOFF_CAP = 64.0


@dataclasses.dataclass
class _Breaker:
    feature: str
    state: BreakerState = BreakerState.CLOSED
    reason: str = ""
    failures: int = 0
    opened_at: float = 0.0  # time.monotonic() of the last OPEN transition
    backoff_s: float = 0.0


_LOCK = threading.Lock()
_BREAKERS: dict[str, _Breaker] = {}
_ABORTS: list[AbortInfo] = []
_NOTED: set[str] = set()
#: Thread-local probe exemption: features the current thread is allowed to
#: see as healthy while their breaker is HALF_OPEN (see :func:`probe_scope`).
_PROBE_TLS = threading.local()


def _probe_base_s() -> float:
    return get_float_env("TDT_DEGRADE_PROBE_S", DEFAULT_DEGRADE_PROBE_S)


def _backoff_for(failures: int) -> float:
    base = max(_probe_base_s(), 0.0)
    return base * min(2.0 ** max(failures - 1, 0), PROBE_BACKOFF_CAP)


def _probe_exempt() -> frozenset:
    return getattr(_PROBE_TLS, "features", frozenset())


def _transition(br: _Breaker, to: BreakerState, why: str) -> None:
    # Callers hold _LOCK; telemetry has its own independent lock.
    if br.state is to:
        return
    frm, br.state = br.state, to
    telemetry.inc(
        "tdt_resilience_breaker_transitions_total", feature=br.feature, to=to.value
    )
    telemetry.set_gauge("tdt_degrade_state", _STATE_GAUGE[to], feature=br.feature)
    telemetry.emit(
        "breaker_transition",
        feature=br.feature, from_state=frm.value, to_state=to.value,
        why=why, failures=br.failures,
    )


def mark_degraded(feature: str, reason: str) -> None:
    """OPEN the feature's circuit breaker with a logged reason. Consulted at
    trace time by AUTO routing; a mark while already non-CLOSED is a no-op
    (first reason wins; a failing probe is re-opened by :func:`end_probe`)."""
    with _LOCK:
        br = _BREAKERS.setdefault(feature, _Breaker(feature=feature))
        if br.state is not BreakerState.CLOSED:
            return
        br.reason = reason
        br.failures += 1
        br.backoff_s = _backoff_for(br.failures)
        br.opened_at = time.monotonic()
        _transition(br, BreakerState.OPEN, reason)
    telemetry.inc("tdt_resilience_degradations_total", feature=feature)
    telemetry.emit("degraded", feature=feature, reason=reason)
    _log(f"[resilience] '{feature}' degraded to XLA fallback: {reason}")


def is_degraded(*features: str) -> bool:
    """True when any named feature — or the global 'collectives' flag the
    watchdog sets — has a non-CLOSED breaker. Features under the current
    thread's :func:`probe_scope` read as healthy so a half-open probe can
    trace the fused path."""
    exempt = _probe_exempt()
    with _LOCK:
        for f in (*features, "collectives"):
            br = _BREAKERS.get(f)
            if br is not None and br.state is not BreakerState.CLOSED and f not in exempt:
                return True
    return False


def any_degraded() -> bool:
    exempt = _probe_exempt()
    with _LOCK:
        return any(
            br.state is not BreakerState.CLOSED and f not in exempt
            for f, br in _BREAKERS.items()
        )


def degraded_reasons() -> dict[str, str]:
    with _LOCK:
        return {
            f: br.reason
            for f, br in _BREAKERS.items()
            if br.state is not BreakerState.CLOSED
        }


def breaker_states() -> dict[str, dict]:
    """JSON-safe view of every breaker (the `/healthz` payload section)."""
    now = time.monotonic()
    with _LOCK:
        return {
            f: {
                "state": br.state.value,
                "reason": br.reason or None,
                "failures": br.failures,
                "backoff_s": round(br.backoff_s, 3),
                "probe_in_s": (
                    round(max(br.opened_at + br.backoff_s - now, 0.0), 3)
                    if br.state is BreakerState.OPEN and _probe_base_s() > 0
                    else None
                ),
            }
            for f, br in _BREAKERS.items()
        }


def probe_due() -> list[str]:
    """OPEN features whose backoff has elapsed, ready for a half-open probe
    (empty while probing is disabled via ``TDT_DEGRADE_PROBE_S <= 0``)."""
    if _probe_base_s() <= 0:
        return []
    now = time.monotonic()
    with _LOCK:
        return sorted(
            f
            for f, br in _BREAKERS.items()
            if br.state is BreakerState.OPEN and now - br.opened_at >= br.backoff_s
        )


def begin_probe(features) -> None:
    """OPEN → HALF_OPEN for each named feature (idempotent)."""
    with _LOCK:
        for f in features:
            br = _BREAKERS.get(f)
            if br is not None and br.state is BreakerState.OPEN:
                _transition(br, BreakerState.HALF_OPEN, "probe dispatch")


@contextlib.contextmanager
def probe_scope(features):
    """Exempt the current thread from the named features' breakers so ONE
    sandboxed dispatch can trace the fused path while everything else stays
    degraded. Entry and exit clear jax's caches — the same rule as
    :func:`fault_plan`: routing flags are read at trace time and do not
    participate in jit cache keys."""
    import jax

    prev = _probe_exempt()
    _PROBE_TLS.features = prev | frozenset(features)
    jax.clear_caches()
    try:
        yield
    finally:
        _PROBE_TLS.features = prev
        jax.clear_caches()


def end_probe(features, ok: bool) -> None:
    """Record the probe verdict: CLOSED on success (failure count resets),
    back to OPEN with doubled (capped) backoff on failure."""
    now = time.monotonic()
    outcome = "ok" if ok else "failed"
    with _LOCK:
        for f in features:
            br = _BREAKERS.get(f)
            if br is None:
                continue
            telemetry.inc(
                "tdt_resilience_probes_total", feature=f, outcome=outcome
            )
            if ok:
                br.reason = ""
                br.failures = 0
                br.backoff_s = 0.0
                _transition(br, BreakerState.CLOSED, "probe succeeded")
            else:
                br.failures += 1
                br.backoff_s = _backoff_for(br.failures)
                br.opened_at = now
                _transition(br, BreakerState.OPEN, "probe failed")
    _log(f"[resilience] probe {outcome} for {sorted(features)}")


def reset_degradation() -> None:
    """Clear all breakers, recorded aborts, the dead-rank registry, and the
    mesh epoch (tests / operator full reset)."""
    global _MESH_EPOCH
    with _LOCK:
        _BREAKERS.clear()
        _ABORTS.clear()
        _NOTED.clear()
        _DEAD_RANKS.clear()
        _MESH_EPOCH = 0


def aborts() -> list[AbortInfo]:
    with _LOCK:
        return list(_ABORTS)


def last_abort() -> AbortInfo | None:
    with _LOCK:
        return _ABORTS[-1] if _ABORTS else None


def note_fallback_once(site: str, what: str) -> None:
    """One-time-per-site log line for a degraded-mode route change. The
    telemetry counter increments on EVERY call (fallback traffic volume is
    the operational signal); only the human log line is deduplicated."""
    telemetry.inc("tdt_resilience_fallbacks_total", site=site)
    with _LOCK:
        if site in _NOTED:
            return
        _NOTED.add(site)
    telemetry.emit("fallback", site=site, what=what)
    _log(f"[resilience] {site}: {what} (degraded: {degraded_reasons()})")


def _log(msg: str, level: str = "warn") -> None:
    try:
        tdt_log(msg, level=level)
    except Exception:  # pragma: no cover - never let logging mask the event
        print(msg)


# ----------------------------------------------------------- abort surfacing


def _stamped_epoch(w) -> int | None:
    """Mesh epoch stamped into a status buffer, or None for the 4-word
    pre-epoch layout (older callers construct those directly)."""
    return int(w[4]) if w.size > 4 else None


def describe_status(words) -> str | None:
    """Human-readable abort description for one rank's status words, or
    None when the status is OK. Unit-testable host-side. A stamped mesh
    epoch older than the live one is itself an abort — the executable
    predates a membership reconfiguration — even when the code word is OK."""
    w = np.asarray(words).reshape(-1)
    stamped = _stamped_epoch(w)
    if stamped is not None and stamped != mesh_epoch():
        return (
            f"fenced at stale mesh epoch {stamped} (live epoch "
            f"{mesh_epoch()}): executable predates a reconfiguration"
        )
    if int(w[0]) != STATUS_ABORT:
        return None
    phase = phase_name(int(w[1]))
    peer = int(w[2])
    who = f"peer rank {peer}" if peer >= 0 else "an unattributable peer"
    return (
        f"stalled in phase '{phase}' waiting on {who} "
        f"(bounded-wait abort after {int(w[3])} polls)"
    )


def record_status(words, *, feature: str, kernel: str) -> None:
    """Host callback body: record an abort (degradation + AbortInfo) and
    raise CollectiveAbortError naming the stalled phase and peer rank.
    No-op on an OK status. A stale stamped epoch raises
    :class:`StaleEpochError` deterministically, before the code word is
    even consulted."""
    w = np.asarray(words).reshape(-1)
    stamped = _stamped_epoch(w)
    if stamped is not None and stamped != mesh_epoch():
        reason = (
            f"{feature} collective ({kernel}) fenced: status stamped at "
            f"mesh epoch {stamped}, live epoch is {mesh_epoch()}"
        )
        info = AbortInfo(
            feature=feature, kernel=kernel, phase="stale_epoch",
            peer=-1, polls=0, reason=reason,
        )
        with _LOCK:
            _ABORTS.append(info)
        telemetry.inc(
            "tdt_resilience_stale_epoch_total", feature=feature, kernel=kernel
        )
        telemetry.emit(
            "stale_epoch_abort",
            feature=feature, kernel=kernel,
            stamped=stamped, live=mesh_epoch(),
        )
        mark_degraded(feature, reason)
        raise StaleEpochError(reason)
    desc = describe_status(words)
    if desc is None:
        return
    reason = f"{feature} collective ({kernel}) {desc}"
    info = AbortInfo(
        feature=feature,
        kernel=kernel,
        phase=phase_name(int(w[1])),
        peer=int(w[2]),
        polls=int(w[3]),
        reason=reason,
    )
    with _LOCK:
        _ABORTS.append(info)
    # The acceptance signal for chaos runs: abort counters labeled with the
    # stalled phase and peer rank (low-cardinality: phases are a fixed
    # vocabulary, peers are bounded by world size).
    telemetry.inc(
        "tdt_resilience_aborts_total",
        feature=feature, phase=info.phase, peer=info.peer,
    )
    telemetry.emit(
        "collective_abort",
        feature=feature, kernel=kernel, phase=info.phase,
        peer=info.peer, polls=info.polls,
    )
    # Pin the abort onto whatever request/server span is live (no-op when
    # none is) — the chrome timeline then shows WHICH request's dispatch hit
    # the stalled peer. Lazy import: tracing pulls telemetry which this
    # module also feeds.
    from triton_dist_tpu.runtime import tracing

    tracing.point_current(
        "tdt_resilience_abort", feature=feature, kernel=kernel,
        phase=info.phase, peer=info.peer,
    )
    mark_degraded(feature, reason)
    raise CollectiveAbortError(reason)


def consume_status(status, *, feature: str, kernel: str) -> None:
    """Attach the host-side abort check to a collective's status output.

    Runs per device under shard_map via ``jax.debug.callback`` (kept by its
    debug effect, so it cannot be DCE'd with the unused status value). An
    aborted rank marks the feature degraded FIRST, then raises — the raise
    surfaces through the runtime (typically as an ``XlaRuntimeError``
    wrapping the :class:`CollectiveAbortError` message); callers that
    swallow it can still consult :func:`last_abort` / :func:`is_degraded`.
    """
    import jax

    def _cb(s):
        record_status(s, feature=feature, kernel=kernel)

    jax.debug.callback(_cb, status)


# ------------------------------------------------------------------- watchdog


class CollectiveWatchdog:
    """Host-side wall-time bound on collective dispatch.

    Runs ``fn`` on a worker thread and waits ``timeout_ms`` (growing by
    ``backoff``× per retry, ``TDT_COLL_RETRIES`` extra attempts). A timed-out
    attempt's thread cannot be cancelled — a wedged XLA rendezvous is not
    interruptible — so it is abandoned (daemon) and the watchdog's job is to
    unwedge the SERVING path: mark the feature degraded, then run the
    caller's ``fallback`` (e.g. rebuild on the XLA backend) or raise
    :class:`CollectiveTimeoutError`. ``timeout_ms=0`` disables the watchdog
    (direct call), which is the default — opt in via ``TDT_COLL_TIMEOUT_MS``.
    """

    def __init__(
        self,
        timeout_ms: int | None = None,
        retries: int | None = None,
        backoff: float = 2.0,
        feature: str = "collectives",
        name: str = "collective",
    ):
        self.timeout_ms = (
            get_int_env("TDT_COLL_TIMEOUT_MS", 0) if timeout_ms is None else timeout_ms
        )
        self.retries = (
            get_int_env("TDT_COLL_RETRIES", 2) if retries is None else retries
        )
        self.backoff = backoff
        self.feature = feature
        self.name = name

    def call(self, fn, *args, fallback=None, **kwargs):
        if self.timeout_ms <= 0:
            return fn(*args, **kwargs)
        from triton_dist_tpu.runtime.utils import block_until_ready

        timeout_s = self.timeout_ms / 1e3
        for attempt in range(self.retries + 1):
            result: list = [None]
            err: list = [None]
            done = threading.Event()

            def _run():
                try:
                    # block_until_ready: async dispatch would "finish"
                    # instantly and the device hang would escape the bound.
                    result[0] = block_until_ready(fn(*args, **kwargs))
                except BaseException as e:  # surfaced in the caller thread
                    err[0] = e
                finally:
                    done.set()

            t = threading.Thread(
                target=_run, name=f"{self.name}-watchdog-{attempt}", daemon=True
            )
            t.start()
            if done.wait(timeout_s):
                if err[0] is not None:
                    raise err[0]
                return result[0]
            telemetry.inc("tdt_resilience_watchdog_timeouts_total", name=self.name)
            if attempt < self.retries:
                telemetry.inc("tdt_resilience_watchdog_retries_total", name=self.name)
            telemetry.emit(
                "watchdog_timeout",
                name=self.name, attempt=attempt + 1,
                attempts=self.retries + 1, timeout_ms=timeout_s * 1e3,
            )
            from triton_dist_tpu.runtime import tracing

            tracing.point_current(
                "tdt_resilience_watchdog_timeout",
                name=self.name, attempt=attempt + 1,
            )
            _log(
                f"[resilience] {self.name}: attempt {attempt + 1}/"
                f"{self.retries + 1} exceeded {timeout_s * 1e3:.0f} ms"
            )
            timeout_s *= self.backoff

        reason = (
            f"{self.name} dispatch exceeded {self.timeout_ms} ms watchdog "
            f"({self.retries + 1} attempts, backoff x{self.backoff})"
        )
        mark_degraded(self.feature, reason)
        if fallback is not None:
            return fallback(*args, **kwargs)
        raise CollectiveTimeoutError(reason)
