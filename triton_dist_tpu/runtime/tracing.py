"""Request-scoped span tracing: trace/span ids, a bounded span ring, and
Chrome-trace export.

``runtime.telemetry`` answers "what is this process doing" in aggregate;
nothing answers "where did *this request's* 400 ms go" — the per-request
visibility serving systems treat as table stakes (vLLM's request metrics,
Orca's iteration timeline). This module is that answer:

* **Spans** — named intervals with monotonic timestamps, a ``trace_id``
  grouping one request's (or one process activity's) spans, a ``span_id``,
  and a ``parent_id`` link. Span *names* follow the same
  ``tdt_<subsystem>_<name>`` registry discipline as metric names (enforced
  by ``scripts/check_metric_names.py``); dynamic detail goes in attrs.
* **Bounded span ring** — finished spans append to a process-wide deque
  (``TDT_SPAN_RING`` entries, default 4096); open spans are tracked
  separately so live introspection (``runtime/introspect.py``) can show
  in-flight requests. Completed *traces* also emit one compact ``trace``
  event into the telemetry event ring — the two rings share one story.
* **Sampling** — ``TDT_TRACE_SAMPLE`` (float in [0, 1], default 1.0) is a
  deterministic rate limiter: an error-feedback accumulator admits exactly
  ``rate`` of traces (0.25 → every 4th), so tests and steady-state serving
  see a predictable cadence instead of RNG jitter. Unsampled traces return
  the shared no-op handle — zero allocation per span.
* **Chrome export** — :func:`to_chrome` / :func:`export_chrome` render
  selected traces as a ``chrome://tracing`` / Perfetto JSON: one process
  row (pid) per trace, span attrs in ``args``, and — via the correlation
  id — the in-kernel ``KernelTrace`` phase marks merged onto the same
  timeline so a request span can zoom into ring-protocol phases.
* **Cross-process propagation** — :func:`inject` serializes a trace's
  ``(trace_id, span_id, sampled)`` as a W3C-``traceparent``-style carrier
  a caller stamps into a wire body; :func:`extract` parses it back and
  :func:`continue_trace` opens a trace in the RECEIVING process under the
  sender's trace_id, parented on the sender's span. Traces meant to cross
  processes start with :func:`start_remote_trace` (globally-unique random
  trace id — two processes' local counters would collide); the sender's
  sampling decision travels in the flags byte, so one fleet request is one
  trace everywhere or nowhere. :func:`merge_chrome` renders span lists
  collected from SEVERAL processes as one timeline, one pid per process —
  the fleet router's ``/fleet/trace/<id>`` merge (``docs/fleet.md``).

* **Two sinks** — a live :meth:`Trace.span` (and :func:`span_current`)
  block is also a ``jax.profiler.TraceAnnotation`` of the same name, so
  while a profiler session runs (``jax.profiler.start_trace``) the
  program's spans lie in the profiler's trace, nested as they nest here, on
  the clock the device's operations are stamped on: an idle gap on the
  device can be laid against the span the host was in. Outside a session
  the annotation costs well under a microsecond. Retroactive
  :meth:`Trace.record` intervals have no block to annotate and stay
  ring-only.
* **Self time** — as a live span closes, its duration less what the live
  spans opened inside its block covered is stored as ``self_s`` and observed
  into the ``tdt_span_self_seconds`` digest under ``phase=<span name>``, so
  a window reads as the difference of two snapshots. Spans opened with
  ``ring=False`` (the serving loop's per-iteration phases) go to the
  profiler and the digest only: they would otherwise halve the seconds of
  serving the ring holds.
* **The device ledger** — the engine says when it *issued* a step program
  (:func:`device_issued`) and when a *wait* for one returned
  (:func:`device_waited`). From a wait that leaves nothing issued unwaited
  to the next issue the device is **starved**: it has no step program to
  run, whatever the host is busy with. The ledger keeps the running total
  of those seconds on this module's clock; a live span takes it as it
  opens and closes, as it takes the time, and what the spans inside it did
  not cover is its starved self time, ``tdt_span_starved_seconds`` under
  ``phase=<span name>``. So "where did the device starve, by span" reads
  from two snapshots, with no profiler (``docs/observability.md``, "The
  loop's spans").

Clocks: spans stamp raw ``time.monotonic()`` seconds. Callers whose
bookkeeping lives in another monotonic-derived clock (the serving loop's
server-relative ``_now()``) convert with a constant offset before calling
:meth:`Trace.record` — see ``serving/scheduler.py``. Chrome export
normalizes all timestamps to the earliest exported span, so mixed-epoch
traces still render.

Correlation with ``KernelTrace``: the kernel-trace collector
(``telemetry.consume_kernel_trace``) stamps the ACTIVE span's
``(trace_id, span_id)`` into each collected record at jit-trace time —
the time the kernel is built, which under serving happens inside the
first request's prefill/decode span. :func:`to_chrome` with
``kernel_traces=True`` files those records under the owning trace's row.

Env knobs::

    TDT_TRACE_SAMPLE   fraction of traces recorded (default 1.0; 0 = off)
    TDT_SPAN_RING      finished-span ring capacity (default 4096)

Tracing inherits telemetry's master gate: ``TDT_TELEMETRY=0`` disables
span collection too (same single-cached-bool no-op path).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import re
import threading
import time
from typing import Any, Mapping, NamedTuple

import jax

from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.utils import get_float_env, get_int_env

# -------------------------------------------------------------------- storage

_LOCK = threading.Lock()
_SPANS: collections.deque | None = None  # finished spans, oldest first
_OPEN: dict[int, dict] = {}  # span_id -> span dict (started, not finished)
_IDS = itertools.count(1)
_SAMPLE_ACC = 0.0  # error-feedback accumulator for deterministic sampling
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "tdt_current_span", default=None
)

#: The ``after`` of an interval in which there was no work for the device:
#: counted apart, and no span's starved time.
NO_WORK = "no_work"
# The device ledger (one a process, as the digest is; written by the one
# thread that drives the engine, read by whoever closes a span).
_ISSUED = 0  # ticket of the newest step program issued
_STARVED_S = 0.0  # starved seconds of the closed intervals, no_work's left out
_STARVED_SINCE: float | None = None  # the open interval's start; None: a program may be running
_STARVED_AFTER = ""  # the wait that began the open interval


def _ring() -> collections.deque:
    global _SPANS
    if _SPANS is None:
        _SPANS = collections.deque(maxlen=max(get_int_env("TDT_SPAN_RING", 4096), 1))
    return _SPANS


def now_s() -> float:
    """The tracing clock: raw ``time.monotonic()`` seconds. Public so
    callers with retroactive intervals in another clock can compute the
    constant conversion offset (``now_s() - other_clock_now``)."""
    return time.monotonic()


def sample_rate() -> float:
    """``TDT_TRACE_SAMPLE`` clamped to [0, 1]. Read per trace start (cheap;
    honors mid-process changes in tests)."""
    return min(max(get_float_env("TDT_TRACE_SAMPLE", 1.0), 0.0), 1.0)


def enabled() -> bool:
    """Tracing rides telemetry's master gate (``TDT_TELEMETRY=0`` disables
    both) and is additionally off when the sample rate is 0."""
    return telemetry.enabled() and sample_rate() > 0.0


def reset() -> None:
    """Drop every span (finished and open) and restart ids + the sampling
    accumulator. Tests and operator resets only."""
    global _SPANS, _IDS, _SAMPLE_ACC
    global _ISSUED, _STARVED_S, _STARVED_SINCE, _STARVED_AFTER
    with _LOCK:
        _SPANS = None
        _OPEN.clear()
        _IDS = itertools.count(1)
        _SAMPLE_ACC = 0.0
        _ISSUED, _STARVED_S, _STARVED_SINCE, _STARVED_AFTER = 0, 0.0, None, ""


def _clean_attrs(attrs: Mapping[str, Any]) -> dict:
    return {
        k: (v if isinstance(v, (str, int, float, bool, type(None))) else str(v))
        for k, v in attrs.items()
    }


# --------------------------------------------------------------------- traces


class Trace:
    """Handle for one trace: a root span plus child spans callers add via
    :meth:`span` (live, context-managed), :meth:`record` (retroactive
    interval), and :meth:`point` (zero-duration marker). Thread-compatible
    the same way the telemetry registry is: every mutation takes the module
    lock, so a submit thread and the serving loop can both touch it."""

    __slots__ = ("trace_id", "root_id", "sampled", "_name")

    def __init__(self, trace_id: int, root_id: int, name: str, sampled: bool):
        self.trace_id = trace_id
        self.root_id = root_id
        self.sampled = sampled
        self._name = name

    # -- span creation ------------------------------------------------------
    def span(self, name: str, /, parent_id: int | None = None,
             ring: bool = True, **attrs):
        """Context manager: one live span, timed around the block. Sets the
        ambient current span (contextvar) so nested spans and the
        resilience abort hook parent correctly. Yields the span dict —
        mutate ``["attrs"]`` inside the block to attach results.

        ``ring=False`` keeps the span out of the finished-span ring, the
        open-span table and the flight recorder: it still nests, reaches the
        profiler and feeds the self-time digest (the module doc's two sinks).

        ``name`` is positional-only (here and on every span entry point)
        so ``name=...`` stays available as an attribute key — the watchdog
        labels its timeout points with the collective's name."""
        if not self.sampled:
            return contextlib.nullcontext()
        return _live_span(self.trace_id, name, parent_id, self.root_id, ring, attrs)

    def record(self, name: str, start_s: float, end_s: float, /,
               parent_id: int | None = None, **attrs) -> int | None:
        """Retroactive span: an interval measured by the caller (in the
        tracing clock — convert first, see the module doc). Returns the
        span_id so siblings can reference it (shared-dispatch attribution)."""
        if not self.sampled:
            return None
        sp = _start_span(
            self.trace_id, name,
            parent_id if parent_id is not None else self.root_id,
            attrs, start_s=start_s,
        )
        _finish_span(sp, end_s=end_s)
        return sp["span_id"]

    def point(self, name: str, /, parent_id: int | None = None, **attrs) -> int | None:
        """Zero-duration marker span at now."""
        t = now_s()
        return self.record(
            name, t, t,
            parent_id=(parent_id if parent_id is not None
                       else _ambient_parent(self.trace_id, self.root_id)),
            **attrs,
        )

    def finish(self, **attrs) -> None:
        """Close the root span and emit one compact ``trace`` event into the
        telemetry event ring (the two rings' join point). Idempotent."""
        if not self.sampled:
            return
        with _LOCK:
            sp = _OPEN.get(self.root_id)
        if sp is None:
            return
        if attrs:
            sp["attrs"].update(_clean_attrs(attrs))
        _finish_span(sp)
        telemetry.emit(
            "trace", trace_id=self.trace_id, name=self._name,
            dur_s=round(sp["end_s"] - sp["start_s"], 6),
            n_spans=len(spans(self.trace_id)),
        )


class _NoopTrace(Trace):
    """Shared unsampled handle: every method an allocation-free no-op."""

    def __init__(self):
        super().__init__(0, 0, "", False)


NOOP_TRACE = _NoopTrace()


def _sampler_admits() -> bool:
    """Advance the deterministic error-feedback sampler one trace."""
    global _SAMPLE_ACC
    rate = sample_rate()
    with _LOCK:
        _SAMPLE_ACC += rate
        take = _SAMPLE_ACC >= 1.0
        if take:
            _SAMPLE_ACC -= 1.0
    return take


def start_trace(name: str, /, **attrs) -> Trace:
    """Open a new trace (root span starts now). Returns the shared no-op
    handle when tracing is disabled or the sampler skips this trace — all
    Trace methods stay safe to call unconditionally."""
    if not telemetry.enabled() or not _sampler_admits():
        return NOOP_TRACE
    trace_id = next(_IDS)
    sp = _start_span(trace_id, name, None, attrs)
    return Trace(trace_id, sp["span_id"], name, True)


# ---------------------------------------------------- cross-process propagation


class SpanContext(NamedTuple):
    """The propagated identity of a span in another process: enough for a
    receiver to parent its own spans under it. What :func:`inject` carries
    and :func:`extract` returns."""

    trace_id: int
    span_id: int
    sampled: bool


#: ``version-traceid(32 hex)-spanid(16 hex)-flags`` (W3C traceparent shape).
_TRACEPARENT = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def new_trace_id() -> int:
    """A globally-unique (random 63-bit) trace id for traces that will cross
    process boundaries. Local trace ids come from a per-process counter, so
    two processes both mint 1, 2, 3… — a propagated trace needs an id no
    receiving process could collide with."""
    return (int.from_bytes(os.urandom(8), "big") >> 1) or 1


def start_remote_trace(name: str, /, **attrs) -> Trace:
    """:func:`start_trace`, but with a :func:`new_trace_id` — the entry
    point for a trace that will be :func:`inject`-ed to other processes
    (the fleet router's one-trace-per-request)."""
    if not telemetry.enabled() or not _sampler_admits():
        return NOOP_TRACE
    trace_id = new_trace_id()
    sp = _start_span(trace_id, name, None, attrs)
    return Trace(trace_id, sp["span_id"], name, True)


def inject(trace: Trace, span_id: int | None = None) -> dict:
    """Serialize ``(trace_id, span_id, sampled)`` as a W3C-traceparent-style
    carrier dict to stamp into a wire body. ``span_id`` picks the span the
    receiver should parent under (default: the root span). Unsampled traces
    inject flags ``00`` so the receiver no-ops too — the sampling decision
    is made once, at the trace's origin."""
    sid = trace.root_id if span_id is None else int(span_id)
    flags = "01" if trace.sampled else "00"
    return {"traceparent": f"00-{trace.trace_id:032x}-{sid:016x}-{flags}"}


def extract(carrier) -> SpanContext | None:
    """Parse a carrier produced by :func:`inject` (the dict, or the raw
    ``traceparent`` string). Returns None on anything missing or malformed —
    the caller falls back to a local root trace, never errors: a bad peer
    must not be able to break admission."""
    if carrier is None:
        return None
    tp = carrier.get("traceparent") if isinstance(carrier, Mapping) else carrier
    if not isinstance(tp, str):
        return None
    m = _TRACEPARENT.match(tp.strip().lower())
    if m is None or m.group(1) == "ff":
        return None
    trace_id = int(m.group(2), 16)
    span_id = int(m.group(3), 16)
    if trace_id == 0 or span_id == 0:
        return None
    return SpanContext(trace_id, span_id, bool(int(m.group(4), 16) & 1))


def parse_trace_id(s: str) -> int | None:
    """Parse a trace id off a URL path: 32-hex (the traceparent form
    :func:`inject` emits) as hex, all-digits as decimal (local counter
    ids); None on anything else — the ``/fleet/trace/<id>`` routes' shared
    input gate."""
    s = s.strip().lower()
    if re.fullmatch(r"[0-9a-f]{32}", s):
        return int(s, 16)
    if s.isdigit():
        return int(s)
    return None


def continue_trace(ctx: SpanContext | None, name: str, /, **attrs) -> Trace:
    """Open a trace that CONTINUES a remote one: same trace_id, root span
    parented under the remote span. Sampling follows the SENDER's decision
    (the flags byte), not the local sampler — one fleet request is one
    trace in every process or in none. ``ctx=None`` (no carrier on the
    wire) falls back to a plain local :func:`start_trace`, so standalone
    operation is unchanged."""
    if ctx is None:
        return start_trace(name, **attrs)
    if not telemetry.enabled() or not ctx.sampled:
        return NOOP_TRACE
    sp = _start_span(ctx.trace_id, name, ctx.span_id, attrs)
    return Trace(ctx.trace_id, sp["span_id"], name, True)


@contextlib.contextmanager
def root_span(name: str, /, **attrs):
    """One-shot trace whose root span wraps the block (``Engine._build``
    style process activities). Yields the Trace handle."""
    t = start_trace(name, **attrs)
    try:
        yield t
    finally:
        t.finish()


def _ambient_parent(trace_id: int, default: int) -> int:
    """The ambient span as a parent for a span of ``trace_id``: its nearest
    ancestor the ring holds, and ``default`` where the ambient span belongs
    to another trace (a request's span opened inside the server's loop
    iteration stays in the request's own tree)."""
    cur = _CURRENT.get()
    if cur is None or cur["trace_id"] != trace_id:
        return default
    return _anchor(cur)


def _anchor(sp: dict) -> int:
    """The id children, points and correlations of live span ``sp`` hang on:
    its own where the ring holds it, else its nearest ancestor's that does."""
    return sp.get("anchor_id", sp["span_id"])


@contextlib.contextmanager
def _live_span(trace_id: int, name: str, parent_id: int | None, root_id: int,
               ring: bool, attrs: Mapping[str, Any]):
    """The one place a live span opens and closes: ring and profiler."""
    if parent_id is None:
        parent_id = _ambient_parent(trace_id, root_id)
    sp = _start_span(trace_id, name, parent_id, attrs, ring=ring)
    if not ring:
        sp["anchor_id"] = parent_id
    sp["child_s"] = sp["child_starved_s"] = 0.0
    starved0 = _starved_at(sp["start_s"])
    outer = _CURRENT.get()
    tok = _CURRENT.set(sp)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield sp
    finally:
        _CURRENT.reset(tok)
        end = now_s()
        dur = end - sp["start_s"]
        starved = _starved_at(end) - starved0
        sp["self_s"] = max(dur - sp.pop("child_s"), 0.0)
        sp["starved_s"] = max(starved - sp.pop("child_starved_s"), 0.0)
        if outer is not None and "child_s" in outer:
            outer["child_s"] += dur
            outer["child_starved_s"] += starved
        if ring:
            _finish_span(sp, end_s=end)
        else:
            sp["end_s"] = end
        telemetry.observe_digest("tdt_span_self_seconds", sp["self_s"], phase=name)
        if sp["starved_s"] > 0.0:
            telemetry.observe_digest(
                "tdt_span_starved_seconds", sp["starved_s"], phase=name)


def span_current(name: str, /, **attrs):
    """A live child span of the ambient span, in the ambient span's trace
    (a no-op context where none is live): how a layer below the one that
    owns the trace — the engine under the server's loop — puts a span on
    its own side of the boundary. Never in the ring (see
    :meth:`Trace.span`)."""
    cur = _CURRENT.get()
    if cur is None:
        return contextlib.nullcontext()
    return _live_span(cur["trace_id"], name, _anchor(cur), _anchor(cur), False, attrs)


def _start_span(trace_id: int, name: str, parent_id: int | None,
                attrs: Mapping[str, Any], start_s: float | None = None,
                ring: bool = True) -> dict:
    sp = {
        "trace_id": trace_id,
        "span_id": next(_IDS),
        "parent_id": parent_id,
        "name": name,
        "start_s": now_s() if start_s is None else float(start_s),
        "end_s": None,
        "attrs": _clean_attrs(attrs),
    }
    if ring:
        with _LOCK:
            _OPEN[sp["span_id"]] = sp
        _flight_span("span_start", sp)
    return sp


def _finish_span(sp: dict, end_s: float | None = None) -> None:
    sp["end_s"] = now_s() if end_s is None else float(end_s)
    with _LOCK:
        _OPEN.pop(sp["span_id"], None)
        _ring().append(sp)
    _flight_span("span_end", sp)


def _flight_span(event: str, sp: dict) -> None:
    """Mirror one span edge into the crash-surviving flight recorder (when
    one is active): the span-start breadcrumbs are how a postmortem knows
    which request/slot/span a SIGKILL'd process was executing — attrs ride
    along so ``req_id``/``slot`` survive with the span."""
    if not telemetry.flight_active():
        return
    telemetry.flight(event, **{
        **sp["attrs"],
        "name": sp["name"], "trace_id": sp["trace_id"],
        "span_id": sp["span_id"], "parent_id": sp["parent_id"],
    })


# ------------------------------------------------------------- ambient access


def current_span() -> dict | None:
    """The innermost live ``Trace.span`` block's span on this thread/context
    (None outside any). Resilience's abort hook parents to it."""
    return _CURRENT.get()


def current_correlation() -> tuple[int, int] | None:
    """``(trace_id, span_id)`` of the ambient span — the correlation id the
    kernel-trace collector stamps into records at jit-trace time."""
    cur = _CURRENT.get()
    if cur is None:
        return None
    return cur["trace_id"], _anchor(cur)


def point_current(name: str, /, **attrs) -> None:
    """Zero-duration marker attached to the ambient span's trace (no-op when
    no span is live) — how ``resilience.record_status`` drops a collective
    abort onto whatever request/server timeline was running."""
    cur = _CURRENT.get()
    if cur is None:
        return
    t = now_s()
    sp = _start_span(cur["trace_id"], name, _anchor(cur), attrs, start_s=t)
    _finish_span(sp, end_s=t)


# ---------------------------------------------------------- the device ledger


def device_issued() -> int:
    """The engine has just issued a step program (a prefill chunk, the pool
    scatter, a decode chunk, a speculative chunk): the device has work, and
    the starved interval that was open ends here, counted into
    ``tdt_engine_device_starved_seconds_total`` under ``after=<the wait that
    began it>``. Returns the program's ticket, which the wait for it hands
    to :func:`device_waited`. The helper programs a step is surrounded by (a
    key split, a buffer of zeros, sampling one row) are not told: the device
    runs each in microseconds, and the host's time in them is what the
    ledger measures. With telemetry off: ticket 0, nothing kept, no clock
    read."""
    global _ISSUED, _STARVED_SINCE
    if not telemetry.enabled():
        return 0
    _ISSUED += 1
    if _STARVED_SINCE is not None:
        _close_starved(now_s())
        _STARVED_SINCE = None
    return _ISSUED


def device_waited(ticket: int, after: str) -> None:
    """A wait for the step program ``ticket`` has returned. The device runs
    its programs in the order of their issue, so if that program is the
    newest issued nothing is left for the device to run, and a starved
    interval opens, named ``after`` the wait. If a later program was issued
    meanwhile (a decode chunk landed behind the next one's issue, a program
    nobody waits for) the device is not known starved and nothing changes:
    where programs go unwaited the total is a lower bound."""
    global _STARVED_SINCE, _STARVED_AFTER
    if ticket and ticket == _ISSUED and _STARVED_SINCE is None:
        _STARVED_SINCE, _STARVED_AFTER = now_s(), after


def device_ticket() -> int:
    """The ticket of the newest step program issued (0 with telemetry off):
    what a wait for a result the device computes after everything issued so
    far (a sample made behind it) hands to :func:`device_waited`."""
    return _ISSUED if telemetry.enabled() else 0


def device_no_work() -> None:
    """The serving loop has found nothing to do (no tenant, no request
    due): the open interval closes where it stands, under the wait that
    began it (the host was finishing that work), and what follows, up to
    the next issue, is counted under ``after="no_work"`` and is no span's
    starved time: a server with nothing to serve is not starving its
    device."""
    global _STARVED_SINCE, _STARVED_AFTER
    if _STARVED_SINCE is None or _STARVED_AFTER == NO_WORK:
        return
    now = now_s()
    _close_starved(now)
    _STARVED_SINCE, _STARVED_AFTER = now, NO_WORK


def device_starved_s() -> float:
    """The ledger's running total now: starved seconds since the process
    began, the open interval included, ``no_work`` left out."""
    return _starved_at(now_s())


def _starved_at(t: float) -> float:
    """The running total at ``t``, a time not before the last call."""
    if _STARVED_SINCE is None or _STARVED_AFTER == NO_WORK:
        return _STARVED_S
    return _STARVED_S + (t - _STARVED_SINCE)


def _close_starved(now: float) -> None:
    global _STARVED_S
    dt = now - _STARVED_SINCE
    telemetry.inc("tdt_engine_device_starved_seconds_total", dt, after=_STARVED_AFTER)
    if _STARVED_AFTER != NO_WORK:
        _STARVED_S += dt


# ------------------------------------------------------------- jit cache misses

#: The event jax records once for each jit cache miss: the program is traced
#: and lowered, whether or not the persistent cache then spares the compile.
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_WATCHING_LOWERINGS = False


def watch_lowerings() -> None:
    """Count jit cache misses in ``tdt_jit_lowerings_total`` and drop a
    ``tdt_jit_lowering`` point into the ambient span, so that "which step
    recompiled" has an answer inside the program. Idempotent; called when an
    engine is built (jax keeps a listener for the life of the process)."""
    global _WATCHING_LOWERINGS
    if _WATCHING_LOWERINGS:
        return
    _WATCHING_LOWERINGS = True
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _on_jax_duration(event: str, duration: float, **kwargs) -> None:
    if event != LOWERING_EVENT:
        return
    telemetry.inc("tdt_jit_lowerings_total")
    point_current(
        "tdt_jit_lowering", seconds=round(duration, 6),
        fun_name=kwargs.get("fun_name"),
    )


# -------------------------------------------------------------------- queries


def spans(trace_id: int | None = None, include_open: bool = False) -> list[dict]:
    """Finished spans, oldest first (optionally one trace; optionally with
    the still-open spans appended — introspection's in-flight view)."""
    with _LOCK:
        out = list(_SPANS or ())
        if include_open:
            out += [dict(sp) for sp in _OPEN.values()]
    if trace_id is not None:
        out = [s for s in out if s["trace_id"] == trace_id]
    return out


def trace_ids() -> list[int]:
    """Distinct trace ids with at least one finished or open span, ascending."""
    with _LOCK:
        ids = {s["trace_id"] for s in (_SPANS or ())}
        ids.update(sp["trace_id"] for sp in _OPEN.values())
    return sorted(ids)


def last_trace_id() -> int | None:
    ids = trace_ids()
    return ids[-1] if ids else None


def snapshot_traces() -> dict:
    """JSON-safe dump of the span rings — the ``"traces"`` section
    ``telemetry.dump`` and the ``/snapshot`` route attach: per-trace span
    lists plus open-span count."""
    with _LOCK:
        finished = [dict(s) for s in (_SPANS or ())]
        open_spans = [dict(s) for s in _OPEN.values()]
    by_trace: dict[int, list] = {}
    for s in finished + open_spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    return {
        "n_spans": len(finished),
        "n_open": len(open_spans),
        "traces": [
            {"trace_id": tid, "spans": sorted(sps, key=lambda s: s["start_s"])}
            for tid, sps in sorted(by_trace.items())
        ],
    }


# --------------------------------------------------------------- chrome export


def to_chrome(trace_id: int | list[int] | None = None,
              kernel_traces: bool = False) -> dict:
    """Render traces as a ``chrome://tracing`` JSON dict.

    One process row (pid) per trace_id, named after its root span +
    request attrs; every span an ``"X"`` event with attrs in ``args`` and
    the span/parent ids included so the chain is machine-checkable.
    Timestamps normalize to the earliest exported span (µs). Open spans
    export with their duration running to now.

    ``kernel_traces=True`` merges ``telemetry.kernel_traces()`` records
    whose correlation id (stamped at jit-trace time) belongs to an
    exported trace: each in-kernel event lands on the owning trace's row
    at tid ``1000 + rank`` — sequence-numbered (the in-kernel clock is
    event ORDER, see ``tools/profiler.py``), so the zoomed view reads as a
    schedule, not wall time."""
    if trace_id is None:
        ids = set(trace_ids())
    elif isinstance(trace_id, int):
        ids = {trace_id}
    else:
        ids = set(trace_id)
    all_spans = [s for s in spans(include_open=True) if s["trace_id"] in ids]
    if not all_spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(s["start_s"] for s in all_spans)
    t_now = now_s()
    events: list[dict] = []
    named: set[int] = set()
    for s in sorted(all_spans, key=lambda x: x["start_s"]):
        if s["trace_id"] not in named:
            named.add(s["trace_id"])
            label = s["name"] if s["parent_id"] is None else f"trace {s['trace_id']}"
            req = s["attrs"].get("req_id")
            if req is not None:
                label = f"{label} req={req}"
            events.append({
                "name": "process_name", "ph": "M", "pid": s["trace_id"],
                "args": {"name": f"{label} [trace {s['trace_id']}]"},
            })
        end = s["end_s"] if s["end_s"] is not None else t_now
        events.append({
            "name": s["name"], "ph": "X",
            "ts": (s["start_s"] - t0) * 1e6,
            "dur": max((end - s["start_s"]) * 1e6, 0.0),
            "pid": s["trace_id"], "tid": 0,
            "args": {
                **s["attrs"], "span_id": s["span_id"],
                "parent_id": s["parent_id"],
                **({} if s["end_s"] is not None else {"open": True}),
            },
        })
    if kernel_traces:
        for rec in telemetry.kernel_traces():
            corr = rec.get("corr")
            if not corr or corr[0] not in ids:
                continue
            tid = 1000 + int(rec.get("rank", 0))
            for e in rec.get("events", ()):
                events.append({
                    "name": f"{rec.get('kernel', 'kernel')}:{e['tag']}",
                    "ph": "X", "ts": float(e["seq"]), "dur": 1.0,
                    "pid": corr[0], "tid": tid,
                    "args": {"step": e["step"], "aux": e["aux"],
                             "corr_span": corr[1]},
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def merge_chrome(segments: list[dict], trace_id: int | None = None) -> dict:
    """Merge span lists collected from SEVERAL processes into one
    chrome://tracing JSON — the cross-process counterpart of
    :func:`to_chrome`.

    Each segment is ``{"label": str, "pid": int, "spans": [span dicts]}``
    (spans in the wire shape ``spans()`` / the ``/fleet/trace/<id>`` route
    return). One process row per segment, ``trace_id`` optionally filters
    every segment to one trace, and timestamps normalize to the earliest
    span across ALL segments. Same-host processes share the monotonic
    clock's boot epoch (Linux ``CLOCK_MONOTONIC``), so a router and its
    replica subprocesses align on one real timeline; spans still open in a
    segment (a snapshot of a live process) render to the latest end seen.
    ``span_id``/``parent_id`` stay in ``args`` — ids are per-process, so
    chains are machine-checkable WITHIN a segment and across the injected
    parent link (a receiver's root span carries the sender's span id)."""
    segs = []
    all_spans: list[dict] = []
    for i, seg in enumerate(segments):
        sps = [s for s in seg.get("spans", ())
               if trace_id is None or s.get("trace_id") == trace_id]
        if not sps:
            continue
        segs.append((seg.get("label", f"proc{i}"), seg.get("pid", i), sps))
        all_spans.extend(sps)
    if not all_spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(s["start_s"] for s in all_spans)
    t_end = max(
        (s["end_s"] if s["end_s"] is not None else s["start_s"])
        for s in all_spans
    )
    events: list[dict] = []
    for label, pid, sps in segs:
        events.append({
            "name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": label},
        })
        for s in sorted(sps, key=lambda x: x["start_s"]):
            end = s["end_s"] if s["end_s"] is not None else t_end
            events.append({
                "name": s["name"], "ph": "X",
                "ts": (s["start_s"] - t0) * 1e6,
                "dur": max((end - s["start_s"]) * 1e6, 0.0),
                "pid": pid, "tid": 0,
                "args": {
                    **s["attrs"], "span_id": s["span_id"],
                    "parent_id": s["parent_id"], "proc": label,
                    **({} if s["end_s"] is not None else {"open": True}),
                },
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome(path: str, trace_id: int | list[int] | None = None,
                  kernel_traces: bool = False) -> str:
    """Write :func:`to_chrome` JSON; returns the path (open the file in
    ``chrome://tracing`` or ui.perfetto.dev)."""
    with open(path, "w") as f:
        json.dump(to_chrome(trace_id, kernel_traces=kernel_traces), f)
    return path
