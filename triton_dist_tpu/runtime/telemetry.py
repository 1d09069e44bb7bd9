"""Process-wide runtime telemetry: metrics registry + structured event ring.

Production systems attribute most debugging wins to always-on telemetry
rather than offline profilers (MegaScale's observability discipline); the
reference repo's intra-kernel profiler answers "what did kernel X do" but
nothing answers "what is this *process* doing right now". This module is
that answer, and every later perf/robustness layer reports through it:

* **Metrics registry** — counters, gauges, histograms with fixed
  log-scale buckets, and mergeable quantile :class:`Digest` sketches
  (DDSketch-style log-γ buckets, relative error ``DIGEST_ALPHA``; see
  ``observe_digest``), all labeled
  (``telemetry.inc("tdt_engine_serve_total", backend="dist_ar")``).
  Metric names follow ``tdt_<subsystem>_<name>`` (enforced by
  ``scripts/check_metric_names.py``); label VALUES may be dynamic but
  must stay low-cardinality (rank ids, phase names — never shapes or
  pointers).
* **Structured event ring** — ``emit(kind, **fields)`` appends one dict to
  a bounded ring (``TDT_EVENT_RING`` entries, default 1024): the
  machine-readable replacement for resilience's ad-hoc ``_log`` lines.
* **Exporters** — :func:`snapshot` / :func:`dump` (JSON) and
  :func:`to_prometheus` (text exposition), surfaced by the
  ``scripts/tdt_metrics.py`` CLI.
* **Kernel-trace collector** — when ``TDT_KERNEL_TRACE=1`` (read at TRACE
  time, like FaultPlans), the allgather / gemm-allreduce kernels thread a
  ``tools.profiler.KernelTrace`` SMEM buffer and the host callback here
  decodes each rank's events into a bounded ring; merge them into one
  chrome://tracing JSON via ``tools.profiler.decode_to_chrome``.

Zero-overhead path: ``TDT_TELEMETRY=0`` makes every instrumentation call a
single cached-bool check and early return — no allocation, no lock, no
string formatting. The flag is resolved once per process (first call);
:func:`reset` re-reads it, which is how tests flip it.

Thread-safety contract (audited for the ``runtime/introspect.py`` HTTP
thread reading concurrently with the serving loop writing): every mutation
and every reader (:func:`snapshot`, :func:`events`, :func:`summary`,
:func:`kernel_traces`, :func:`counter_value`) copies shared state under
``_LOCK``, so readers always see a consistent point-in-time view and never
iterate a deque mid-append. Two races are tolerated by design: (a) the
:func:`enabled` lazy resolve is an unlocked read-then-write of a bool —
two threads may both resolve it, converging on the same env-derived value
(benign); (b) a reader racing :func:`reset` may observe either the old or
the empty registry, never a torn one. ``tests/test_telemetry.py`` has a
threaded stress test pinning this contract.

Counting semantics on this runtime: jit means most call sites run at TRACE
time, so counters like ``tdt_shmem_collective_calls`` count *traced
launches* (one per compilation), not per-step executions — which is exactly
the signal routing bugs need ("AUTO flipped methods between traces").
Host-side sites (``Engine.serve``, watchdog, abort callbacks) count real
runtime occurrences. See ``docs/observability.md``.

**Flight recorder** (``TDT_FLIGHT_RECORDER=<dir>``): the event ring and the
``TDT_TELEMETRY_DUMP`` atexit hook both die with the process — a SIGKILL
takes the whole story with it. The :class:`FlightRecorder` is the
crash-surviving sibling: a bounded ring of fixed-size records in an
mmap-backed file that :func:`emit` (and the span tracer, via
:func:`flight`) appends to with no fsync on the hot path. Once the bytes
are memcpy'd into the mapping the KERNEL owns the dirty pages, so a
SIGKILL'd process loses at most the one record being written at death
(dropped by :meth:`FlightRecorder.read`'s torn-record check) — only power
loss can lose more. :func:`flight_postmortem` folds a recovered ring into
"what was this process doing when it died".

Env flags::

    TDT_TELEMETRY        0 disables all collection (default 1)
    TDT_TELEMETRY_DUMP   path: dump a JSON snapshot at process exit
    TDT_EVENT_RING       event-ring capacity (default 1024)
    TDT_KERNEL_TRACE     1 wires KernelTrace into adopted kernels (default 0)
    TDT_FLIGHT_RECORDER  dir: crash-surviving mmap event ring (default off)
    TDT_FLIGHT_RECORDS   flight-ring record capacity (default 1024)
"""

from __future__ import annotations

import collections
import json
import math
import mmap
import os
import struct
import threading
import time
from typing import Any, Iterable, Mapping

from triton_dist_tpu.runtime.utils import get_bool_env, get_int_env

# ----------------------------------------------------------------- enable gate

_ENABLED: bool | None = None  # resolved lazily; reset() re-resolves


def enabled() -> bool:
    """Cached ``TDT_TELEMETRY`` gate — the no-op path's single check."""
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = get_bool_env("TDT_TELEMETRY", True)
    return _ENABLED


def kernel_trace_enabled() -> bool:
    """``TDT_KERNEL_TRACE`` gate, read at TRACE time by adopted kernels.

    Deliberately NOT cached: flipping it between jit traces is how a test
    (or an operator with fresh functions) turns tracing on — but like every
    trace-time flag here it does not participate in jit cache keys, so a
    cached executable keeps its previous setting until caches clear."""
    return enabled() and get_bool_env("TDT_KERNEL_TRACE", False)


# -------------------------------------------------------------------- storage

# Fixed log2-scale histogram bounds: ~1 µs .. 64 s in doubling steps. One
# static tuple shared by every histogram keeps bucketing allocation-free and
# cross-metric comparable; latencies outside the span land in the first /
# +Inf bucket with count+sum still exact.
DEFAULT_BUCKETS: tuple[float, ...] = tuple(2.0**e for e in range(-20, 7))

_LOCK = threading.Lock()
_COUNTERS: dict[tuple[str, tuple], float] = {}
_GAUGES: dict[tuple[str, tuple], float] = {}
# histogram value: [counts per bucket + overflow, total_sum, n]
_HISTS: dict[tuple[str, tuple], list] = {}
_DIGESTS: dict[tuple[str, tuple], "Digest"] = {}
_EVENT_SEQ = 0
_EVENTS: collections.deque | None = None
_KTRACES: collections.deque = collections.deque(maxlen=64)


def _ring() -> collections.deque:
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = collections.deque(maxlen=max(get_int_env("TDT_EVENT_RING", 1024), 1))
    return _EVENTS


def _key(name: str, labels: Mapping[str, Any]) -> tuple[str, tuple]:
    if len(labels) == 1:  # most series, a span's digests among them: nothing to sort
        ((k, v),) = labels.items()
        return name, ((k, str(v)),)
    return name, tuple(sorted((k, str(v)) for k, v in labels.items()))


def reset(enabled_override: bool | None = None) -> None:
    """Clear every metric, event, and kernel trace; re-resolve the enable
    gate from the env (or force it). Tests and operator resets only — a
    serving process keeps its registry for the life of the process."""
    global _ENABLED, _EVENT_SEQ, _EVENTS, _FLIGHT, _FLIGHT_RESOLVED
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _DIGESTS.clear()
        _KTRACES.clear()
        _EVENT_SEQ = 0
        _EVENTS = None
        # Override assignment stays under the lock: a concurrent enabled()
        # between "None" and the override would re-resolve from the env and
        # clobber a forced-off test gate.
        _ENABLED = None if enabled_override is None else bool(enabled_override)
        fr = _FLIGHT
        _FLIGHT = None
        _FLIGHT_RESOLVED = False  # re-resolve TDT_FLIGHT_RECORDER next use
    if fr is not None:
        fr.close()


# ------------------------------------------------------------ quantile digests

#: Relative-accuracy bound of every :class:`Digest` in the registry. A
#: quantile estimate ``est`` for true value ``x`` satisfies
#: ``|est - x| <= DIGEST_ALPHA * x`` — the documented SLO-engine error bound
#: (pinned by ``tests/test_telemetry.py`` against a sorted-list oracle).
DIGEST_ALPHA = 0.01

#: Convenience quantiles exporters attach to every digest entry.
DIGEST_QUANTILES: tuple[float, ...] = (0.5, 0.9, 0.99, 0.999)
_QUANTILE_NAMES = {0.5: "p50", 0.9: "p90", 0.99: "p99", 0.999: "p999"}


class Digest:
    """Mergeable bounded-relative-error quantile sketch (DDSketch-style).

    A strict upgrade of the fixed log2 histograms for latency SLOs: values
    land in sparse log-γ buckets (``γ = (1+α)/(1-α)``, bucket ``i`` covers
    ``(γ^(i-1), γ^i]``), so any quantile is answerable to relative error α
    instead of "somewhere inside a 2× bucket". Buckets are keyed by integer
    index, which makes :meth:`merge` a plain per-key count sum — two
    digests built on the same α merge into *exactly* the digest a single
    observer of the union stream would hold (merge invariance), so
    per-replica digests federate into fleet-wide p50/p99/p999 that equal
    the single-digest answer. Values ``<= 0`` go to a dedicated zero
    bucket (latencies only hit it via clock skew clamps).

    Not thread-safe on its own: the module registry serializes access
    under ``_LOCK``; standalone users (bench.py's percentile helper) are
    single-threaded."""

    __slots__ = ("alpha", "gamma", "_ln_gamma", "buckets", "zero",
                 "sum", "n", "min", "max")

    def __init__(self, alpha: float = DIGEST_ALPHA):
        self.alpha = float(alpha)
        self.gamma = (1.0 + self.alpha) / (1.0 - self.alpha)
        self._ln_gamma = math.log(self.gamma)
        self.buckets: dict[int, int] = {}
        self.zero = 0
        self.sum = 0.0
        self.n = 0
        self.min = math.inf
        self.max = -math.inf

    def add(self, value: float, count: int = 1) -> None:
        v = float(value)
        self.sum += v * count
        self.n += count
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if v <= 0.0:
            self.zero += count
        else:
            i = math.ceil(math.log(v) / self._ln_gamma)
            self.buckets[i] = self.buckets.get(i, 0) + count

    def merge(self, other: "Digest") -> "Digest":
        """Fold ``other`` into this digest (same α required); returns self.
        Commutative and associative: bucket counts are plain sums."""
        if abs(other.gamma - self.gamma) > 1e-12:
            raise ValueError(
                f"cannot merge digests with different accuracy: "
                f"alpha {self.alpha} vs {other.alpha}"
            )
        for i, c in other.buckets.items():
            self.buckets[i] = self.buckets.get(i, 0) + c
        self.zero += other.zero
        self.sum += other.sum
        self.n += other.n
        if other.min < self.min:
            self.min = other.min
        if other.max > self.max:
            self.max = other.max
        return self

    def quantile(self, q: float) -> float | None:
        """Value at quantile ``q`` (rank ``int(q * (n-1))`` of the sorted
        stream, the same convention as a sorted-list oracle), within
        relative error α. None when empty."""
        if self.n <= 0:
            return None
        q = min(max(float(q), 0.0), 1.0)
        rank = int(q * (self.n - 1))
        if rank < self.zero:
            est = min(self.min, 0.0)
        else:
            cum = self.zero
            est = self.max
            for i in sorted(self.buckets):
                cum += self.buckets[i]
                if cum > rank:
                    # Geometric bucket midpoint: ≤ α relative error for any
                    # value inside (γ^(i-1), γ^i].
                    est = 2.0 * self.gamma**i / (self.gamma + 1.0)
                    break
        # Clamping to the observed range only tightens the estimate (the
        # true value lies inside it) and pins p0/p100 exactly.
        return min(max(est, self.min), self.max)

    def to_dict(self) -> dict:
        """JSON-safe serialization; ``from_dict`` round-trips it exactly,
        which is what lets digests ride the ``/fleet/metrics`` wire."""
        return {
            "alpha": self.alpha,
            "n": self.n,
            "sum": self.sum,
            "zero": self.zero,
            "min": self.min if self.n else None,
            "max": self.max if self.n else None,
            "buckets": {str(i): c for i, c in sorted(self.buckets.items())},
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Digest":
        dg = cls(alpha=float(d.get("alpha", DIGEST_ALPHA)))
        dg.n = int(d.get("n", 0))
        dg.sum = float(d.get("sum", 0.0))
        dg.zero = int(d.get("zero", 0))
        mn, mx = d.get("min"), d.get("max")
        dg.min = math.inf if mn is None else float(mn)
        dg.max = -math.inf if mx is None else float(mx)
        for i, c in (d.get("buckets") or {}).items():
            dg.buckets[int(i)] = int(c)
        return dg


def digest_entry(labels: Mapping[str, str], d: Digest) -> dict:
    """One exporter-facing digest entry: serialized state + convenience
    quantiles. Shared by :func:`snapshot` and the fleet federation merge so
    a merged entry is indistinguishable from a locally-built one."""
    return {
        "labels": dict(labels),
        "count": d.n,
        "quantiles": {
            _QUANTILE_NAMES[q]: d.quantile(q) for q in DIGEST_QUANTILES
        },
        **d.to_dict(),
    }


def merge_digest_entries(entries: Iterable[Mapping[str, Any]]) -> dict | None:
    """Merge serialized digest entries (one label set, e.g. the same metric
    scraped from every replica) into one entry. None when empty."""
    merged: Digest | None = None
    labels: dict = {}
    for e in entries:
        d = Digest.from_dict(e)
        if merged is None:
            merged, labels = d, dict(e.get("labels") or {})
        else:
            merged.merge(d)
    return None if merged is None else digest_entry(labels, merged)


# ---------------------------------------------------------------- instruments


def inc(name: str, value: float = 1.0, /, **labels) -> None:
    """Add ``value`` to the counter ``name`` with the given labels."""
    if not enabled():
        return
    k = _key(name, labels)
    with _LOCK:
        _COUNTERS[k] = _COUNTERS.get(k, 0.0) + value


def set_gauge(name: str, value: float, /, **labels) -> None:
    """Set the gauge ``name`` to ``value`` (last write wins)."""
    if not enabled():
        return
    k = _key(name, labels)
    with _LOCK:
        _GAUGES[k] = float(value)


def observe(name: str, value: float, /, **labels) -> None:
    """Record ``value`` into the histogram ``name`` (log2 buckets)."""
    if not enabled():
        return
    k = _key(name, labels)
    with _LOCK:
        h = _HISTS.get(k)
        if h is None:
            h = _HISTS[k] = [[0] * (len(DEFAULT_BUCKETS) + 1), 0.0, 0]
        counts, _, _ = h
        for i, bound in enumerate(DEFAULT_BUCKETS):
            if value <= bound:
                counts[i] += 1
                break
        else:
            counts[-1] += 1  # +Inf bucket
        h[1] += float(value)
        h[2] += 1


def observe_digest(name: str, value: float, /, **labels) -> None:
    """Record ``value`` into the quantile digest ``name`` (log-γ buckets,
    relative error ``DIGEST_ALPHA``). The digest sibling of :func:`observe`
    — use it wherever a tail quantile (p99/p999) must be answerable live."""
    if not enabled():
        return
    k = _key(name, labels)
    with _LOCK:
        d = _DIGESTS.get(k)
        if d is None:
            d = _DIGESTS[k] = Digest()
        d.add(value)


def digest_quantile(name: str, q: float, /, **labels) -> float | None:
    """Quantile ``q`` of one labeled digest (None when never observed)."""
    with _LOCK:
        d = _DIGESTS.get(_key(name, labels))
        return None if d is None else d.quantile(q)


def digest_merged(name: str) -> Digest | None:
    """One digest merging ALL label sets of ``name`` — the
    across-tenants / across-phases view (None when never observed)."""
    merged: Digest | None = None
    with _LOCK:
        for (n, _), d in _DIGESTS.items():
            if n != name:
                continue
            if merged is None:
                merged = Digest(alpha=d.alpha)
            merged.merge(d)
    return merged


def emit(kind: str, /, **fields) -> None:
    """Append one structured event to the bounded ring (and mirror it into
    the flight recorder when one is active — the crash-surviving copy)."""
    if not enabled():
        return
    global _EVENT_SEQ
    ev = {
        k: (v if isinstance(v, (str, int, float, bool, type(None))) else str(v))
        for k, v in fields.items()
    }
    with _LOCK:
        _EVENT_SEQ += 1
        ev["seq"] = _EVENT_SEQ
        ev["kind"] = kind
        _ring().append(ev)
    fr = flight_recorder()
    if fr is not None:
        fr.append(ev)


def events(kind: str | None = None) -> list[dict]:
    """Events currently in the ring, oldest first (optionally one kind)."""
    with _LOCK:
        evs = list(_EVENTS or ())
    return [e for e in evs if kind is None or e["kind"] == kind]


def counter_value(name: str, /, **labels) -> float:
    """Current value of one labeled counter (0.0 when never incremented)."""
    with _LOCK:
        return _COUNTERS.get(_key(name, labels), 0.0)


def counter_total(name: str) -> float:
    """Sum of a counter across ALL label sets — the ``/healthz`` view of
    e.g. ``tdt_resilience_watchdog_timeout_total`` regardless of which
    feature/peer labels it accrued under."""
    with _LOCK:
        return sum(v for (n, _), v in _COUNTERS.items() if n == name)


def gauge_value(name: str, /, **labels) -> float | None:
    """Current value of one labeled gauge (None when never set)."""
    with _LOCK:
        return _GAUGES.get(_key(name, labels))


# ------------------------------------------------------------ flight recorder

#: On-disk format identity: bump on any layout change (self-describing —
#: the reader trusts the header, not this module's constants).
FLIGHT_MAGIC = b"TDTFLT1\n"
FLIGHT_HEADER_BYTES = 64
FLIGHT_RECORD_BYTES = 256
#: File name inside a ``TDT_FLIGHT_RECORDER`` directory — fixed so a parent
#: that knows a child's working dir (the fleet router, which already knows
#: the journal path) can harvest the ring after a kill -9.
FLIGHT_FILE = "flight.bin"
_FLIGHT_REC_HDR = struct.Struct("<QdH")  # seq, monotonic seconds, payload len


class FlightRecorder:
    """Crash-surviving bounded event ring: fixed-size records in an
    mmap-backed file.

    Layout (little-endian)::

        header (64 B): magic(8) | record_bytes u32 | capacity u32 | pid u32
                       | pad(4) | seq u64 at offset 24 (last written)
        records:       capacity × record_bytes, each
                       seq u64 | t_mono f64 | len u16 | JSON payload

    Record ``seq`` is 1-based and monotonically increasing; a record lands
    in slot ``(seq - 1) % capacity``, so the file is a ring that always
    holds the newest ``capacity`` events. Appends memcpy into the mapping
    and return — no fsync, no msync: the kernel owns the dirty pages from
    that point, so a SIGKILL (the whole reason this exists — the
    ``TDT_TELEMETRY_DUMP`` atexit hook never runs under SIGKILL) loses at
    most the single record being written at death. :meth:`read` drops such
    a torn record via the seq/JSON checks. Oversized payloads are replaced
    with a ``{"truncated": true}`` stub rather than torn JSON."""

    def __init__(self, path: str | os.PathLike,
                 capacity: int | None = None,
                 record_bytes: int = FLIGHT_RECORD_BYTES):
        self.path = os.fspath(path)
        self.capacity = max(
            get_int_env("TDT_FLIGHT_RECORDS", 1024)
            if capacity is None else int(capacity), 1
        )
        self.record_bytes = max(int(record_bytes), _FLIGHT_REC_HDR.size + 32)
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        size = FLIGHT_HEADER_BYTES + self.capacity * self.record_bytes
        fd = os.open(self.path, os.O_RDWR | os.O_CREAT, 0o644)
        try:
            os.ftruncate(fd, size)
            self._mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        self._lock = threading.Lock()
        self._seq = 0
        self._closed = False
        struct.pack_into(
            "<8sIII", self._mm, 0,
            FLIGHT_MAGIC, self.record_bytes, self.capacity, os.getpid(),
        )
        struct.pack_into("<Q", self._mm, 24, 0)

    def append(self, fields: Mapping[str, Any]) -> None:
        """Write one record (a JSON-safe dict; ``kind`` conventionally
        present). Hot path: one json.dumps + two pack_into, no syscalls."""
        payload = json.dumps(
            dict(fields), separators=(",", ":"), default=str
        ).encode()
        cap = self.record_bytes - _FLIGHT_REC_HDR.size
        if len(payload) > cap:
            payload = json.dumps(
                {"kind": fields.get("kind", "?"), "truncated": True},
                separators=(",", ":"),
            ).encode()[:cap]
        with self._lock:
            if self._closed:
                return
            self._seq += 1
            off = (FLIGHT_HEADER_BYTES
                   + ((self._seq - 1) % self.capacity) * self.record_bytes)
            _FLIGHT_REC_HDR.pack_into(
                self._mm, off, self._seq, time.monotonic(), len(payload)
            )
            self._mm[off + _FLIGHT_REC_HDR.size:
                     off + _FLIGHT_REC_HDR.size + len(payload)] = payload
            struct.pack_into("<Q", self._mm, 24, self._seq)
        inc("tdt_flight_records_total")

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._mm.flush()
            self._mm.close()

    @staticmethod
    def read(path: str | os.PathLike) -> list[dict]:
        """Decode a flight file (typically another — possibly dead —
        process's), oldest record first. Self-describing: geometry comes
        from the file header. Torn or corrupt records (the one being
        written at death, or slots never yet written) are silently
        dropped — a postmortem reader must never crash on the crash."""
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            return []
        if len(data) < FLIGHT_HEADER_BYTES or data[:8] != FLIGHT_MAGIC:
            return []
        record_bytes, capacity, pid = struct.unpack_from("<III", data, 8)
        if record_bytes <= _FLIGHT_REC_HDR.size or capacity < 1:
            return []
        out: list[dict] = []
        for slot in range(capacity):
            off = FLIGHT_HEADER_BYTES + slot * record_bytes
            if off + record_bytes > len(data):
                break
            seq, t_mono, ln = _FLIGHT_REC_HDR.unpack_from(data, off)
            if seq == 0 or ln == 0 or ln > record_bytes - _FLIGHT_REC_HDR.size:
                continue
            start = off + _FLIGHT_REC_HDR.size
            try:
                obj = json.loads(data[start:start + ln].decode())
            except (UnicodeDecodeError, json.JSONDecodeError):
                continue
            if not isinstance(obj, dict):
                continue
            obj["flight_seq"] = seq
            obj["t_mono_s"] = t_mono
            obj["pid"] = pid
            out.append(obj)
        out.sort(key=lambda r: r["flight_seq"])
        return out


_FLIGHT: FlightRecorder | None = None
_FLIGHT_RESOLVED = False


def flight_recorder() -> FlightRecorder | None:
    """This process's flight recorder, opened lazily from
    ``TDT_FLIGHT_RECORDER=<dir>`` (file ``<dir>/flight.bin``). None when
    the knob is unset or the open failed — recording is strictly optional
    and must never take down the instrumented process."""
    global _FLIGHT, _FLIGHT_RESOLVED
    if not _FLIGHT_RESOLVED:
        with _LOCK:
            if not _FLIGHT_RESOLVED:  # double-checked: one ring per process
                d = os.environ.get("TDT_FLIGHT_RECORDER", "").strip()
                if d:
                    try:
                        _FLIGHT = FlightRecorder(os.path.join(d, FLIGHT_FILE))
                    except OSError:
                        _FLIGHT = None
                _FLIGHT_RESOLVED = True
    return _FLIGHT


def flight_active() -> bool:
    """One cheap check for high-frequency callers (the span tracer)."""
    return enabled() and flight_recorder() is not None


def flight(kind: str, /, **fields) -> None:
    """Append one record to the flight recorder ONLY — no event-ring entry.
    For breadcrumbs too chatty for the in-memory ring (span open/close)
    whose whole value is surviving a crash."""
    if not enabled():
        return
    fr = flight_recorder()
    if fr is None:
        return
    ev = {
        k: (v if isinstance(v, (str, int, float, bool, type(None))) else str(v))
        for k, v in fields.items()
    }
    ev["kind"] = kind
    fr.append(ev)


def flight_postmortem(records: list[dict]) -> dict:
    """Fold recovered flight records into a death report: what was this
    process doing when it died. ``open_spans`` are spans started but never
    ended within the ring — at-death activity, with their ``req_id`` /
    ``slot`` attrs surfaced. Approximate by construction: a span whose
    start wrapped out of the ring cannot be matched, and the final record
    may have been torn — the report is evidence, not a transcript."""
    open_spans: dict[int, dict] = {}
    for r in records:
        kind = r.get("kind")
        if kind == "span_start" and "span_id" in r:
            open_spans[r["span_id"]] = r
        elif kind == "span_end":
            open_spans.pop(r.get("span_id"), None)
    active = sorted(open_spans.values(), key=lambda r: r.get("flight_seq", 0))
    return {
        "n_records": len(records),
        "last": records[-1] if records else None,
        "tail": records[-8:],
        "open_spans": active,
        "active_requests": sorted(
            {r["req_id"] for r in active if "req_id" in r}
        ),
        "active_slots": sorted({r["slot"] for r in active if "slot" in r}),
        "active_span_names": sorted(
            {r["name"] for r in active if "name" in r}
        ),
    }


# ------------------------------------------------------ kernel-trace collector


def maybe_kernel_trace(capacity: int = 256):
    """A fresh ``KernelTrace`` when ``TDT_KERNEL_TRACE=1``, else None — the
    one-line opt-in adopted kernel entry points call at trace time."""
    if not kernel_trace_enabled():
        return None
    from triton_dist_tpu.tools.profiler import KernelTrace

    return KernelTrace(capacity=capacity)


def consume_kernel_trace(kt, events_arr, *, kernel: str) -> None:
    """Attach a host callback that decodes one rank's event buffer into the
    bounded trace ring. Runs per device under shard_map via
    ``jax.debug.callback`` (the ``resilience.consume_status`` pattern: the
    debug effect keeps the otherwise-unused SMEM output alive)."""
    import jax
    import numpy as np

    # Correlation id captured NOW — at jit-trace time, which under serving
    # happens inside the request span that forced this compile. The span
    # tracer merges correlated records onto that trace's chrome row.
    from triton_dist_tpu.runtime import tracing

    corr = tracing.current_correlation()

    def _cb(ev):
        e = np.asarray(ev)
        rec = {"kernel": kernel, "rank": int(e[0, 1]), "corr": corr, **kt.decode(e)}
        with _LOCK:
            _KTRACES.append(rec)

    jax.debug.callback(_cb, events_arr)


def kernel_traces(kernel: str | None = None) -> list[dict]:
    """Decoded per-rank kernel traces collected so far, oldest first:
    ``{"kernel", "rank", "events": [...], "n_dropped"}`` dicts, ready for
    ``tools.profiler.decode_to_chrome``."""
    with _LOCK:
        recs = list(_KTRACES)
    return [r for r in recs if kernel is None or r["kernel"] == kernel]


# ------------------------------------------------------------------- exporters


def _metric_entries(table: dict) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for (name, labels), value in sorted(table.items()):
        out.setdefault(name, []).append({"labels": dict(labels), "value": value})
    return out


def snapshot() -> dict:
    """One JSON-safe dict of everything: metrics, events, kernel traces."""
    with _LOCK:
        counters = dict(_COUNTERS)
        gauges = dict(_GAUGES)
        hists = {k: [list(v[0]), v[1], v[2]] for k, v in _HISTS.items()}
        digest_out: dict[str, list[dict]] = {}
        for (name, labels), d in sorted(_DIGESTS.items()):
            digest_out.setdefault(name, []).append(digest_entry(dict(labels), d))
        evs = list(_EVENTS or ())
        traces = list(_KTRACES)
    hist_out: dict[str, list[dict]] = {}
    for (name, labels), (counts, total, n) in sorted(hists.items()):
        cum = 0
        buckets = []
        for bound, c in zip(DEFAULT_BUCKETS, counts):
            cum += c
            buckets.append([bound, cum])
        buckets.append(["+Inf", cum + counts[-1]])
        hist_out.setdefault(name, []).append(
            {"labels": dict(labels), "count": n, "sum": total, "buckets": buckets}
        )
    return {
        "enabled": enabled(),
        "counters": _metric_entries(counters),
        "gauges": _metric_entries(gauges),
        "histograms": hist_out,
        "digests": digest_out,
        "events": evs,
        "kernel_traces": traces,
    }


def dump(path: str) -> str:
    """Write :func:`snapshot` as JSON (plus the span-trace section when any
    spans were recorded — one file tells the whole story); returns the path."""
    snap = snapshot()
    from triton_dist_tpu.runtime import tracing  # circular-at-import otherwise

    traces = tracing.snapshot_traces()
    if traces["n_spans"] or traces["n_open"]:
        snap["traces"] = traces
    with open(path, "w") as f:
        json.dump(snap, f, indent=1)
    return path


def _fmt_labels(labels: Mapping[str, str], extra: Iterable[tuple[str, str]] = ()) -> str:
    items = [*labels.items(), *extra]
    if not items:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in items)
    return "{" + body + "}"


def to_prometheus(snap: dict | None = None) -> str:
    """Prometheus text exposition of a snapshot (default: the live one).

    Accepting a snapshot dict lets ``scripts/tdt_metrics.py`` render a file
    another process dumped — there is no in-process scrape endpoint."""
    snap = snapshot() if snap is None else snap
    lines: list[str] = []
    for name, entries in snap.get("counters", {}).items():
        lines.append(f"# TYPE {name} counter")
        for e in entries:
            lines.append(f"{name}{_fmt_labels(e['labels'])} {e['value']:g}")
    for name, entries in snap.get("gauges", {}).items():
        lines.append(f"# TYPE {name} gauge")
        for e in entries:
            lines.append(f"{name}{_fmt_labels(e['labels'])} {e['value']:g}")
    for name, entries in snap.get("histograms", {}).items():
        lines.append(f"# TYPE {name} histogram")
        for e in entries:
            for bound, cum in e["buckets"]:
                le = bound if isinstance(bound, str) else f"{bound:g}"
                lines.append(
                    f"{name}_bucket{_fmt_labels(e['labels'], [('le', le)])} {cum}"
                )
            lines.append(f"{name}_sum{_fmt_labels(e['labels'])} {e['sum']:g}")
            lines.append(f"{name}_count{_fmt_labels(e['labels'])} {e['count']}")
    # Digests render as Prometheus summaries: one pre-computed quantile
    # series per entry plus _sum/_count, mirroring the histogram layout.
    for name, entries in snap.get("digests", {}).items():
        lines.append(f"# TYPE {name} summary")
        for e in entries:
            for q, qname in sorted(_QUANTILE_NAMES.items()):
                v = (e.get("quantiles") or {}).get(qname)
                if v is None:
                    continue
                lines.append(
                    f"{name}{_fmt_labels(e['labels'], [('quantile', f'{q:g}')])}"
                    f" {v:g}"
                )
            lines.append(f"{name}_sum{_fmt_labels(e['labels'])} {e['sum']:g}")
            lines.append(f"{name}_count{_fmt_labels(e['labels'])} {e['count']}")
    return "\n".join(lines) + "\n"


def summary() -> dict:
    """Compact per-section digest for bench emission: flattened counters,
    histogram count/sum/mean, event + kernel-trace tallies. Small enough to
    ride along every BENCH line without bloating it."""
    with _LOCK:
        counters = dict(_COUNTERS)
        hists = {k: (v[1], v[2]) for k, v in _HISTS.items()}
        digest_stats = {
            k: (d.n, d.quantile(0.5), d.quantile(0.99))
            for k, d in _DIGESTS.items()
        }
        n_events = len(_EVENTS or ())
        n_traces = len(_KTRACES)

    def flat(name: str, labels: tuple) -> str:
        return name + _fmt_labels(dict(labels))

    hist_summary = {}
    for (name, labels), (total, n) in sorted(hists.items()):
        hist_summary[flat(name, labels)] = {
            "count": n,
            "sum_s": round(total, 6),
            "mean_s": round(total / n, 6) if n else 0.0,
        }
    digest_summary = {}
    for (name, labels), (n, p50, p99) in sorted(digest_stats.items()):
        digest_summary[flat(name, labels)] = {
            "count": n,
            "p50": round(p50, 6) if p50 is not None else None,
            "p99": round(p99, 6) if p99 is not None else None,
        }
    return {
        "enabled": enabled(),
        "counters": {flat(n, l): v for (n, l), v in sorted(counters.items())},
        "histograms": hist_summary,
        "digests": digest_summary,
        "events": n_events,
        "kernel_traces": n_traces,
    }


# ------------------------------------------------------------- exit-time dump

import atexit as _atexit  # noqa: E402
import os as _os  # noqa: E402


def _dump_at_exit() -> None:  # pragma: no cover - exercised via CLI docs
    path = _os.environ.get("TDT_TELEMETRY_DUMP")
    if path and enabled():
        try:
            dump(path)
        except Exception:
            pass  # exit-path telemetry must never mask the real exit status


_atexit.register(_dump_at_exit)
