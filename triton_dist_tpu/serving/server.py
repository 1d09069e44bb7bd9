"""Streaming inference server: the host loop of continuous batching.

``InferenceServer`` drives one :class:`~triton_dist_tpu.models.engine.Engine`
with the step-granular programs it exposes (``prefill_chunk``,
``complete_paged_prefill``, ``decode_steps_paged``) under a
:class:`~triton_dist_tpu.serving.scheduler.Scheduler`, over ONE serving
cache: a global block pool + per-slot block tables
(:class:`~triton_dist_tpu.models.kv_cache.PagedKVCache`):

* **join** — every loop iteration first admits arrived requests (FCFS) into
  free slots and arms their prefill; the prefill then advances one chunk of
  ``TDT_PREFILL_CHUNK`` rows an iteration, and its last chunk scatters the
  prompt's KV into the slot's block chain and streams the first sampled
  token (TTFT is measured to this point);
* **decode chunk** — then runs ``TDT_SERVE_CHUNK`` decode steps over the
  whole slot batch as ONE device dispatch with a per-slot active mask, and
  streams each slot's newly valid tokens to its ``on_token`` callback.
  Chunking is the host/device trade: larger chunks amortize dispatch,
  smaller chunks tighten join latency for requests arriving mid-decode.
  Where nothing changes the slot set (every slot decoding, none finishing
  inside the chunk) the chunk is left **in flight** when :meth:`step`
  returns, and the next one is issued from its device-resident last tokens
  before it is landed (waited for, fetched, streamed): the device never
  waits for the host between the two. See :meth:`_decode_once`.

Everything the device sees is fixed-shape (one compile per chunk size, one
prefill compile per distinct (chunk, prompt) length pair, one scatter
program per prompt length; block tables are data), so a slot batch whose
composition changes every chunk never recompiles — the jit analog of the
reference engine's per-token CUDA-graph replay, lifted to iteration-level
scheduling.

**Degraded-mode recovery without dropping the queue**: a bounded-wait abort
(``CollectiveAbortError`` via ``resilience.consume_status``) or a
``CollectiveWatchdog`` timeout surfacing from a join or a decode chunk
triggers :meth:`InferenceServer._recover`: the engine rebuilds on the
``xla`` backend (the feature's circuit breaker OPENs, same contract as
``Engine.serve``), a fresh pool is allocated (the aborted dispatch
may have poisoned or consumed the donated buffers), and every in-flight
slot re-prefills from its token history ``prompt + tokens[:-1]`` — the
re-prefill's sampled token is discarded (it was already streamed), so
recovery produces **zero dropped and zero duplicated** stream tokens.
Queued requests are untouched. A fault DURING the re-prefill (the
double-fault scenario) is retried a bounded number of times on a fresh
cache before surfacing.

**Un-degrade via half-open probes**: while the engine runs degraded, every
:meth:`step` first asks ``resilience.probe_due()`` whether a breaker's
backoff has elapsed; if so the preferred backend is rebuilt and probed with
ONE sandboxed join and decode step through the programs that serve (on a
throwaway 1-slot pool, under ``resilience.probe_scope`` so only the probing
thread sees the feature healthy). A successful probe CLOSEs the breaker and
:meth:`_restore_streams` re-resolves routing for live traffic — fresh
cache, re-prefill from history, zero stream disruption (the same machinery
as recovery, pointed back at the fused path). A failed probe re-opens the
breaker with doubled backoff and the server stays on xla; live slots are
untouched either way because the probe never touches the serving cache.

**SLO guardrails** (scheduler-enforced, see ``serving/scheduler.py``):
per-request TTFT/total deadlines with queue-time expiry, EWMA-projected
overload shedding before admission, and :meth:`cancel` — the server's half
is :meth:`_reap_slots`, which frees cancelled and total-deadline-expired
slots at each chunk boundary with distinct finish reasons.

**Crash recovery via the write-ahead journal** (``serving/journal.py``):
with a journal attached (``journal=`` or ``TDT_JOURNAL_DIR``) the server
journals every request lifecycle transition; after a process crash a fresh
server pointed at the same journal calls :meth:`recover` — queued requests
are re-admitted, in-flight requests re-prefill from ``prompt + journaled
tokens`` (the recovery branch of :meth:`_complete_prefill`), and completed
requests are skipped idempotently. **Rank death** (heartbeat lease expiry
on the ``mesh.HealthBoard``, or a scripted chaos ``die@<rank>``) is
discovered by the per-step health sweep or by the trace-time ``dead_peer``
fail-fast; either way survivors rebuild once on xla at the new mesh epoch —
no per-collective timeout storm — and resume every stream from history.

**Graceful shutdown**: :meth:`shutdown` (or SIGTERM via
:meth:`install_signal_handlers`, or Ctrl-C inside :meth:`run`) rejects new
joins with reason ``shutting_down``, drains (or journals) running slots,
flushes the journal + dumps telemetry, and stops the introspect endpoint.

**Paged KV with prefix reuse and chunked prefill**: admission is a
block-budget reservation through the scheduler's
:class:`~triton_dist_tpu.serving.scheduler.KVLedger` (prefix-index eviction,
``kv_wait`` parking), prompts sharing a block-aligned prefix reuse the
donor's KV blocks via the radix index, and prefill runs as incremental
chunks interleaved with decode — a long prompt joining mid-decode stalls
the decode stream at most ONE chunk boundary. Prompts no longer than the
chunk knob prefill in one chunk sized exactly to the prompt, which is
bitwise-identical to the one-shot prefill program; see ``docs/serving.md``
for the full parity contract.

Env knobs::

    TDT_SERVE_SLOTS       fixed slot-batch size B (default 4)
    TDT_SERVE_CHUNK       decode steps per device dispatch (default 8)
    TDT_KV_BLOCK_SIZE     KV block size, token rows per block (default 16)
    TDT_KV_BLOCKS         pool size incl. the null block (default: every
                          slot can hold a full max_len chain, + 1)
    TDT_PREFILL_CHUNK     prefill rows per chunk dispatch (default max_len)
    TDT_PREFIX_REUSE      share block-aligned prompt-prefix KV (default 1)
    TDT_SPEC_K            speculative draft width k (default 0 = off; >=2
                          turns on speculative greedy decode — see
                          docs/speculative.md)
    TDT_SPEC_MIN_ACCEPT   adaptive-k backoff threshold on the per-slot
                          acceptance-fraction EWMA (default 0.5)
    TDT_SPEC_DRAFTER      drafter kind: truncated (default) | gdn
    TDT_SPEC_DRAFT_LAYERS target layers the truncated drafter keeps
                          (default: half the stack)
    TDT_DEADLINE_TTFT_S   default TTFT budget, s (<=0/unset = none)
    TDT_DEADLINE_TOTAL_S  default total budget, s (<=0/unset = none)
    TDT_SHED_WAIT_S       global projected-wait shed budget, s (0 = off)
    TDT_SHED_PRIORITY     min priority class eligible for shedding (def. 1)
    TDT_SHED_HEALTH_S     /healthz not-ready window after a shed (def. 5)
    TDT_DEGRADE_PROBE_S   breaker probe backoff base, s (def. 30; <=0 off)
    TDT_JOURNAL_DIR       directory for the write-ahead journal (unset = off)
    TDT_JOURNAL_FSYNC     journal appends between fsyncs (default 8)
    TDT_DRAIN_TIMEOUT_S   shutdown drain budget, s (0 = unbounded)
    TDT_POOL_ROLE         disaggregated pool role: unified (default) |
                          prefill | decode — see docs/disagg.md

Metrics (``tdt_serving_*``, see ``docs/serving.md`` and
``docs/observability.md``): request/completion/reject/preemption/recovery
counters, queue-depth and slot-occupancy gauges, TTFT and per-request TPOT
histograms.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.disagg.kv_transfer import (
    pack_kv_blocks,
    scatter_kv_blocks,
    unpack_kv_blocks,
)
from triton_dist_tpu.disagg.pool import pool_role_from_env, role_id
from triton_dist_tpu.models.quant import kv_quant_from_env
from triton_dist_tpu.runtime import resilience, slo, telemetry, tracing
from triton_dist_tpu.runtime.utils import get_float_env, get_int_env
from triton_dist_tpu.serving.scheduler import (
    KVLedger,
    Request,
    RequestState,
    Scheduler,
    Slot,
    SlotState,
)

#: Bounded retry budget for faults that land DURING a recovery or restore
#: re-prefill (each retry rebuilds on xla over a fresh cache).
REPREFILL_RETRIES = 3


@dataclasses.dataclass
class _Chunk:
    """One decode chunk between its issue and its landing, as the loop
    keeps it."""

    handle: object  # the engine's ``DecodeChunk``: what the landing waits on
    out: object  # (B, chunk) tokens, as the engine's call returned them
    tok: object  # (B,) last tokens and
    rem: object  # (B,) what is left, on the device: the next chunk's operands
    decoding: list  # the slots that decode in it
    pre: dict  # slot idx -> tokens the slot still owed before it
    t_issue: float  # ``time.perf_counter()`` before the issue
    d_start: float  # the same moment on the tracing clock
    dispatch_id: int | None  # the issue's span, for the tenants' traces


class InferenceServer:
    """Continuous-batching server over one engine (host-side loop)."""

    def __init__(self, engine, num_slots: int | None = None,
                 chunk: int | None = None, queue_limit: int = 0,
                 key: jax.Array | None = None, watchdog=None,
                 shed_wait_s: float | None = None,
                 shed_priority: int | None = None,
                 journal=None, spec_k: int | None = None, drafter=None,
                 prefill_chunk: int | None = None,
                 block_size: int | None = None):
        self.engine = engine
        self.num_slots = (
            get_int_env("TDT_SERVE_SLOTS", 4) if num_slots is None else int(num_slots)
        )
        self.chunk = (
            get_int_env("TDT_SERVE_CHUNK", 8) if chunk is None else int(chunk)
        )
        assert self.num_slots >= 1 and self.chunk >= 1
        #: The backend the operator asked for — the restore target whenever
        #: a breaker closes while the engine is running degraded. Read off
        #: the engine's own construction-time record, NOT engine.backend:
        #: an engine that already degraded (or was probed) before the
        #: server wrapped it would otherwise bake the fallback in as the
        #: "preferred" target and the probe could never restore mega.
        self._preferred_backend = getattr(
            engine, "preferred_backend", engine.backend
        )
        #: Positions a page of the pool: the argument (a model whose
        #: selection reads whole blocks states its block), else
        #: TDT_KV_BLOCK_SIZE, else 16.
        self.block_size = (
            get_int_env("TDT_KV_BLOCK_SIZE", 16) if block_size is None else int(block_size)
        )
        assert self.block_size >= 1
        max_blocks = -(-engine.max_len // self.block_size)
        # Default pool: every slot can hold a FULL max_len chain at once
        # (+1 for the reserved null block) — zero eviction pressure. Size
        # it down (TDT_KV_BLOCKS) to trade capacity for memory; prefix
        # sharing and kv_wait parking absorb the overcommit.
        self.num_blocks = get_int_env(
            "TDT_KV_BLOCKS", self.num_slots * max_blocks + 1
        )
        #: Prefill rows a chunk dispatch: the argument, else
        #: TDT_PREFILL_CHUNK, else the whole prompt in one.
        self.prefill_chunk = (
            get_int_env("TDT_PREFILL_CHUNK", engine.max_len)
            if prefill_chunk is None else int(prefill_chunk)
        )
        assert self.prefill_chunk >= 1
        #: Quantized KV storage (TDT_QUANT_KV=int8|fp8): the pool holds
        #: wire-dtype blocks + per-row scale pools; greedy streams stay
        #: byte-identical across prefix sharing/CoW (quantize-once).
        self.kv_quant = kv_quant_from_env()
        #: A model that keeps per-slot state beside the pool (recurrent
        #: state, a window's ring: ``Engine.stateful``). The pool's blocks
        #: do not hold it, so what rests on the pool alone is refused, not
        #: served wrong: no prefix hit (skipped and counted), no
        #: speculation (a rejected draft cannot be rewound), no KV handoff
        #: (``export_kv`` / ``import_kv``); a preempted or recovered request
        #: re-prefills from its token history, which rebuilds the state.
        self.stateful = engine.stateful
        want_prefix = get_int_env("TDT_PREFIX_REUSE", 1) != 0
        #: The trie lookup an operator asked for and the model's state
        #: forbids: skipped at every join, and counted there.
        self._prefix_skipped = want_prefix and self.stateful
        self.kv_ledger = KVLedger(
            self.num_blocks, self.block_size,
            prefix_reuse=want_prefix and not self.stateful,
        )
        #: Disaggregated-pool role (``TDT_POOL_ROLE``, docs/disagg.md): a
        #: "prefill" replica parks finished prefills for handoff instead of
        #: decoding them; a "decode" replica receives parked KV over the
        #: wire; "unified" (the default) serves both phases.
        self.role = pool_role_from_env()
        telemetry.set_gauge("tdt_disagg_pool_role", float(role_id(self.role)))
        #: Parked handoffs awaiting export: req_id -> {"blocks", "length",
        #: "tokens", "tenant"}. Each parked chain holds one extra allocator
        #: ref per block, taken before the slot's release, so the prefilled
        #: content survives until :meth:`release_handoff` (or process death
        #: — the router then re-derives KV from the journaled history).
        self._handoffs: dict[int, dict] = {}
        self.scheduler = Scheduler(
            self.num_slots, engine.max_len, queue_limit,
            shed_wait_s=shed_wait_s, shed_priority=shed_priority,
            kv_ledger=self.kv_ledger,
        )
        #: Speculative decoding (TDT_SPEC_K >= 2 turns it on; 0/1 = off).
        #: Greedy-only: the verify program replays the target's own decode
        #: step per draft position, so acceptance == argmax agreement and
        #: the stream is byte-identical to non-speculative greedy decode.
        self.spec_k = (
            get_int_env("TDT_SPEC_K", 0) if spec_k is None else int(spec_k)
        )
        if self.stateful and (self.spec_k >= 2 or drafter is not None):
            raise ValueError(
                "speculative decoding rewinds a rejected draft by its length "
                "alone; this model's per-slot state cannot be rewound"
            )
        self.spec_min_accept = get_float_env("TDT_SPEC_MIN_ACCEPT", 0.5)
        self._drafter = drafter
        self._dstate = None
        self._kcap = np.zeros((self.num_slots,), np.int32)
        self._accept_ewma = np.ones((self.num_slots,), np.float64)
        if self.spec_k >= 2 and engine.sample_method != "greedy":
            telemetry.emit(
                "serving_spec_disabled", why="non-greedy sampling",
                sample_method=engine.sample_method,
            )
            self.spec_k = 0
        if self.spec_k >= 2:
            if self._drafter is None:
                self._drafter = self._build_drafter()
            self.engine.attach_drafter(self._drafter)
            self._dstate = self._drafter.init_state(self.num_slots)
            self._kcap[:] = self.spec_k
            telemetry.set_gauge("tdt_spec_k", float(self.spec_k))
        #: In-flight chunked prefills: slot idx -> cursor state (ids, row
        #: offset, context buffers, sampling key). One chunk per slot per
        #: step keeps decode within one chunk boundary of a long prompt.
        self._prefilling: dict[int, dict] = {}
        #: Host mirror of per-slot KV lengths (the device ``lengths``
        #: travel as data the host re-pushes with the tables).
        self._lengths = np.zeros((self.num_slots,), np.int32)
        # Process-level trace owning the spans no single request owns (the
        # loop's iterations and phases, shared decode dispatches, recovery).
        # Left open for the server's lifetime — introspection shows it as
        # in-flight. Opened before the first table push, which is a span.
        self._trace = tracing.start_trace(
            "tdt_serving_server", slots=self.num_slots, chunk=self.chunk,
            backend=getattr(engine, "backend", None),
        )
        self.cache = self._fresh_cache()
        # Host-authoritative per-slot decode state (tiny, synced per chunk).
        # ``_remaining`` and ``_lengths`` advance when a chunk is ISSUED, by
        # the counts the host knows beforehand; ``_last`` is set when it lands.
        self._last = np.zeros((self.num_slots,), np.int32)
        self._remaining = np.zeros((self.num_slots,), np.int32)
        self._key = jax.random.PRNGKey(0) if key is None else key
        #: The decode chunk issued and not yet landed (at most one when
        #: :meth:`step` returns), see :meth:`_decode_once`.
        self._in_flight: _Chunk | None = None
        #: ``time.perf_counter()`` of the last landing: a chunk's wall is
        #: the time between two landings.
        self._landed_at = 0.0
        # The watchdog bounds a chunk's LANDING, which is where a hung
        # device shows. retries=0: the chunk donated the pool, so a
        # timed-out one is never dispatched again — recovery reallocates.
        self._watchdog = watchdog if watchdog is not None else (
            resilience.CollectiveWatchdog(
                feature="collectives", name="serving.decode", retries=0
            )
        )
        self._t0 = time.monotonic()
        # Live introspection endpoint (no-op unless TDT_HTTP_PORT is set).
        # The health provider makes /healthz reflect shed pressure and the
        # degraded/preferred backend split regardless of who started the
        # endpoint.
        # Write-ahead journal: explicit handle/path wins, else TDT_JOURNAL_DIR
        # opts in. No journal = the pre-crash-recovery behavior, zero cost.
        if journal is None:
            jdir = os.environ.get("TDT_JOURNAL_DIR", "").strip()
            if jdir:
                journal = os.path.join(jdir, "journal.jsonl")
        if isinstance(journal, (str, os.PathLike)):
            from triton_dist_tpu.serving.journal import RequestJournal

            journal = RequestJournal(journal)
        self._journal = journal
        #: req_ids already replayed by :meth:`recover` (idempotence guard).
        self._recovered_ids: set[int] = set()
        self._shutdown = False
        #: Set by the SIGTERM handler; :meth:`run` converts it into a drain.
        self._shutdown_requested = False
        #: Drain mode (fleet rolling rebuild): new submits bounce, admitted
        #: work keeps running, the process stays up. See :meth:`drain_begin`.
        self._draining = False
        from triton_dist_tpu.runtime import introspect

        self._introspect = introspect.maybe_start()
        introspect.set_health_provider(self._health_info)
        introspect.set_requests_provider(self._requests_info)
        # Live SLO view: per-tenant goodput/violations + latency quantiles
        # and the engine's step-phase digests (see runtime/slo.py).
        introspect.register_json_route("/slo", self._r_slo, methods=("GET",))

    def _build_drafter(self):
        """Construct the env-selected drafter (``TDT_SPEC_DRAFTER``):
        ``truncated`` (default) runs the first ``TDT_SPEC_DRAFT_LAYERS``
        layers of the target over its own small paged KV; ``gdn`` runs the
        single-layer Gated-DeltaNet linear-attention stub."""
        kind = os.environ.get("TDT_SPEC_DRAFTER", "truncated").strip().lower()
        if kind == "gdn":
            from triton_dist_tpu.models.drafter import GDNDrafter

            return GDNDrafter(self.engine.model)
        from triton_dist_tpu.models.drafter import TruncatedDrafter

        layers = get_int_env("TDT_SPEC_DRAFT_LAYERS", 0)
        return TruncatedDrafter(
            self.engine.model,
            num_layers=layers if layers >= 1 else None,
            max_len=self.engine.max_len,
            block_size=self.block_size,
        )

    def _spec_prefill(self, idx: int, ids) -> None:
        """Re-seed the drafter for ``idx``'s tenant from the same token
        history the target prefilled (fresh join, recovery, restore and
        journal replay all come through here) and reset its adaptive-k
        state. ``ids`` is the prefill history (``prompt + tokens[:-1]``);
        the pending last streamed token is deliberately NOT in the drafter
        KV — the next propose consumes it, exactly like the target."""
        if self.spec_k >= 2:
            self._dstate = self._drafter.prefill_state(self._dstate, idx, ids)
            self._kcap[idx] = self.spec_k
            self._accept_ewma[idx] = 1.0

    def _health_info(self) -> dict:
        shedding = self.scheduler.shedding(self._now())
        return {
            "ready": not (shedding or self._shutdown or self._draining),
            "role": self.role,
            "parked_handoffs": len(self._handoffs),
            "shedding": shedding,
            "draining": self._draining,
            "shutting_down": self._shutdown,
            "backend": self.engine.backend,
            "preferred_backend": self._preferred_backend,
            "queue_depth": self.scheduler.queue_depth(),
            "slot_occupancy": self.scheduler.occupancy(),
            "mesh_epoch": resilience.mesh_epoch(),
        }

    def _requests_info(self) -> dict:
        """The `/requests` introspection payload: queue depth, per-slot
        state-machine position, remaining deadline budgets, journal lag."""
        now = self._now()
        slots = []
        for slot in self.scheduler.slots:
            entry: dict = {"idx": slot.idx, "state": slot.state.value}
            req = slot.request
            if req is not None:
                entry.update(
                    req_id=req.req_id,
                    request_state=req.state.value,
                    prompt_len=len(req.prompt),
                    n_tokens=len(req.tokens),
                    max_new=req.max_new,
                    remaining=int(self._remaining[slot.idx]),
                    deadline_remaining_s=(
                        round(req.deadline_s - (now - req.arrived_at), 3)
                        if req.deadline_s is not None else None
                    ),
                    ttft_deadline_remaining_s=(
                        round(req.ttft_deadline_s - (now - req.arrived_at), 3)
                        if req.ttft_deadline_s is not None
                        and req.first_token_at is None else None
                    ),
                    kv_blocks=len(req.kv_blocks),
                    kv_prefix_shared=req.kv_shared,
                    kv_len=int(self._lengths[slot.idx]),
                    prefilling=slot.idx in self._prefilling,
                )
                if req.prefill_only:
                    entry["prefill_only"] = True
                if self.spec_k >= 2:
                    entry.update(
                        spec_k=int(self._kcap[slot.idx]),
                        spec_accept_ewma=round(
                            float(self._accept_ewma[slot.idx]), 4
                        ),
                    )
            slots.append(entry)
        return {
            "kv": self.kv_ledger.stats(),
            **({"spec": {
                "k": self.spec_k,
                "min_accept": self.spec_min_accept,
                "drafter": self._drafter.name,
                "proposed": telemetry.counter_total("tdt_spec_proposed_total"),
                "accepted": telemetry.counter_total("tdt_spec_accepted_total"),
            }} if self.spec_k >= 2 else {}),
            **({"ep": self._ep_info()} if self._is_ep_model() else {}),
            "mesh_epoch": resilience.mesh_epoch(),
            "backend": self.engine.backend,
            "role": self.role,
            "handoffs": {
                "parked": len(self._handoffs),
                "req_ids": sorted(self._handoffs),
            },
            "shutting_down": self._shutdown,
            "queue_depth": self.scheduler.queue_depth(),
            "queued": self.scheduler.queued_summary(now),
            "slots": slots,
            "journal": (
                self._journal.stats() if self._journal is not None else None
            ),
        }

    def _r_slo(self, method: str, query: str, body) -> tuple[int, dict]:
        """The `/slo` introspection payload: per-(tenant, tier) goodput +
        latency quantiles, and the engine's per-backend step-phase digests
        ("where did this step's milliseconds go", live)."""
        snap = telemetry.snapshot()
        phases: dict[str, dict] = {}
        for e in snap.get("digests", {}).get("tdt_engine_phase_seconds", []):
            backend = e["labels"].get("backend", "?")
            phases.setdefault(backend, {})[e["labels"].get("phase", "?")] = {
                "count": e["count"], **(e.get("quantiles") or {})
            }
        return 200, {
            **slo.slo_summary(snap),
            "phases": phases,
            "backend": self.engine.backend,
            "alpha": telemetry.DIGEST_ALPHA,
        }

    def _is_ep_model(self) -> bool:
        return getattr(self.engine.model, "ep_crossover_tokens", None) is not None

    def _ep_info(self) -> dict:
        """Expert-parallel MoE introspection: which a2a route the AUTO
        resolver took, live per-expert load shares, overflow drops and wire
        bytes — the ``tdt_ep_*`` series reshaped for the `/requests` view
        (Prometheus `/metrics` carries the same series raw)."""
        snap = telemetry.snapshot()
        routes = {
            e["labels"].get("method", "?"): e["value"]
            for e in snap["counters"].get("tdt_ep_auto_route_total", [])
        }
        load = {
            str(e["labels"].get("expert", "?")): round(e["value"], 4)
            for e in snap["gauges"].get("tdt_ep_expert_load", [])
        }
        return {
            "routes": routes,
            "expert_load": load,
            "dropped_tokens": telemetry.counter_total(
                "tdt_ep_dropped_tokens_total"
            ),
            "wire_bytes": telemetry.counter_total("tdt_ep_wire_bytes_total"),
            "crossover_t": self.engine.model.ep_crossover_tokens(),
        }

    # ------------------------------------------------------------------ clock
    def _now(self) -> float:
        """Server-relative clock: request arrival times are offsets on it."""
        return time.monotonic() - self._t0

    # ----------------------------------------------------------------- submit
    def submit(self, prompt, max_new: int, arrival_time_s: float = 0.0,
               on_token=None, on_finish=None, priority: int = 1,
               ttft_deadline_s: float | None = None,
               deadline_s: float | None = None,
               trace_ctx=None, tenant: str = "default",
               weight: float = 1.0, prefill_only: bool = False) -> Request:
        """Admission-check and enqueue one request; returns its handle
        (``state=REJECTED`` + ``reject_reason`` when not admitted). Admitted
        requests are journaled (write-ahead) when a journal is attached —
        including tenant identity and QoS weight, so migration replays
        land in the survivor's per-tenant accounting byte-identically.
        ``trace_ctx`` (an extracted ``tracing.SpanContext``) makes the
        request trace continue a remote caller's trace — the fleet replica
        passes the router's propagated context through here.
        ``prefill_only`` runs prefill + the first token and then parks the
        KV chain for a disaggregated handoff instead of decoding — see
        docs/disagg.md."""
        req = self.scheduler.submit(
            prompt, max_new, arrival_time_s=arrival_time_s,
            on_token=on_token, on_finish=on_finish, now_s=self._now(),
            priority=priority, ttft_deadline_s=ttft_deadline_s,
            deadline_s=deadline_s, trace_ctx=trace_ctx,
            tenant=tenant, weight=weight, prefill_only=prefill_only,
        )
        if self._journal is not None and req.state is RequestState.QUEUED:
            # Rejections are never journaled: there is nothing to resume.
            self._journal.append(
                "submit", req_id=req.req_id, prompt=req.prompt,
                max_new=req.max_new, arrival_time_s=req.arrival_time_s,
                priority=req.priority, tenant=req.tenant,
                weight=req.weight, ttft_deadline_s=req.ttft_deadline_s,
                deadline_s=req.deadline_s,
            )
        return req

    def cancel(self, req_id: int) -> bool:
        """Client cancellation: a queued request finalizes immediately; a
        running one frees its slot at the next chunk boundary."""
        ok = self.scheduler.cancel(int(req_id))
        if ok and self._journal is not None:
            self._journal.append("cancel", req_id=int(req_id))
        return ok

    def resume(self, prompt, max_new: int, tokens, on_token=None,
               on_finish=None, priority: int = 1,
               ttft_deadline_s: float | None = None,
               deadline_s: float | None = None,
               trace_ctx=None, tenant: str = "default",
               weight: float = 1.0) -> Request:
        """Admit a request MID-STREAM: ``tokens`` is the history another
        server already streamed for it (journal-replay migration — the
        fleet router moving an in-flight request off a dead or draining
        replica). Admission runs normally (fresh local req_id, KV budget,
        shedding); on admit the history is pre-seeded, so the join sweep
        re-prefills from ``prompt + tokens`` and decoding continues at
        position ``len(tokens)`` — seeded tokens are NOT re-streamed to the
        callbacks (deterministic greedy regeneration of any suffix the
        donor generated past the seed keeps the stream byte-identical).
        The seed is journaled as a position-0 chunk so THIS server's
        journal is self-contained for the next migration or crash."""
        toks = [int(t) for t in tokens][: int(max_new)]
        req = self.scheduler.submit(
            prompt, max_new, on_token=on_token, on_finish=on_finish,
            now_s=self._now(), priority=priority,
            ttft_deadline_s=ttft_deadline_s, deadline_s=deadline_s,
            tokens=toks, trace_ctx=trace_ctx,
            tenant=tenant, weight=weight,
        )
        if req.state is not RequestState.QUEUED:
            return req
        telemetry.inc("tdt_serving_resumed_total")
        if self._journal is not None:
            self._journal.append(
                "submit", req_id=req.req_id, prompt=req.prompt,
                max_new=req.max_new, arrival_time_s=req.arrival_time_s,
                priority=req.priority, tenant=req.tenant,
                weight=req.weight, ttft_deadline_s=req.ttft_deadline_s,
                deadline_s=req.deadline_s,
            )
            if toks:
                self._journal.append(
                    "chunk", req_id=req.req_id, start=0, tokens=toks
                )
        return req

    # ------------------------------------------------------------ fleet hooks
    def placement_info(self, prompt, tenant: str = "default") -> dict:
        """Placement hint for a fleet router: how warm is this replica for
        ``prompt`` (longest indexed full-block prefix, WITHIN ``tenant``'s
        trie only — affinity can never leak another tenant's cached
        prompts through routing timing) and how loaded is it
        (EWMA-projected wait + backlog). Read-only and thread-safe — the
        prefix probe never touches LRU stamps — so the introspect endpoint
        can serve it off the loop thread."""
        prompt = [int(t) for t in prompt]
        warm = 0
        if self.kv_ledger.prefix_reuse:
            warm = self.kv_ledger.prefix.match_blocks(prompt, tenant)
        est = self.scheduler.est_wait_s()
        return {
            "warm_blocks": warm,
            "block_size": self.block_size,
            "est_wait_s": None if est is None else round(est, 6),
            "backlog_tokens": self.scheduler.backlog_tokens(),
            "queue_depth": self.scheduler.queue_depth(),
            "occupancy": self.scheduler.occupancy(),
            "num_slots": self.num_slots,
            "backend": self.engine.backend,
            "degraded": self.engine.backend != self._preferred_backend,
            "draining": self._draining,
            "shedding": self.scheduler.shedding(self._now()),
            "ready": not (self._draining or self._shutdown),
        }

    def drain_begin(self) -> None:
        """Enter drain mode (rolling rebuild): reject new submits with
        reason ``shutting_down`` while admitted work keeps running and the
        process (journal, endpoint) stays up — :meth:`drained` flips once
        the queue and every slot are empty. Unlike :meth:`shutdown` this is
        NOT terminal: the replica can still export its journal and serve
        its in-flight streams while the router migrates them away. Only
        flags are set here (the fleet replica calls this from its endpoint's
        thread): the loop sees them at its next chunk, which it lands
        before :meth:`step` returns, as every chunk of a draining server."""
        if self._draining:
            return
        self._draining = True
        self.scheduler.shutting_down = True
        telemetry.inc("tdt_serving_drains_total")
        telemetry.emit(
            "serving_drain_begin",
            in_flight=self.scheduler.occupancy(),
            queued=self.scheduler.queue_depth(),
        )

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True once drain mode holds no admitted work (queue + slots empty)."""
        return (
            self._draining
            and self.scheduler.occupancy() == 0
            and self.scheduler.queue_depth() == 0
        )

    def journal_records(self) -> list[dict]:
        """Flush and export the attached journal's records (the migration
        donor's half of journal-replay migration). Empty without a journal."""
        if self._journal is None:
            return []
        return self._journal.read_records()

    # ------------------------------------------------- disaggregated handoff
    def export_kv(self, req_id: int) -> dict:
        """Pack a parked handoff's prefilled blocks into a wire blob
        (``disagg.kv_transfer`` v1 format). Read-only and retryable: the
        parked state stays until :meth:`release_handoff`. Raises
        ``KeyError`` when nothing is parked under ``req_id`` (the request
        never parked, or a recovery rebuild dropped the chain) — the
        caller's cue to re-derive from the journaled history."""
        self._refuse_pool_alone("export_kv")
        st = self._handoffs.get(int(req_id))
        if st is None:
            raise KeyError(f"no parked handoff for request {int(req_id)}")
        return pack_kv_blocks(self.cache, st["blocks"], length=st["length"])

    def _refuse_pool_alone(self, what: str) -> None:
        if self.stateful:
            raise ValueError(
                f"{what}: the pool's blocks alone do not restore a request of "
                "a model with per-slot state; resume it from its token history"
            )

    def release_handoff(self, req_id: int) -> bool:
        """Drop a parked handoff's extra block refs (the transfer landed,
        or the router abandoned it). Idempotent; False when unknown."""
        st = self._handoffs.pop(int(req_id), None)
        if st is None:
            return False
        self.kv_ledger.allocator.free(st["blocks"])
        self._publish_kv_gauges()
        telemetry.emit("serving_handoff_released", req_id=int(req_id))
        return True

    def import_kv(self, prompt, max_new: int, tokens, kv_blob: dict, *,
                  on_token=None, on_finish=None, priority: int = 1,
                  ttft_deadline_s: float | None = None,
                  deadline_s: float | None = None, trace_ctx=None,
                  tenant: str = "default", weight: float = 1.0) -> Request:
        """Decode-pool half of a handoff: admit a request whose prefill KV
        arrives OVER THE WIRE. ``tokens`` is the donor's streamed history
        (at least the first sampled token — the donor always samples and
        streams token0 before parking); admission runs normally (KV budget,
        shedding), the payload is applied by the join sweep in place of a
        local prefill, and seeded tokens are NOT re-streamed. The payload
        is consumed on first application, so a crash after admission falls
        back to re-deriving the same KV from the journaled token history —
        the stream stays byte-identical either way."""
        self._refuse_pool_alone("import_kv")
        payload = unpack_kv_blocks(kv_blob)
        toks = [int(t) for t in tokens][: int(max_new)]
        if not toks:
            raise ValueError("KV import needs the donor's token history")
        req = self.scheduler.submit(
            prompt, max_new, on_token=on_token, on_finish=on_finish,
            now_s=self._now(), priority=priority,
            ttft_deadline_s=ttft_deadline_s, deadline_s=deadline_s,
            tokens=toks, trace_ctx=trace_ctx, tenant=tenant, weight=weight,
        )
        if req.state is not RequestState.QUEUED:
            return req
        req.kv_import = payload
        if self._journal is not None:
            self._journal.append(
                "submit", req_id=req.req_id, prompt=req.prompt,
                max_new=req.max_new, arrival_time_s=req.arrival_time_s,
                priority=req.priority, tenant=req.tenant,
                weight=req.weight, ttft_deadline_s=req.ttft_deadline_s,
                deadline_s=req.deadline_s,
            )
            self._journal.append(
                "chunk", req_id=req.req_id, start=0, tokens=toks
            )
        return req

    # ------------------------------------------------------------------- loop
    def step(self) -> bool:
        """One scheduler iteration: probe a due circuit breaker (restoring
        the preferred backend on success), join arrived requests into free
        slots (prefill + first token), reap cancelled/expired slots, then
        one masked decode chunk over the slot batch. Returns True when any
        work was done. A health sweep runs first: an expired heartbeat
        lease (or a chaos ``die@<rank>``) triggers ONE proactive rebuild at
        the new epoch instead of a timeout per collective. A decode chunk
        may still be in flight when this returns (:meth:`_decode_once`
        says when): the next call lands it.

        The iteration is one ``tdt_serving_step`` span of the server's own
        trace and everything it does that is not free a span beneath it
        (``docs/observability.md``, "The loop's spans"): none enters the
        span ring; they reach the profiler, on the device trace's clock, and
        the ``tdt_span_self_seconds`` digest. An iteration that finds no
        tenant and no request due says so to the device's ledger first: what
        follows is ``no_work``, not the device starved by the host."""
        with self._trace.span("tdt_serving_step", ring=False):
            if self._nothing_to_serve():
                tracing.device_no_work()
            with self._trace.span("tdt_serving_health", ring=False):
                worked = self._health_sweep()
                worked = self._maybe_probe() or worked
            worked = self._join_ready() or worked
            worked = self._advance_prefills() or worked
            with self._trace.span("tdt_serving_reap", ring=False):
                self._reap_slots()
            if not self.scheduler.decoding_slots():
                return worked
            self._guarded(self._decode_once, what="decode chunk")
            return True

    def _nothing_to_serve(self) -> bool:
        if self._in_flight is not None or self.scheduler.occupancy():
            return False
        nxt = self.scheduler.next_arrival_s()
        return nxt is None or nxt > self._now()

    def run(self, poll_s: float = 0.05) -> None:
        """Serve until the queue is drained and every slot is free.
        Requests submitted from other threads while running are picked up;
        with synthetic ``arrival_time_s`` offsets the loop sleeps (bounded
        by ``poll_s``) until the next arrival is due. A pending SIGTERM
        (see :meth:`install_signal_handlers`) converts into a draining
        :meth:`shutdown`; Ctrl-C shuts down WITHOUT draining — the journal
        holds the in-flight state for :meth:`recover`."""
        try:
            while True:
                if self._shutdown_requested and not self._shutdown:
                    self.shutdown(drain=True)
                    return
                if self.step():
                    continue
                nxt = self.scheduler.next_arrival_s()
                if nxt is None:
                    if self.scheduler.queue_depth() == 0 and not self.scheduler.occupancy():
                        return
                    continue
                wait = nxt - self._now()
                if wait > 0:
                    time.sleep(min(wait, poll_s))
        except KeyboardInterrupt:
            self.shutdown(drain=False)

    # --------------------------------------------------------------- paged KV
    def _fresh_cache(self):
        """Allocate the serving KV cache and resync every piece of host
        bookkeeping to the empty pool (recovery and restore reallocate
        mid-flight).

        A fresh pool holds NO valid content, so the prefix index must
        forget its donor blocks and every surviving tenant must own its
        WHOLE chain — a shared head would re-prefill over a donor's
        garbage. Chains are released and re-reserved all-fresh; a tenant
        the shrunk effective pool can no longer hold (possible only with an
        overcommitted ``TDT_KV_BLOCKS``) is preempted back to the queue
        with its token history intact — the next join re-prefills it."""
        if self.spec_k >= 2:
            # Speculative state is never durable: a fresh cache always
            # pairs with a drafter reset + per-slot re-prefill from history.
            self._dstate = self._drafter.init_state(self.num_slots)
        self._prefilling.clear()
        self._lengths = np.zeros((self.num_slots,), np.int32)
        led = self.kv_ledger
        led.prefix.clear()
        if self._handoffs:
            # A pool rebuild invalidates every parked chain's CONTENT, so
            # the parked refs must not outlive it: drop them — a later
            # export fails loudly and the router re-derives the KV from the
            # journaled token history instead of shipping garbage.
            for st in self._handoffs.values():
                led.allocator.free(st["blocks"])
            telemetry.emit(
                "serving_handoffs_dropped", n=len(self._handoffs),
            )
            self._handoffs.clear()
        occupied = self.scheduler.occupied_slots()
        for slot in occupied:
            led.release(slot.request)
        for slot in occupied:
            req = slot.request
            req.kv_shared = 0
            if led.reserve(req):
                continue
            self.scheduler.finish(slot)
            self.scheduler.release(slot)
            self._remaining[slot.idx] = 0
            req.state = RequestState.QUEUED
            telemetry.emit("serving_kv_requeue", req_id=req.req_id)
            self.scheduler.restore(req)
        self.cache = self.engine.alloc_paged(
            self.num_slots, block_size=self.block_size,
            num_blocks=self.num_blocks, quant=self.kv_quant,
        )
        # Teach admission the pool's REAL per-block HBM cost (payloads +
        # scale pools) — quantized pools admit more chains per byte and the
        # ledger/gauges must reflect that, not the logical block count.
        led.set_bytes_per_block(self.cache.bytes_per_block)
        for kind, nbytes in self.cache.bytes_per_block_by_kind.items():
            telemetry.set_gauge(
                "tdt_kv_pool_bytes", float(nbytes * self.num_blocks), kind=kind
            )
        if self.stateful:
            led.set_slot_state_bytes(self.cache.slot_state_bytes)
            telemetry.set_gauge(
                "tdt_kv_pool_bytes", float(self.cache.slot_state_bytes),
                kind="slot_state",
            )
        self._push_tables()
        self._publish_kv_gauges()
        return self.cache

    def _table_row(self, req: Request) -> np.ndarray:
        """``req``'s block chain as one padded device-table row."""
        row = np.zeros((self.cache.max_blocks,), np.int32)
        row[: len(req.kv_blocks)] = req.kv_blocks
        return row

    def _push_tables(self) -> None:
        """Re-push every slot's block table + KV length to the device. The
        tables are DATA operands of the (fixed-shape) paged programs, so
        this never recompiles anything."""
        with self._trace.span("tdt_serving_table_push", ring=False):
            mb = self.cache.max_blocks
            tables = np.zeros((self.num_slots, mb), np.int32)
            for slot in self.scheduler.occupied_slots():
                chain = slot.request.kv_blocks
                tables[slot.idx, : len(chain)] = chain
            # Placed on the mesh here, once per push: left on the default
            # device they would be copied to every chip again by each
            # dispatch that takes them. The mirror is snapshotted — a
            # zero-copy alias of an aligned numpy buffer would let later
            # host-side `+=` mutations leak into (or race with) device
            # reads, a run-to-run coin flip.
            tables, lengths = jax.device_put(
                (tables, self._lengths.astype(np.int32)),
                self.engine.model.ctx.replicated(),
            )
            self.cache = dataclasses.replace(
                self.cache, tables=tables, lengths=lengths
            )

    def _publish_kv_gauges(self) -> None:
        s = self.kv_ledger.stats()
        telemetry.set_gauge("tdt_kv_blocks_free", float(s["blocks_free"]))
        telemetry.set_gauge("tdt_kv_blocks_used", float(s["blocks_used"]))
        telemetry.set_gauge("tdt_kv_blocks_shared", float(s["blocks_shared"]))
        if s.get("bytes_per_block"):
            telemetry.set_gauge(
                "tdt_kv_bytes_per_block", float(s["bytes_per_block"])
            )

    # ------------------------------------------------------------------ joins
    def _join_ready(self) -> bool:
        with self._trace.span("tdt_serving_join", ring=False):
            joined = self.scheduler.join_free_slots(self._now())
            for slot in joined:
                # A recovery triggered by an EARLIER slot's failed prefill
                # already re-prefilled every occupied slot, this one
                # included (or finished+released it) — do not stream its
                # first token twice. State is the discriminator, not token
                # history: a journal-recovered request joins WITH tokens but
                # still in PREFILL, and must re-prefill from them.
                if slot.request is None or slot.state is not SlotState.PREFILL:
                    continue
                # The join only ARMS the chunked prefill; the per-step
                # _advance_prefills sweep advances it one chunk at a time.
                self._guarded(lambda s=slot: self._begin_prefill(s),
                              what=f"join of request {slot.request.req_id}")
        if joined:
            telemetry.inc("tdt_serving_joins_total", float(len(joined)))
            if self._prefix_skipped:
                telemetry.inc("tdt_serving_prefix_lookups_skipped_total",
                              float(len(joined)))
        return bool(joined)

    def _prefill_to_completion(self, slot: Slot) -> None:
        """The chunked prefill of ``slot``'s tenant run to its end before
        the next slot's turn: what recovery and restore re-prefill with.
        History is ``prompt + tokens[:-1]`` (the last streamed token's KV is
        pending, exactly like a resumed decode) — the prefill-sampled token
        is discarded, nothing streams twice."""
        self._begin_prefill(slot)
        while slot.idx in self._prefilling:
            self._advance_prefill(slot)

    # ------------------------------------------------------- chunked prefill
    def _begin_prefill(self, slot: Slot) -> None:
        """Arm a chunked prefill: seed the context buffer — from the reused
        prefix chain when the ledger found one, zeros otherwise — and queue
        the slot on the prefill cursor map. The sampling key is split HERE,
        in join order, so the token stream does not depend on how the
        chunks of several prefills interleave."""
        with self._trace.span("tdt_serving_prefill_arm", ring=False, slot=slot.idx):
            req = slot.request
            if req.kv_import is not None:
                # Disaggregated handoff: the prefill KV arrived over the wire.
                # The payload is consumed up front so any failure — a malformed
                # blob, a pool-geometry mismatch, a recovery preemption — falls
                # back to deriving the very same KV from the token history
                # below (the determinism fallback: stored wire bytes and a
                # local prefill produce bitwise-identical blocks).
                payload, req.kv_import = req.kv_import, None
                try:
                    self._import_prefill(slot, payload)
                    return
                except Exception as e:
                    telemetry.emit(
                        "serving_kv_import_failed", req_id=req.req_id,
                        error=f"{type(e).__name__}: {e}",
                    )
            ids = req.prompt + req.tokens[:-1]
            # Scripted chaos site: "recovery" when re-prefilling from history
            # (double-fault scenarios), "prefill" on a fresh join.
            resilience.chaos_check("recovery" if req.tokens else "prefill")
            self._key, sub = jax.random.split(self._key)
            p_len = len(ids)
            shared_rows = min(req.kv_shared * self.block_size, max(p_len - 1, 0))
            if shared_rows > 0:
                kbuf, vbuf = self.engine.paged_seed_kbuf(
                    self.cache, self._table_row(req), shared_rows, p_len
                )
            else:
                kbuf, vbuf = self.engine.paged_kbuf_zeros(p_len)
            self._prefilling[slot.idx] = {
                "req": req, "ids": ids, "off": shared_rows,
                "kbuf": kbuf, "vbuf": vbuf, "key": sub, "n_chunks": 0,
                "state": self.engine.prompt_state(),
                # Seconds of this request's own prefill calls so far
                # (``tdt_serving_prefill_own_seconds``).
                "own_s": 0.0,
            }

    def _advance_prefills(self) -> bool:
        """Advance every in-flight chunked prefill by ONE chunk (the decode
        stall bound: a long prompt joining mid-decode delays the next decode
        dispatch by at most one chunk's work). Only a prompt's last chunk
        is waited for (:meth:`_advance_prefill`): the others queue on the
        device behind whatever is in flight."""
        if not self._prefilling:
            return False
        for idx in list(self._prefilling):
            if idx not in self._prefilling:
                continue  # a recovery mid-sweep rebuilt the cursor map
            slot = self.scheduler.slots[idx]
            self._guarded(lambda s=slot: self._advance_prefill(s),
                          what=f"prefill chunk for slot {idx}")
        return True

    def _advance_prefill(self, slot: Slot) -> None:
        st = self._prefilling.get(slot.idx)
        if st is None:
            return
        ids, off, req = st["ids"], st["off"], st["req"]
        p_len = len(ids)
        # Chunk geometry: C = min(knob, P). A prompt no longer than the
        # knob prefills in ONE chunk sized exactly to it — no padding, and
        # bitwise-identical to the one-shot prefill program. The final
        # chunk of a longer prompt arrives PADDED to C; the drop-mode
        # insert in the kernel discards rows past P.
        c = min(self.prefill_chunk, p_len)
        take = ids[off:off + c]
        chunk_ids = np.zeros((1, c), np.int32)
        chunk_ids[0, : len(take)] = take
        final = off + len(take) >= p_len
        last_idx = (p_len - 1 - off) if final else (c - 1)
        t_own = self._now()
        with req.trace.span(
            "tdt_serving_prefill", slot=slot.idx, hist_len=p_len,
            off=off, chunk_len=len(take), recovery=bool(req.tokens),
        ):
            # Waited for only where its result is needed, at the prompt's
            # last chunk: until then the buffers are the prompt's own and the
            # logits are nobody's, so the chunk is left to the device.
            logits, st["kbuf"], st["vbuf"], st["state"] = self.engine.prefill_chunk_state(
                st["kbuf"], st["vbuf"], jnp.asarray(chunk_ids), off, last_idx,
                st["state"], wait=final,
            )
        st["off"] = off + len(take)
        st["n_chunks"] += 1
        st["own_s"] += self._now() - t_own
        if not final:
            telemetry.inc("tdt_serving_prefill_chunks_unfenced_total")
            return
        # The completion writes the pool, the tables and the mirrors, and
        # the slot decodes from the host's ``_last`` / ``_remaining``: a
        # decode chunk kept in flight beside this prefill lands first (the
        # fence above has waited for it too: it was issued before).
        self._land_in_flight("prefill")
        with self._trace.span(
            "tdt_serving_prefill_complete", ring=False, slot=slot.idx
        ):
            self._complete_prefill(slot, st, logits)

    def _complete_prefill(self, slot: Slot, st: dict, logits) -> None:
        """Finish a chunked prefill: scatter the context buffer into the
        pool along the slot's chain (shared prefix blocks stay the donor's),
        publish the table row, then sample/stream token0. A request that
        arrives WITH tokens (recovery, restore, journal replay, migration)
        arms decode from its history instead.

        As token0 streams, the request's time in its slot is known:
        ``tdt_serving_prefill_residence_seconds`` from its admission (where
        ``tdt_serving_queue_wait_seconds`` ends) to that token (where
        ``tdt_serving_ttft_seconds`` ends, on the same clock), and of it
        ``tdt_serving_prefill_own_seconds``, its own chunk calls and this
        completion up to the token. The rest it sat in the slot while the
        loop served the others."""
        t_own = self._now()
        req = st["req"]
        del self._prefilling[slot.idx]
        p_len = len(st["ids"])
        self.cache = self.engine.complete_paged_prefill(
            self.cache, st["kbuf"], st["vbuf"], self._table_row(req),
            req.kv_shared, slot.idx, st["state"],
        )
        self._lengths[slot.idx] = p_len
        self.kv_ledger.register_prefix(req)
        # CoW safety net over decode's write range. Structurally dead (the
        # index stops at full PROMPT blocks; decode writes past them) but
        # it turns a future invariant slip into a copy, not corruption.
        for j in range(p_len // self.block_size, len(req.kv_blocks)):
            self.kv_ledger.make_writable(req, j)
        self._push_tables()
        self._publish_kv_gauges()
        telemetry.observe("tdt_serving_prefill_chunks", float(st["n_chunks"]))
        self._spec_prefill(slot.idx, st["ids"])
        if req.tokens:
            # Recovery re-prefill: the last streamed token's KV is pending,
            # nothing streams twice. Host decode state must derive from the
            # durable history, not from retained process memory: a
            # journal-recovered request arrives in a FRESH process where
            # _remaining is all zeros.
            self._last[slot.idx] = req.tokens[-1]
            self._remaining[slot.idx] = max(req.max_new - len(req.tokens), 0)
            if slot.state is SlotState.PREFILL:
                self.scheduler.start_decode(slot)
            if self._remaining[slot.idx] == 0:
                # Fully generated before the crash, only the finish record
                # was lost — finalize now, nothing to decode.
                self._finish(slot)
            elif req.prefill_only:
                # A prefill-pool donor recovering mid-handoff re-parks: the
                # re-derived chain is bitwise the one it would have shipped.
                self._park_handoff(slot, p_len)
            return
        _, sub = jax.random.split(st["key"])
        tok = self.engine.sample_logits(logits, sub)
        with self._trace.span("tdt_serving_fetch", ring=False, what="token0"):
            tok = int(tok[0])
        self._last[slot.idx] = tok
        self._remaining[slot.idx] = req.max_new - 1
        self.scheduler.start_decode(slot)
        with self._trace.span("tdt_serving_emit", ring=False, n_tokens=1):
            self._stream(req, tok)
        if req.admitted_at is not None:
            telemetry.observe(
                "tdt_serving_prefill_residence_seconds",
                max(req.first_token_at - req.admitted_at, 0.0),
            )
            telemetry.observe(
                "tdt_serving_prefill_own_seconds",
                st["own_s"] + max(req.first_token_at - t_own, 0.0),
            )
        if self._journal is not None:
            self._journal.append(
                "prefill", req_id=req.req_id, start=0, tokens=[tok]
            )
        if self._remaining[slot.idx] == 0:
            self._finish(slot)
        elif req.prefill_only:
            self._park_handoff(slot, p_len)

    def _park_handoff(self, slot: Slot, p_len: int) -> None:
        """Prefill-pool half of a disaggregated handoff: keep the prefilled
        chain alive under one extra allocator ref per block, record the
        export state, and finish the slot with reason ``"handoff"`` — the
        fleet router reads that finish as "ready to transfer", not
        "complete". The parked blocks outlive the slot's release until
        :meth:`release_handoff` (or process death, after which the router
        re-derives the KV from the journaled token history)."""
        req = slot.request
        self.kv_ledger.allocator.incref(req.kv_blocks)
        self._handoffs[req.req_id] = {
            "blocks": list(req.kv_blocks),
            "length": int(p_len),
            "tokens": list(req.tokens),
            "tenant": req.tenant,
        }
        telemetry.emit(
            "serving_handoff_parked", req_id=req.req_id, kv_len=int(p_len),
            n_blocks=len(req.kv_blocks),
        )
        self._finish(slot, reason="handoff")

    def _import_prefill(self, slot: Slot, payload: dict) -> None:
        """Apply an unpacked handoff payload in place of a local prefill:
        CoW-isolate the chain (a prefix-index hit may have lent shared
        blocks; every scattered block is fully overwritten, so no content
        copy is needed), scatter the wire blocks in, and arm decode at the
        seeded history. The sampling key is still split in join order, so
        this server's key stream stays uniform with a local prefill."""
        req = slot.request
        ids = req.prompt + req.tokens[:-1]
        p_len = len(ids)
        if int(payload["length"]) != p_len:
            raise ValueError(
                f"handoff covers {payload['length']} rows, prefill history "
                f"holds {p_len}"
            )
        if int(payload["n_blocks"]) > len(req.kv_blocks):
            raise ValueError(
                f"handoff ships {payload['n_blocks']} blocks, chain holds "
                f"{len(req.kv_blocks)}"
            )
        self._key, _ = jax.random.split(self._key)
        for j in range(len(req.kv_blocks)):
            self.kv_ledger.make_writable(req, j)
        self.cache = scatter_kv_blocks(self.cache, req.kv_blocks, payload)
        self._lengths[slot.idx] = p_len
        # The scattered content is bitwise what a local prefill writes, so
        # indexing it for prefix reuse is as sound as after a local prefill.
        self.kv_ledger.register_prefix(req)
        self._push_tables()
        self._publish_kv_gauges()
        self._spec_prefill(slot.idx, ids)
        self._last[slot.idx] = req.tokens[-1]
        self._remaining[slot.idx] = max(req.max_new - len(req.tokens), 0)
        if slot.state is SlotState.PREFILL:
            self.scheduler.start_decode(slot)
        telemetry.emit(
            "serving_kv_import", req_id=req.req_id, kv_len=p_len,
            n_blocks=int(payload["n_blocks"]),
        )
        if self._remaining[slot.idx] == 0:
            self._finish(slot)

    # ----------------------------------------------------------------- decode
    def _decode_once(self) -> None:
        """Issue one decode chunk and land what is due.

        The loop keeps at most one chunk **in flight** (issued, not landed)
        across :meth:`step`. With chunk k in flight, chunk k+1 is issued from
        k's last tokens and counts as they lie on the device, and only then
        is k landed: waited for, fetched, streamed, journaled. So the device
        goes from k to k+1 while the host still works on k. Chunk k+1 in its
        turn stays in flight only if nothing about it changes the slot set
        (:meth:`_sync_reason`); otherwise it is landed here too, before
        :meth:`step` returns, which is the order the loop always had. The
        prefill chunks a step issues lie between k and k+1 on the device, as
        they always did; those that are not their prompt's last are waited
        for by nobody.

        The host's mirrors follow the issue, not the landing: ``_remaining``
        and ``_lengths`` advance by what the chunk will do (``min(remaining,
        chunk)`` a slot, known beforehand: there is no stop token), so a
        table push between the two never rolls a slot back. Whatever reads
        or rewrites the slots, the tables or the cache from outside this
        method lands the chunk in flight first (:meth:`_land_in_flight`)."""
        if self.spec_k >= 2:
            self._spec_decode_once()
            return
        ahead = self._in_flight
        with self._trace.span("tdt_serving_decode_prep", ring=False):
            decoding = self.scheduler.decoding_slots()
            pre = {s.idx: int(self._remaining[s.idx]) for s in decoding}
            self._key, sub = jax.random.split(self._key)
        t0 = time.perf_counter()
        # One decode chunk is ONE shared device dispatch over the whole slot
        # batch: its issue gets a single span in the SERVER trace (and is
        # the ambient span while the chunk compiles, for KernelTrace
        # correlation); at the landing each tenant gets a per-slot chunk
        # span in its own trace, from the issue to the landing, referencing
        # the shared span's id.
        d_start = tracing.now_s()
        issued: list = []
        with self._trace.span(
            "tdt_serving_dispatch", n_active=len(decoding), chunk=self.chunk
        ) as dsp:
            if ahead is None:
                # Placed as the chunk program places its own outputs, so
                # that both routes run one executable. Copies: the mirrors
                # change below, before the device need have read them.
                tok, rem = jax.device_put(
                    (self._last.copy(), self._remaining.copy()),
                    self.engine.model.ctx.replicated(),
                )
            else:
                tok, rem = ahead.tok, ahead.rem
            # Through ``decode_steps_paged`` by that name, and with what it
            # returns: whoever wraps the engine's decode call (the
            # benchmark's planted faults do) wraps the loop's chunks.
            out, tok, self.cache, rem = self.engine.decode_steps_paged(
                self.cache, tok, rem, self.chunk, sub, in_flight=issued,
            )
        chunk = _Chunk(
            issued[0], out, tok, rem, decoding, pre, t0, d_start,
            dsp["span_id"] if dsp is not None else None,
        )
        for slot in decoding:
            n = min(pre[slot.idx], self.chunk)
            self._remaining[slot.idx] -= n
            self._lengths[slot.idx] += n  # the device's advance in-chunk
        # From here a landing that fails drops all that is in flight: none
        # of it was streamed or journaled, and recovery re-prefills from
        # the history that was.
        self._in_flight = None
        if ahead is not None:
            self._land(ahead, None)
        self._in_flight = chunk
        if ahead is not None:
            # What just streamed may have cancelled a request or run its
            # deadline out (a callback, the clock): the boundary's sweep,
            # as it ran between the two chunks when k was landed before
            # k+1 was issued. A slot it reaps gets nothing of k+1.
            with self._trace.span("tdt_serving_reap", ring=False):
                self._reap_slots()
        if self._in_flight is chunk and (why := self._sync_reason(chunk)) is not None:
            self._land_in_flight(why)

    def _sync_reason(self, chunk: _Chunk) -> str | None:
        """Why the chunk just issued is landed before :meth:`step` returns
        (the label of ``tdt_serving_decode_sync_boundaries_total``), or None:
        it stays in flight, because the next chunk will run over the same
        slots whatever this one streams. The first that holds, in this
        order: a slot finishes inside it; a slot is free (a request may join
        it at the next boundary); the chunk bounced through the contiguous
        layout (a pp mesh: landed as issued); the server is draining. A slot
        that prefills is no reason: its chunks touch the prompt's own buffers
        alone, and the one that completes it lands what is in flight itself
        (:meth:`_advance_prefill`, counted as ``prefill``). The loop can see
        all of it before the chunk runs; nothing here is a setting."""
        if any(n <= self.chunk for n in chunk.pre.values()):
            return "finish"
        if any(s.state not in (SlotState.DECODE, SlotState.PREFILL)
               for s in self.scheduler.slots):
            return "free_slot"
        if chunk.handle.landed:
            return "bounce"
        if self._draining or self._shutdown:
            return "drain"
        return None

    def _land_in_flight(self, why: str = "other", drop=()) -> None:
        """Land the chunk in flight, if any: what everything outside
        :meth:`_decode_once` does before it touches the slots, the tables,
        the cache or the mirrors. A landing that fails leaves nothing in
        flight; the caller's ``_guarded`` recovers."""
        chunk, self._in_flight = self._in_flight, None
        if chunk is not None:
            self._land(chunk, why, drop)

    def _land(self, chunk: _Chunk, why: str | None, drop=()) -> None:
        """Land one issued chunk: wait for it (in the engine, under
        ``tdt_engine_host_sync``; the watchdog bounds the wait), fetch its
        tokens, stream and journal them, finish the slots it finished.
        ``why`` is None for a chunk landed behind the next one's issue, else
        why it was not. The slots in ``drop`` get nothing of it: they are
        being reaped, and the chunk was issued before the reap could know."""
        with self._trace.span("tdt_serving_fetch", ring=False, what="chunk"):
            # The scripted fault of a decode chunk shows where a real one
            # does: when the host comes for the chunk.
            resilience.chaos_check("decode")
            self._watchdog.call(self.engine.land_decode_chunk, chunk.handle, why)
            out_np = np.asarray(chunk.out)
            self._last = np.asarray(chunk.tok, dtype=np.int32).copy()
        d_end = tracing.now_s()
        now = time.perf_counter()
        wall = now - max(chunk.t_issue, self._landed_at)
        self._landed_at = now
        telemetry.inc("tdt_serving_decode_chunks_total")
        telemetry.observe("tdt_serving_decode_chunk_seconds", wall)
        if why is None:
            telemetry.inc("tdt_serving_decode_chunks_ahead_total")
        else:
            telemetry.inc("tdt_serving_decode_sync_boundaries_total", why=why)
        # Rows the chunk's steps really advanced (a slot that runs out
        # inside the chunk idles for the rest of it), of slots x chunk.
        telemetry.inc(
            "tdt_serving_decode_rows_total",
            float(sum(min(n, self.chunk) for n in chunk.pre.values())),
        )
        decoding = [s for s in chunk.decoding if s.idx not in drop]
        with self._trace.span("tdt_serving_emit", ring=False):
            n_streamed = 0
            for slot in decoding:
                req = slot.request
                n_valid = min(chunk.pre[slot.idx], self.chunk)
                req.trace.record(
                    "tdt_serving_decode_chunk", chunk.d_start, d_end,
                    slot=slot.idx, n_tokens=n_valid, dispatch=chunk.dispatch_id,
                )
                s_start = tracing.now_s()
                toks = [int(out_np[slot.idx, j]) for j in range(n_valid)]
                for t in toks:
                    self._stream(req, t)
                if n_valid:
                    req.trace.record(
                        "tdt_serving_stream", s_start, tracing.now_s(),
                        slot=slot.idx, n_tokens=n_valid,
                    )
                    if self._journal is not None:
                        self._journal.append(
                            "chunk", req_id=req.req_id,
                            start=len(req.tokens) - n_valid, tokens=toks,
                        )
                n_streamed += n_valid
        # Finishes run after every slot's tokens are out: _finish pushes the
        # tables, and frees a slot that a request may join next.
        for slot in decoding:
            if slot.request is not None and chunk.pre[slot.idx] <= self.chunk:
                self._finish(slot)
        if n_streamed:
            telemetry.inc("tdt_serving_tokens_total", float(n_streamed))
            # Feed the admission-time overload projection.
            self.scheduler.note_decode_rate(n_streamed, wall)

    def _pin_draft_blocks(self, decoding) -> None:
        """CoW-isolate every block the coming draft window may write.

        The verify step writes draft KV at rows ``[length, length + ec)``
        per round — always inside the tenant's reserved chain, past its
        full prompt blocks, so structurally these blocks are already
        exclusive (the prefix index never indexes them and
        ``_complete_prefill`` pre-pins the decode tail). This sweep is the
        speculative analog of that safety net: ``ensure_exclusive`` on the
        whole draft window turns any future sharing-invariant slip into a
        block copy instead of silently corrupting a prefix donor's KV. A
        copy remaps the chain, so the device tables are re-pushed."""
        from triton_dist_tpu.models.kv_cache import draft_block_range

        copied_any = False
        for slot in decoding:
            req = slot.request
            lo, hi = draft_block_range(
                int(self._lengths[slot.idx]), self.chunk * self.spec_k,
                self.block_size,
            )
            for j in range(lo, min(hi, len(req.kv_blocks))):
                _, copied = self.kv_ledger.make_writable(req, j)
                copied_any = copied_any or copied
        if copied_any:
            self._push_tables()
            self._publish_kv_gauges()

    def _spec_decode_once(self) -> None:
        """One speculative decode chunk: the drafter proposes up to
        ``kcap[slot]`` tokens per active slot per round, the target scores
        every draft in ONE k-wide masked verify dispatch, and only the
        greedy-agreeing prefix (plus the target's own next token) is
        accepted — rejected rows are rolled back by rewinding the device
        lengths, so the stream stays byte-identical to plain greedy
        decode. Acceptance stats feed per-slot adaptive k backoff."""
        resilience.chaos_check("decode")
        decoding = self.scheduler.decoding_slots()
        pre = {s.idx: int(self._remaining[s.idx]) for s in decoding}
        self._pin_draft_blocks(decoding)
        self._key, sub = jax.random.split(self._key)
        t0 = time.perf_counter()
        d_start = tracing.now_s()
        with self._trace.span(
            "tdt_serving_dispatch", n_active=len(decoding), chunk=self.chunk,
            spec_k=self.spec_k,
        ) as dsp:
            out, tok, cache, _, dstate, stats = self._watchdog.call(
                self.engine.spec_decode_steps_paged, self.cache, self._dstate,
                jnp.asarray(self._last), jnp.asarray(self._remaining),
                jnp.asarray(self._kcap), self.chunk, self.spec_k, sub,
            )
        d_end = tracing.now_s()
        dispatch_id = dsp["span_id"] if dsp is not None else None
        self.cache = cache
        self._dstate = dstate
        out_np = np.asarray(out)
        stats_np = np.asarray(stats)
        self._last = np.asarray(tok, dtype=np.int32).copy()
        wall = time.perf_counter() - t0
        telemetry.inc("tdt_serving_decode_chunks_total")
        telemetry.observe("tdt_serving_decode_chunk_seconds", wall)
        # The accepted counts are the host's to read before the next round:
        # a speculative chunk never stays in flight.
        telemetry.inc("tdt_serving_decode_sync_boundaries_total", why="spec")
        n_streamed = 0
        n_proposed = 0
        n_accepted = 0
        for slot in decoding:
            req = slot.request
            # The out row is (chunk * k) wide with -1 holes after each
            # round's accepted prefix — compact to the accepted stream.
            toks = [int(t) for t in out_np[slot.idx] if t >= 0]
            n_valid = min(len(toks), pre[slot.idx])
            toks = toks[:n_valid]
            req.trace.record(
                "tdt_serving_decode_chunk", d_start, d_end,
                slot=slot.idx, n_tokens=n_valid, dispatch=dispatch_id,
                spec_k=self.spec_k,
            )
            s_start = tracing.now_s()
            for t in toks:
                self._stream(req, t)
            if n_valid:
                req.trace.record(
                    "tdt_serving_stream", s_start, tracing.now_s(),
                    slot=slot.idx, n_tokens=n_valid,
                )
                if self._journal is not None:
                    # Only ACCEPTED tokens ever reach the journal — replay
                    # and migration never see speculative state.
                    self._journal.append(
                        "chunk", req_id=req.req_id,
                        start=len(req.tokens) - n_valid, tokens=toks,
                    )
            self._remaining[slot.idx] -= n_valid
            self._lengths[slot.idx] += n_valid
            n_streamed += n_valid
            proposed, accepted, rounds = (int(x) for x in stats_np[slot.idx])
            n_proposed += proposed
            n_accepted += accepted
            if rounds > 0:
                telemetry.observe("tdt_spec_accept_len", accepted / rounds)
            if proposed > 0:
                # Adaptive k: EWMA of the per-chunk acceptance fraction;
                # persistent rejection shrinks this slot's draft width to
                # 1, recovery grows it back toward TDT_SPEC_K.
                frac = accepted / proposed
                ew = 0.5 * self._accept_ewma[slot.idx] + 0.5 * frac
                self._accept_ewma[slot.idx] = ew
                if ew < self.spec_min_accept:
                    self._kcap[slot.idx] = max(int(self._kcap[slot.idx]) - 1, 1)
                elif int(self._kcap[slot.idx]) < self.spec_k:
                    self._kcap[slot.idx] += 1
            telemetry.set_gauge(
                "tdt_spec_k", float(self._kcap[slot.idx]), slot=str(slot.idx)
            )
        if n_proposed:
            telemetry.inc("tdt_spec_proposed_total", float(n_proposed))
        if n_accepted:
            telemetry.inc("tdt_spec_accepted_total", float(n_accepted))
        for slot in decoding:
            if slot.request is not None and self._remaining[slot.idx] == 0:
                self._finish(slot)
        if n_streamed:
            telemetry.inc("tdt_serving_tokens_total", float(n_streamed))
            self.scheduler.note_decode_rate(n_streamed, wall)

    # -------------------------------------------------------------- streaming
    def _stream(self, req: Request, token: int) -> None:
        req.tokens.append(token)
        now = self._now()
        if req.first_token_at is None:
            req.first_token_at = now
            telemetry.observe(
                "tdt_serving_ttft_seconds", max(now - req.arrived_at, 0.0)
            )
        if req.on_token is not None:
            try:
                req.on_token(req, token, len(req.tokens) - 1)
            except Exception:  # a user callback must never kill the loop
                telemetry.inc("tdt_serving_callback_errors_total", kind="token")

    def _finish(self, slot: Slot, reason: str = "ok") -> None:
        """End a slot's stream and free it. ``reason`` distinguishes a
        natural completion ("ok") from a client cancel ("cancelled") and a
        total-deadline truncation ("deadline") — only "ok" counts toward
        ``tdt_serving_requests_completed_total``."""
        with self._trace.span(
            "tdt_serving_finish_slot", ring=False, slot=slot.idx, reason=reason
        ):
            req = slot.request
            req.finish_reason = reason
            req.state = (
                RequestState.CANCELLED if reason == "cancelled" else RequestState.DONE
            )
            req.finished_at = self._now()
            if reason == "ok":
                tpot = req.tpot_s
                if tpot is not None:
                    telemetry.observe("tdt_serving_tpot_seconds", tpot)
                telemetry.inc("tdt_serving_requests_completed_total")
            # Per-(tenant, tier) SLO ledger: digests + goodput/violation
            # counters, classified against the request's own deadline fields.
            slo.record_finish(req, reason)
            self.scheduler.finish(slot)
            self.scheduler.release(slot)
            self._remaining[slot.idx] = 0
            # A cancel can land mid-prefill: drop the cursor (its context
            # buffers die with it), return the chain, null the table row.
            self._prefilling.pop(slot.idx, None)
            self._lengths[slot.idx] = 0
            self.kv_ledger.release(req)
            self._push_tables()
            self._publish_kv_gauges()
            if self._journal is not None:
                # "finish" always forces the fsync: a completed stream must be
                # durable so recovery can skip it idempotently.
                self._journal.append(
                    "finish", req_id=req.req_id, reason=reason,
                    n_tokens=len(req.tokens),
                )
            if req.on_finish is not None:
                try:
                    req.on_finish(req)
                except Exception:
                    telemetry.inc("tdt_serving_callback_errors_total", kind="finish")
            req.trace.point("tdt_serving_finish", slot=slot.idx, reason=reason)
            req.trace.finish(status=reason, n_tokens=len(req.tokens))

    def _reap_slots(self) -> None:
        """Chunk-boundary lifecycle sweep: free cancelled slots and truncate
        streams whose TOTAL deadline passed mid-decode. Runs between chunk
        dispatches, so both free their slot within one chunk of the event.
        A chunk in flight was issued before the sweep could know: it is
        landed first, and a reaped slot gets nothing of it (the chunk would
        not have run for that slot had the loop landed before it issued)."""
        now = self._now()
        hits = []
        for slot in self.scheduler.occupied_slots():
            req = slot.request
            if slot.state not in (SlotState.PREFILL, SlotState.DECODE):
                continue
            if req.cancel_requested:
                hits.append((slot, req, "cancelled"))
            elif (
                req.deadline_s is not None
                and now - req.arrived_at > req.deadline_s
            ):
                hits.append((slot, req, "deadline"))
        if hits and self._in_flight is not None:
            self._guarded(
                lambda: self._land_in_flight(drop={slot.idx for slot, _, _ in hits}),
                what="decode chunk",
            )
        for slot, req, reason in hits:
            if slot.request is not req:
                continue  # a recovery at the landing sent it back to the queue
            if reason == "cancelled":
                telemetry.inc("tdt_serving_cancelled_total", where="running")
            else:
                telemetry.inc(
                    "tdt_serving_deadline_expiries_total", where="decode"
                )
                telemetry.observe(
                    "tdt_serving_deadline_overrun_seconds",
                    now - req.arrived_at - req.deadline_s,
                )
            self._finish(slot, reason=reason)

    # ----------------------------------------------------------- rank health
    def _health_sweep(self) -> bool:
        """Per-step liveness check: expire heartbeat leases on the installed
        ``mesh.HealthBoard`` (if any), and — when ranks are dead while the
        engine still runs a fused backend — rebuild ONCE at the new epoch.
        This is the no-timeout-storm property: discovery costs one sweep,
        not one bounded-wait abort per collective per step."""
        from triton_dist_tpu.runtime import mesh

        board = mesh.health_board()
        if board is not None:
            board.sweep()
        dead = resilience.dead_ranks()
        if dead and self.engine.backend != "xla":
            self._recover(
                f"dead rank(s) {sorted(dead)} at mesh epoch "
                f"{resilience.mesh_epoch()}"
            )
            return True
        return False

    # --------------------------------------------------------------- recovery
    def _guarded(self, fn, what: str):
        """Run one serving step; on a degraded-mode failure (bounded-wait
        abort or watchdog timeout), rebuild on xla WITHOUT dropping the
        queue or any in-flight stream, then resume. Anything else raises."""
        try:
            return fn()
        except Exception as e:
            # Host-injected aborts (chaos) can fire even while the engine is
            # already on xla — recovery handles both, it just skips the
            # backend rebuild and reallocates the cache.
            recoverable = isinstance(
                e, (resilience.CollectiveAbortError,
                    resilience.CollectiveTimeoutError)
            ) or (self.engine.backend != "xla" and resilience.any_degraded())
            if not recoverable:
                raise
            self._recover(f"{type(e).__name__} during {what}")
            return None

    def _reprefill_occupied(self, occupied) -> None:
        """Re-prefill every in-flight slot from its durable token history,
        absorbing faults that land DURING the re-prefill (the double-fault
        scenario): each retry rebuilds on xla over a fresh cache — the
        failed attempt's prefill scatter consumed (donated) cache buffers —
        and starts the walk over. Safe to restart: a slot whose re-prefill
        already succeeded just re-prefills again; token0 cannot stream twice
        because a recovering request's history is non-empty."""
        attempts = 0
        while True:
            try:
                for slot in occupied:
                    if slot.request is None:
                        # Preempted back to the queue by the pool fixup
                        # (_fresh_cache) — nothing to re-prefill.
                        continue
                    self._prefill_to_completion(slot)
                return
            except (resilience.CollectiveAbortError,
                    resilience.CollectiveTimeoutError) as e:
                attempts += 1
                telemetry.inc("tdt_serving_recovery_retries_total")
                telemetry.emit(
                    "serving_recovery_retry",
                    why=type(e).__name__, attempt=attempts,
                )
                if attempts >= REPREFILL_RETRIES:
                    raise
                if self.engine.backend != "xla":
                    self.engine._degrade_to_xla(
                        f"{type(e).__name__} during recovery re-prefill"
                    )
                self.cache = self._fresh_cache()

    def _recover(self, why: str) -> None:
        # A chunk still in flight lands first: what it streams is history
        # the re-prefill starts from. One that cannot land is dropped
        # unstreamed, as the chunk that failed was.
        try:
            self._land_in_flight()
        except Exception as e:
            telemetry.emit(
                "serving_in_flight_dropped", error=f"{type(e).__name__}: {e}"
            )
        eng = self.engine
        from_backend = eng.backend
        occupied = self.scheduler.occupied_slots()
        telemetry.inc("tdt_serving_recoveries_total", from_backend=from_backend)
        if occupied:
            # Each in-flight slot's decode is preempted by the rebuild (the
            # only preemption in the system) and re-prefilled from history.
            telemetry.inc("tdt_serving_preemptions_total", float(len(occupied)))
        telemetry.emit(
            "serving_recovery", from_backend=from_backend, why=why,
            in_flight=len(occupied), queued=self.scheduler.queue_depth(),
        )
        r_start = tracing.now_s()
        eng._degrade_to_xla(why)
        # The aborted dispatch consumed (donated) or may have poisoned the
        # old pool — rebuild it whole from each tenant's durable
        # token history. Queued requests ride along untouched.
        self.cache = self._fresh_cache()
        self._reprefill_occupied(occupied)
        r_end = tracing.now_s()
        telemetry.observe("tdt_serving_recovery_seconds", r_end - r_start)
        # Recovery preempted every in-flight request — each affected trace
        # gets the full rebuild+re-prefill interval as a span of its own
        # (parented at its root), plus one in the server trace.
        for slot in occupied:
            if slot.request is not None:
                slot.request.trace.record(
                    "tdt_serving_recovery", r_start, r_end,
                    why=why, from_backend=from_backend, slot=slot.idx,
                )
        self._trace.record(
            "tdt_serving_recovery", r_start, r_end,
            why=why, from_backend=from_backend, in_flight=len(occupied),
        )

    # ------------------------------------------------------- half-open probe
    def _maybe_probe(self) -> bool:
        """When running degraded and a breaker's backoff has elapsed, probe
        the preferred backend with one sandboxed join and decode step.
        Success closes the breaker and restores live routing; failure
        re-opens it with doubled backoff. Either way the serving cache is
        untouched — the probe runs on a throwaway 1-slot pool."""
        if self.engine.backend == self._preferred_backend:
            return False
        if resilience.dead_ranks():
            # Membership is still short: the fused path cannot be healthy
            # until the dead rank is revived (epoch bump), so don't burn
            # the breaker's backoff on a probe that must fail.
            return False
        due = resilience.probe_due()
        if not due:
            return False
        self._guarded(self._land_in_flight, what="decode chunk")
        resilience.begin_probe(due)
        ok, err = True, ""
        with self._trace.span(
            "tdt_serving_probe", features=",".join(due),
            to_backend=self._preferred_backend,
        ):
            try:
                with resilience.probe_scope(due):
                    self.engine.rebuild(self._preferred_backend)
                    resilience.chaos_check("probe")
                    self._probe_dispatch()
            except Exception as e:  # a probe must never kill the loop
                ok, err = False, f"{type(e).__name__}: {e}"
        resilience.end_probe(due, ok=ok)
        if ok:
            self._restore_streams()
        else:
            telemetry.emit("serving_probe_failed", features=",".join(due), error=err)
            # Back to the degraded programs; the serving cache was never
            # touched, so live streams resume exactly where they were.
            self.engine.rebuild("xla")
        return True

    def _probe_dispatch(self) -> None:
        """What a join and a chunk dispatch, once, on a throwaway one-slot
        pool of one chain: the probe compiles and runs the programs that
        serve (``chunk_fn``, ``paged_scatter_prefill``,
        ``decode_chunk_paged``) before live streams are moved onto them.
        Its sampling key is its own — the serving key stream is not the
        probe's to advance."""
        eng = self.engine
        ids = np.asarray([[1, 2, 3]], np.int32)
        p_len = ids.shape[1]
        chain = -(-(p_len + 1) // self.block_size)  # the prompt + one step
        sandbox = eng.alloc_paged(
            1, block_size=self.block_size, num_blocks=chain + 1,
            quant=self.kv_quant,
        )
        row = np.zeros((sandbox.max_blocks,), np.int32)
        row[:chain] = np.arange(1, chain + 1)
        kbuf, vbuf = eng.paged_kbuf_zeros(p_len)
        logits, kbuf, vbuf, state = eng.prefill_chunk_state(
            kbuf, vbuf, jnp.asarray(ids), 0, p_len - 1, eng.prompt_state()
        )
        sandbox = eng.complete_paged_prefill(sandbox, kbuf, vbuf, row, 0, 0, state)
        sandbox = dataclasses.replace(
            sandbox, tables=jnp.asarray(row[None]),
            lengths=jnp.asarray([p_len], jnp.int32),
        )
        token0 = eng.sample_logits(logits, jax.random.PRNGKey(0))
        out = eng.decode_steps_paged(
            sandbox, token0, jnp.asarray([1], jnp.int32), 1
        )
        jax.block_until_ready(out[0])

    def _restore_streams(self) -> None:
        """Re-resolve routing onto the (just-probed) preferred backend for
        LIVE traffic without dropping a stream: fresh pool +
        re-prefill from history — the recovery machinery pointed back at
        the fused path."""
        self._land_in_flight()
        occupied = self.scheduler.occupied_slots()
        to_backend = self.engine.backend
        telemetry.inc("tdt_serving_restores_total", to_backend=to_backend)
        telemetry.emit(
            "serving_restore", to_backend=to_backend,
            in_flight=len(occupied), queued=self.scheduler.queue_depth(),
        )
        r_start = tracing.now_s()
        self.cache = self._fresh_cache()
        self._reprefill_occupied(occupied)
        r_end = tracing.now_s()
        telemetry.observe("tdt_serving_restore_seconds", r_end - r_start)
        for slot in occupied:
            if slot.request is not None:
                slot.request.trace.record(
                    "tdt_serving_restore", r_start, r_end,
                    to_backend=to_backend, slot=slot.idx,
                )
        self._trace.record(
            "tdt_serving_restore", r_start, r_end,
            to_backend=to_backend, in_flight=len(occupied),
        )

    # --------------------------------------------------------- crash recovery
    def recover(self, journal=None, *, on_token=None, on_finish=None) -> list:
        """Replay a write-ahead journal into the queue (call BEFORE
        :meth:`run`). Terminal requests are skipped idempotently; queued
        ones re-enter the pending queue; in-flight ones re-enter with their
        journaled token history pre-seeded, so the join sweep re-prefills
        them from ``prompt + tokens`` and decoding resumes exactly where
        the journal left off — journaled tokens are NOT re-streamed to the
        new callbacks. Deadline budgets restart at recovery time (the
        original server's clock died with it).

        ``journal`` defaults to this server's own attached journal; a path
        or :class:`~triton_dist_tpu.serving.journal.RequestJournal` handle
        replays someone else's. Replaying twice is a no-op (per-process id
        guard on top of the journal's positional idempotence). Returns the
        restored request handles in ``req_id`` (original FCFS) order."""
        from triton_dist_tpu.serving.journal import RequestJournal

        self._guarded(self._land_in_flight, what="decode chunk")
        if journal is None:
            journal = self._journal
        if journal is None:
            return []
        if isinstance(journal, (str, os.PathLike)):
            records = RequestJournal.read(journal)
            path = os.fspath(journal)
        else:
            records = journal.read_records()
            path = journal.path
        state = RequestJournal.replay(records)
        restored = []
        now = self._now()
        t0 = time.monotonic()
        for rid in sorted(state):
            rr = state[rid]
            if rr.terminal:
                telemetry.inc(
                    "tdt_serving_journal_replayed_total",
                    outcome="skipped_terminal",
                )
                continue
            if rid in self._recovered_ids:
                telemetry.inc(
                    "tdt_serving_journal_replayed_total",
                    outcome="skipped_duplicate",
                )
                continue
            if (
                len(rr.prompt) + rr.max_new > self.engine.max_len
                or not self.kv_ledger.can_ever_fit(len(rr.prompt), rr.max_new)
            ):
                # The journal came from a server with a bigger KV row (or
                # block pool); resuming here would abort mid-decode. Drop
                # loudly.
                telemetry.inc(
                    "tdt_serving_journal_replayed_total",
                    outcome="dropped_kv_budget",
                )
                continue
            req = Request(
                req_id=rid, prompt=list(rr.prompt), max_new=rr.max_new,
                arrival_time_s=0.0, on_token=on_token, on_finish=on_finish,
                priority=rr.priority,
                tenant=rr.tenant, weight=rr.weight,
                ttft_deadline_s=rr.ttft_deadline_s,
                deadline_s=rr.deadline_s,
                tokens=list(rr.tokens),
            )
            req.submitted_at = now
            req.trace = tracing.start_trace(
                "tdt_serving_request", req_id=rid,
                prompt_len=len(rr.prompt), max_new=rr.max_new,
                recovered=True, journaled_tokens=len(rr.tokens),
            )
            self.scheduler.restore(req)
            self._recovered_ids.add(rid)
            restored.append(req)
            telemetry.inc(
                "tdt_serving_journal_replayed_total",
                outcome="reprefill" if rr.tokens else "requeued",
            )
        telemetry.observe(
            "tdt_serving_journal_replay_seconds", time.monotonic() - t0
        )
        telemetry.emit(
            "serving_journal_replay", path=path, records=len(records),
            restored=len(restored),
            terminal=sum(1 for rr in state.values() if rr.terminal),
        )
        return restored

    # ------------------------------------------------------ graceful shutdown
    def shutdown(self, drain: bool = True, timeout_s: float | None = None) -> None:
        """Stop serving cleanly: reject new joins (``shutting_down``),
        drain admitted work (or leave it journaled when ``drain=False`` /
        the ``TDT_DRAIN_TIMEOUT_S`` budget lapses — either way the journal
        holds everything :meth:`recover` needs), flush+close the journal,
        dump telemetry (``TDT_TELEMETRY_DUMP``), and stop the introspect
        endpoint. Idempotent."""
        if self._shutdown:
            return
        self._shutdown = True
        self.scheduler.shutting_down = True
        # What is in flight is streamed and journaled whether or not the
        # rest drains.
        self._guarded(lambda: self._land_in_flight("drain"), what="decode chunk")
        t0 = time.monotonic()
        if timeout_s is None:
            timeout_s = get_float_env("TDT_DRAIN_TIMEOUT_S", 0.0)
        telemetry.emit(
            "serving_shutdown", drain=drain,
            in_flight=self.scheduler.occupancy(),
            queued=self.scheduler.queue_depth(),
        )
        if drain:
            while self.scheduler.occupancy() or self.scheduler.queue_depth():
                if timeout_s > 0 and time.monotonic() - t0 > timeout_s:
                    telemetry.emit(
                        "serving_drain_timeout",
                        in_flight=self.scheduler.occupancy(),
                        queued=self.scheduler.queue_depth(),
                    )
                    break
                if not self.step():
                    time.sleep(0.005)
        if self._journal is not None:
            self._journal.flush()
            self._journal.close()
        drain_s = time.monotonic() - t0
        telemetry.observe("tdt_serving_drain_seconds", drain_s)
        dump_path = os.environ.get("TDT_TELEMETRY_DUMP", "").strip()
        if dump_path:
            try:
                telemetry.dump(dump_path)
            except Exception:  # shutdown must not die on a bad dump path
                telemetry.inc("tdt_serving_callback_errors_total", kind="dump")
        from triton_dist_tpu.runtime import introspect

        introspect.set_health_provider(None)
        introspect.set_requests_provider(None)
        introspect.register_json_route("/slo", None)
        if self._introspect is not None:
            self._introspect.stop()
            self._introspect = None
        self._trace.finish(status="shutdown", drained=drain)
        telemetry.emit(
            "serving_shutdown_done", drain_s=round(drain_s, 3),
            in_flight=self.scheduler.occupancy(),
            queued=self.scheduler.queue_depth(),
        )

    def install_signal_handlers(self, signums=None) -> None:
        """Route SIGTERM/SIGINT into a graceful drain: the handler only
        sets a flag; :meth:`run` notices it at the next loop iteration and
        calls :meth:`shutdown(drain=True)` from the serving thread (signal
        handlers must not run device work). Main-thread only."""
        import signal as _signal

        if signums is None:
            signums = (_signal.SIGTERM, _signal.SIGINT)
        for s in signums:
            _signal.signal(s, self._on_signal)

    def _on_signal(self, signum, frame) -> None:
        self._shutdown_requested = True
