"""Request scheduler: admission control + slot-based continuous batching.

Iteration-level (Orca-style, Yu et al. OSDI'22) scheduling over a FIXED
batch of B slots: requests join the running batch whenever a slot frees up
instead of waiting for the whole batch to drain, and short requests stop
consuming decode steps the moment they finish. The KV side is vLLM's
block management (Kwon et al., SOSP'23) under fixed shapes: a global pool
of blocks, a block table a slot that travels to the device as data, and a
:class:`KVLedger` here on the host that owns the free list, the refcounts
and the prefix index, so admission is a reservation of the request's whole
chain.

State machines::

    slot     FREE → PREFILL → DECODE → DONE → FREE       (join/evict cycle)
    request  QUEUED → RUNNING → DONE   |   REJECTED | CANCELLED

Scheduling policy: weighted-fair across tenants, FCFS within a tenant.
Every request carries a tenant id and a QoS weight; ``submit`` stamps a
virtual finish tag (start-time = max(queue virtual clock, tenant's last
tag); finish = start + ``max_new / weight``) and
:meth:`Scheduler.join_free_slots` walks the pending queue in tag order —
with a single tenant the tags are monotone in submission order, so the
walk degrades to exactly the old FCFS. A request whose (synthetic)
arrival lies in the future never blocks one behind it that has already
arrived.

Admission contract (KV-budget aware). The server always attaches a
:class:`KVLedger`, and the budget is BLOCKS:
``blocks_needed(prompt, max_new) = ceil((len(prompt)+max_new)/block_size)``
must fit the pool outright (else ``kv_budget_hard`` at submit — it can
NEVER fit), and at join time the ledger must actually reserve the chain —
prefix-index eviction runs first, and a request that would fit after
in-flight frees is *parked* (``kv_wait``), not rejected, and is exempt
from queue-time deadline expiry while parked (it is one eviction away
from admission, not doomed). A full bounded queue still rejects with
``reason="queue_full"``. Either way a running request can NEVER run out
of cache mid-decode. A bare ``Scheduler`` built without a ledger, as its
unit tests build it, checks ``len(prompt) + max_new <= max_len`` alone and
rejects with ``reason="kv_budget"`` (making the ledger mandatory is
ROADMAP D6's remainder).

SLO guardrails (all optional, all enforced BEFORE a slot is spent):

* **Deadlines** — per-request TTFT and total budgets (seconds from
  effective arrival; ``TDT_DEADLINE_TTFT_S`` / ``TDT_DEADLINE_TOTAL_S``
  defaults). A non-positive deadline rejects at submit
  (``shed_deadline``); a queued request whose budget lapses before a slot
  frees is expired by the sweep in :meth:`join_free_slots` — a doomed
  request never occupies a slot. Mid-decode total-deadline truncation is
  the server's half (``InferenceServer._reap_slots``).
* **Shedding** — an EWMA decode-capacity estimate (fed by the server via
  :meth:`note_decode_rate`) projects the queue wait at submit time; when
  the projection blows the request's TTFT deadline or the global
  ``TDT_SHED_WAIT_S`` budget, requests at priority >= ``TDT_SHED_PRIORITY``
  are rejected early (``shed_overload``). Lower numbers are MORE
  important; priority-0 traffic is never shed by default.
* **Cancellation** — :meth:`cancel` finalizes a queued request immediately
  and flags a running one; the server frees the slot at the next chunk
  boundary. Terminal requests are never re-finalized (no double-free).

The scheduler is pure host-side bookkeeping — it never touches jax. The
device work (chunked prefill, masked decode chunks) lives in
``models/engine.py``; the loop that drives both is ``InferenceServer``.
Telemetry: ``tdt_serving_queue_depth`` / ``tdt_serving_slot_occupancy``
gauges track every transition, counters are listed in ``docs/serving.md``.
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import threading
import time
from typing import Callable

from triton_dist_tpu.models.kv_cache import NULL_BLOCK, BlockAllocator
from triton_dist_tpu.runtime import slo, telemetry, tracing
from triton_dist_tpu.runtime.utils import get_float_env, get_int_env

#: EWMA smoothing for the decode-capacity estimate: heavy enough to ride
#: out chunk-to-chunk jitter, light enough to track a recovery rebuild.
EWMA_ALPHA = 0.3


def _env_deadline(name: str) -> float | None:
    v = get_float_env(name, 0.0)  # env-knob-ok: forwards documented TDT_DEADLINE_* literals
    return v if v > 0 else None


class SlotState(enum.Enum):
    FREE = "free"
    PREFILL = "prefill"
    DECODE = "decode"
    DONE = "done"


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    REJECTED = "rejected"
    CANCELLED = "cancelled"


@dataclasses.dataclass
class Request:
    """One served generation request (host-side handle).

    ``tokens`` accumulates every streamed token in order — it is the
    request's durable history, and the recovery path re-prefills a slot
    from ``prompt + tokens[:-1]`` (see ``InferenceServer._prefill_slot``),
    so completed streams survive an engine rebuild with zero drops or
    duplicates."""

    req_id: int
    prompt: list[int]
    max_new: int
    #: Offered-load arrival time, seconds relative to the server clock's
    #: zero. The scheduler will not join the request before it "arrives".
    arrival_time_s: float = 0.0
    #: ``on_token(request, token, index)`` — called once per streamed token.
    on_token: Callable[["Request", int, int], None] | None = None
    #: ``on_finish(request)`` — called once when the stream completes.
    on_finish: Callable[["Request"], None] | None = None
    #: Shedding class: lower is MORE important (0 = never shed by default).
    priority: int = 1
    #: Tenant identity (multi-tenant QoS): scopes prefix-cache reuse and
    #: weighted-fair queueing; carried end-to-end through wire bodies and
    #: journal records so it survives migration byte-identically.
    tenant: str = "default"
    #: Weighted-fair-queueing weight (higher = larger share of admissions).
    weight: float = 1.0
    #: WFQ virtual finish tag, assigned at submit/restore — the join walk
    #: admits pending requests in tag order (pure FCFS with one tenant).
    wfq_tag: float = 0.0
    #: SLO budgets, seconds from effective arrival (None = no bound).
    ttft_deadline_s: float | None = None
    deadline_s: float | None = None

    state: RequestState = RequestState.QUEUED
    reject_reason: str | None = None
    #: How the stream ended: "ok" | "cancelled" | "deadline" (None while
    #: running or when rejected before any slot was spent).
    finish_reason: str | None = None
    #: Set by :meth:`Scheduler.cancel` on a RUNNING request; the server
    #: honors it at the next chunk boundary.
    cancel_requested: bool = False
    #: Paged-KV reservation (ledger mode only). ``kv_blocks`` is the
    #: physical block chain backing this request (reserved at join time,
    #: released at finish); the first ``kv_shared`` of them are borrowed
    #: from the prefix index (donor-written, never written by this
    #: request); ``kv_wait`` marks a request parked for BLOCKS rather than
    #: for a slot — exempt from queue-time expiry while parked.
    kv_blocks: list[int] = dataclasses.field(default_factory=list)
    kv_shared: int = 0
    kv_wait: bool = False
    #: Disaggregated serving (``docs/disagg.md``). ``prefill_only``: this
    #: replica runs prefill + the first token, then parks the KV chain for
    #: handoff instead of decoding. ``kv_import``: an unpacked handoff
    #: payload (``disagg.kv_transfer``) to scatter into this request's
    #: chain in place of a local prefill; consumed (set back to None) the
    #: first time it is applied, so a post-crash re-prefill falls back to
    #: deriving KV from the token history.
    prefill_only: bool = False
    kv_import: dict | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    tokens: list[int] = dataclasses.field(default_factory=list)
    #: Per-request trace handle (``runtime.tracing``). ``submit`` opens it;
    #: the server closes it at completion. Defaults to the no-op handle so
    #: directly-constructed Requests stay safe to serve.
    trace: tracing.Trace = dataclasses.field(
        default=tracing.NOOP_TRACE, repr=False, compare=False
    )
    submitted_at: float = 0.0
    arrived_at: float = 0.0
    admitted_at: float | None = None  # its newest join into a slot
    first_token_at: float | None = None
    finished_at: float | None = None

    @property
    def done(self) -> bool:
        return self.state is RequestState.DONE

    @property
    def ttft_s(self) -> float | None:
        """Wall seconds from (effective) arrival to the first streamed token."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.arrived_at

    @property
    def tpot_s(self) -> float | None:
        """Mean wall seconds per token after the first (None until finished
        or when only one token was generated)."""
        if self.finished_at is None or self.first_token_at is None:
            return None
        steps = len(self.tokens) - 1
        if steps <= 0:
            return None
        return (self.finished_at - self.first_token_at) / steps


@dataclasses.dataclass
class Slot:
    """One fixed batch position: its state and current tenant."""

    idx: int
    state: SlotState = SlotState.FREE
    request: Request | None = None


class _PrefixNode:
    """One radix-trie node: an edge of ``block_size`` prompt tokens mapping
    to the physical block that holds their KV rows."""

    __slots__ = ("children", "block", "last_used", "parent", "key")

    def __init__(self, block: int, parent: "_PrefixNode | None" = None, key: tuple = ()):
        self.children: dict[tuple, "_PrefixNode"] = {}
        self.block = int(block)
        self.last_used = 0
        self.parent = parent  # None for a tenant's root
        self.key = key  # this node's edge in ``parent.children``


class PrefixIndex:
    """Radix trie over full prompt-token blocks (RadixAttention-style,
    Zheng et al.), one trie PER TENANT. Each indexed node pins its block
    with one allocator ref of its own, so a donor finishing (and freeing
    its chain) cannot recycle a block that a later prompt may still match.
    Eviction drops least-recently-used LEAVES only — an interior node's
    block backs every chain below it. LRU uses a logical clock (ticked per
    lookup/register), not wall time, so behavior is deterministic under
    test.

    Tenant isolation: lookups and placement probes (:meth:`match_blocks`)
    only ever walk the requesting tenant's trie — tenant A can neither
    reuse nor *observe* (via placement timing) tenant B's warm prefixes.
    ``TDT_TENANT_PREFIX_QUOTA`` caps each tenant's indexed blocks; under
    pool pressure eviction prefers (1) the requester's own leaves, then
    (2) leaves of tenants over their quota, then (3) the global LRU leaf.
    The isolation invariant is therefore: a tenant at or under its quota
    never loses a warm prefix to another tenant's demand unless the pool
    cannot otherwise satisfy an admission (liveness beats strict isolation
    — a request must never deadlock on blocks the index is hoarding)."""

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = int(block_size)
        self._roots: dict[str, _PrefixNode] = {}
        self._clock = 0
        self.num_blocks_indexed = 0
        #: Indexed-block count per tenant (drives quota + gauges).
        self._tenant_blocks: dict[str, int] = {}
        #: Max indexed blocks per tenant (0 = unlimited).
        self.tenant_quota = get_int_env("TDT_TENANT_PREFIX_QUOTA", 0)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _root_for(self, tenant: str) -> _PrefixNode:
        node = self._roots.get(tenant)
        if node is None:
            node = self._roots[tenant] = _PrefixNode(-1)
        return node

    def _note_blocks(self, tenant: str, delta: int) -> None:
        n = self._tenant_blocks.get(tenant, 0) + delta
        self._tenant_blocks[tenant] = n
        telemetry.set_gauge("tdt_tenant_prefix_blocks", float(n), tenant=tenant)

    def tenant_blocks(self, tenant: str) -> int:
        """Blocks currently indexed for ``tenant``."""
        return self._tenant_blocks.get(tenant, 0)

    def lookup(self, prompt: list[int], tenant: str = "default") -> list[int]:
        """Longest indexed chain of full prompt blocks, root-down, WITHIN
        ``tenant``'s trie only. Touches LRU stamps; takes NO refs — the
        caller pins before any eviction."""
        bs = self.block_size
        node = self._roots.get(tenant)
        chain: list[int] = []
        if node is None:
            return chain
        t = self._tick()
        for i in range(len(prompt) // bs):
            child = node.children.get(tuple(prompt[i * bs:(i + 1) * bs]))
            if child is None:
                break
            child.last_used = t
            chain.append(child.block)
            node = child
        return chain

    def match_blocks(self, prompt: list[int], tenant: str = "default") -> int:
        """Longest indexed full-block prefix of ``prompt`` within
        ``tenant``'s trie, WITHOUT touching LRU stamps or taking refs — the
        fleet placement-hint probe. Tenant-scoped so placement affinity can
        never leak one tenant's cached prompts to another through routing
        timing. Safe to call from an endpoint thread: the walk only does
        dict lookups on the trie (concurrent registration may make the
        answer one block stale, which a *hint* can tolerate)."""
        bs = self.block_size
        node = self._roots.get(tenant)
        n = 0
        if node is None:
            return n
        for i in range(len(prompt) // bs):
            child = node.children.get(tuple(prompt[i * bs:(i + 1) * bs]))
            if child is None:
                break
            n += 1
            node = child
        return n

    def register(self, prompt: list[int], blocks: list[int],
                 tenant: str = "default") -> int:
        """Index a finished prefill's FULL prompt blocks (``len(prompt) //
        block_size`` of them — decode writes only ever land past that
        boundary, so indexed content is immutable) under ``tenant``'s trie.
        Existing nodes win on collision (their content is equivalent); each
        new node takes one allocator ref. A tenant at its quota recycles
        its own LRU leaves to make room; if none predate this registration,
        indexing stops (never detach the chain being registered). Returns
        the number of newly indexed blocks."""
        bs = self.block_size
        node = self._root_for(tenant)
        t = self._tick()
        added = 0
        for i in range(min(len(prompt) // bs, len(blocks))):
            key = tuple(prompt[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                blk = int(blocks[i])
                if blk == NULL_BLOCK:
                    break
                if self.tenant_quota > 0 and not self._make_quota_room(
                    tenant, exclude_tick=t
                ):
                    break
                self.allocator.incref([blk])
                child = _PrefixNode(blk, node, key)
                node.children[key] = child
                self.num_blocks_indexed += 1
                self._note_blocks(tenant, +1)
                added += 1
            child.last_used = t
            node = child
        return added

    def _make_quota_room(self, tenant: str, exclude_tick: int) -> bool:
        """Recycle ``tenant``'s own LRU leaves until one more block fits
        its quota. Leaves stamped at ``exclude_tick`` (the in-progress
        registration's own path) are never victims."""
        while self._tenant_blocks.get(tenant, 0) >= self.tenant_quota:
            if not self._drop_leaf(
                [tenant], cause="self", exclude_tick=exclude_tick
            ):
                return False
        return True

    def freeable_blocks(self) -> int:
        """Blocks that evicting the whole index would return to the pool:
        the indexed ones no running slot holds a ref on."""
        n = 0
        stack = list(self._roots.values())
        while stack:
            for child in stack.pop().children.values():
                stack.append(child)
                n += self.allocator.refcount(child.block) == 1
        return n

    def evict(self, need_free: int, tenant: str | None = None) -> int:
        """Drop LRU leaves until the allocator has ``need_free`` free blocks
        or the index is empty, in isolation-preserving preference order:
        the requesting ``tenant``'s own leaves first, then leaves of
        tenants over their quota, then the global LRU leaf (pool liveness
        trumps isolation as the last resort). Dropping a leaf only frees
        its block when no running slot still holds a ref — the loop keeps
        going either way. Returns the number of index entries dropped."""
        dropped = 0
        short = lambda: self.allocator.num_free < need_free
        if tenant is not None:
            while short():
                n = self._drop_leaf([tenant], cause="self", more=short)
                if not n:
                    break
                dropped += n
        if self.tenant_quota > 0:
            while short():
                over = [
                    t for t, n in self._tenant_blocks.items()
                    if n > self.tenant_quota
                ]
                n = self._drop_leaf(over, cause="over_quota", more=short) if over else 0
                if not n:
                    break
                dropped += n
        while short():
            n = self._drop_leaf(None, cause="pressure", more=short)
            if not n:
                break
            dropped += n
        return dropped

    def _drop_leaf(self, tenants: list[str] | None, cause: str,
                   exclude_tick: int | None = None, more=None) -> int:
        """Remove the LRU leaf among ``tenants`` (None = all); 0 when no
        eligible leaf exists. While ``more()`` holds, go on up the chain:
        an ancestor that the removal leaves bare and that carries the same
        stamp is the next LRU leaf (one lookup or registration stamps a
        whole path with its tick, and an ancestor is never older than what
        hangs below it), so the order of eviction is what one walk of the
        trie a block gave, at one walk a chain: a long prompt's thousand
        blocks cost a thousand walks before. Returns the number removed."""
        lru = self._lru_leaf(tenants, exclude_tick=exclude_tick)
        if lru is None:
            return 0
        tname, parent, key, node = lru
        stamp = node.last_used
        removed = 0
        while True:
            del parent.children[key]
            self.num_blocks_indexed -= 1
            self._note_blocks(tname, -1)
            self.allocator.free([node.block])
            telemetry.inc(
                "tdt_tenant_prefix_evictions_total", tenant=tname, cause=cause
            )
            removed += 1
            node = parent
            if (more is None or not more() or node.parent is None
                    or node.children or node.last_used != stamp):
                return removed
            parent, key = node.parent, node.key

    def _lru_leaf(
        self, tenants: list[str] | None = None,
        exclude_tick: int | None = None,
    ) -> tuple[str, "_PrefixNode", tuple, "_PrefixNode"] | None:
        best = None
        roots = (
            self._roots.items() if tenants is None
            else [(t, self._roots[t]) for t in tenants if t in self._roots]
        )
        for tname, root in roots:
            stack = [root]
            while stack:
                node = stack.pop()
                for key, child in node.children.items():
                    if child.children:
                        stack.append(child)
                    elif exclude_tick is not None and (
                        child.last_used >= exclude_tick
                    ):
                        continue
                    elif best is None or child.last_used < best[3].last_used:
                        best = (tname, node, key, child)
        return best

    def clear(self) -> None:
        """Drop every index entry (and its ref). Recovery-path reset."""
        for tenant, root in self._roots.items():
            stack = [root]
            while stack:
                node = stack.pop()
                for child in node.children.values():
                    stack.append(child)
                    self.allocator.free([child.block])
                node.children.clear()
            if self._tenant_blocks.get(tenant):
                self._note_blocks(tenant, -self._tenant_blocks[tenant])
        self.num_blocks_indexed = 0


class KVLedger:
    """Host-side paged-KV bookkeeping: block-budget admission, prefix
    reuse, and copy-on-write — owns the :class:`BlockAllocator` and the
    :class:`PrefixIndex` over it.

    ``reserve`` runs INSIDE the scheduler's join walk so the allocation is
    atomic with admission (no stale can-admit answer when several slots
    join in one sweep): it pins any prefix hit first, evicts LRU index
    leaves if the pool is short, then allocates the fresh tail
    all-or-nothing. The shared prefix is capped at ``(len(prompt)-1) //
    block_size`` blocks so prefill always computes at least the last
    prompt row (its logits seed decode)."""

    def __init__(self, num_blocks: int, block_size: int, *,
                 prefix_reuse: bool = True, bytes_per_block: int = 0):
        self.allocator = BlockAllocator(num_blocks)
        self.block_size = int(block_size)
        self.prefix_reuse = bool(prefix_reuse)
        #: Bytes the slots' state takes beside the pool, whatever the load.
        self.slot_state_bytes = 0
        #: Real HBM bytes one pool block costs (payloads + scale pools,
        #: ``PagedKVCache.bytes_per_block``). The server teaches the ledger
        #: this after allocating the device pool — budget math and the
        #: ``/requests`` view then report bytes, not logical block counts,
        #: so a quantized pool's smaller per-block cost is visible to
        #: admission and federation instead of being a dtype fiction.
        self.bytes_per_block = int(bytes_per_block)
        self.prefix = PrefixIndex(self.allocator, self.block_size)

    def set_bytes_per_block(self, nbytes: int) -> None:
        self.bytes_per_block = int(nbytes)

    def set_slot_state_bytes(self, nbytes: int) -> None:
        self.slot_state_bytes = int(nbytes)

    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        return -(-(int(prompt_len) + int(max_new)) // self.block_size)

    def can_ever_fit(self, prompt_len: int, max_new: int) -> bool:
        """Could the chain fit an EMPTY pool? (Block 0 is the null block.)"""
        need = self.blocks_needed(prompt_len, max_new)
        return need <= self.allocator.num_blocks - 1

    def reserve(self, req: Request) -> bool:
        """Reserve ``req``'s full block chain (shared prefix + fresh tail).
        On success ``req.kv_blocks``/``req.kv_shared`` are set and True is
        returned; on False nothing is held (park the request, do not
        reject — in-flight frees will eventually satisfy it). The prefix
        index is evicted only where that makes the room: one stripped for
        nothing would cost the prefix hits that come after."""
        bs = self.block_size
        need_total = self.blocks_needed(len(req.prompt), req.max_new)
        shared: list[int] = []
        if self.prefix_reuse:
            chain = self.prefix.lookup(req.prompt, req.tenant)
            shared = chain[: (len(req.prompt) - 1) // bs]
        if shared:
            # Pin BEFORE eviction so evicting a leaf on our own chain
            # cannot recycle a block we are about to borrow.
            self.allocator.incref(shared)
        fresh_need = need_total - len(shared)
        if self.allocator.num_free < fresh_need:
            if self.allocator.num_free + self.prefix.freeable_blocks() < fresh_need:
                if shared:
                    self.allocator.free(shared)
                return False
            dropped = self.prefix.evict(fresh_need, tenant=req.tenant)
            if dropped:
                telemetry.inc("tdt_kv_evictions_total", float(dropped))
        fresh = self.allocator.alloc(fresh_need) if fresh_need > 0 else []
        if fresh is None:
            if shared:
                self.allocator.free(shared)
            return False
        if shared:
            telemetry.inc("tdt_kv_prefix_hits_total")
            telemetry.inc(
                "tdt_kv_prefix_blocks_reused_total", float(len(shared))
            )
        req.kv_blocks = shared + fresh
        req.kv_shared = len(shared)
        return True

    def release(self, req: Request) -> None:
        """Return ``req``'s chain (one ref per block — shared blocks stay
        alive under the index's / other slots' refs). Idempotent."""
        if req.kv_blocks:
            self.allocator.free(req.kv_blocks)
        req.kv_blocks = []
        req.kv_shared = 0

    def register_prefix(self, req: Request) -> int:
        """Index ``req``'s full prompt blocks after its prefill completes
        (content now valid — both the donor-written shared head and the
        freshly prefilled tail)."""
        if not self.prefix_reuse:
            return 0
        return self.prefix.register(req.prompt, req.kv_blocks, req.tenant)

    def make_writable(self, req: Request, block_idx: int) -> tuple[int, bool]:
        """Copy-on-write guard: ensure chain position ``block_idx`` is
        exclusively owned before a write. Structurally the serving path
        never writes a shared block (indexing stops at full prompt blocks,
        decode writes past them), so this is a safety net; a copy updates
        the chain in place and the caller must re-push the device table."""
        blk, copied = self.allocator.ensure_exclusive(req.kv_blocks[block_idx])
        if copied:
            req.kv_blocks[block_idx] = blk
            telemetry.inc("tdt_kv_cow_copies_total")
        return blk, copied

    def stats(self) -> dict:
        a = self.allocator
        out = {
            "blocks_total": a.num_blocks - 1,
            "blocks_free": a.num_free,
            "blocks_used": a.num_used,
            "blocks_shared": a.num_shared,
            "blocks_indexed": self.prefix.num_blocks_indexed,
            "block_size": self.block_size,
        }
        if self.bytes_per_block:
            out["bytes_per_block"] = self.bytes_per_block
            out["bytes_used"] = a.num_used * self.bytes_per_block
            out["bytes_free"] = a.num_free * self.bytes_per_block
        if self.slot_state_bytes:
            out["bytes_slot_state"] = self.slot_state_bytes
        return out

    def reset(self) -> None:
        """Drop every reservation and index entry (engine-rebuild path:
        the device pool is recreated from scratch, so host bookkeeping
        restarts empty)."""
        self.allocator = BlockAllocator(self.allocator.num_blocks)
        self.prefix = PrefixIndex(self.allocator, self.block_size)


class Scheduler:
    """FCFS admission + join-on-free-slot over ``num_slots`` fixed slots.

    Thread-safe on the submit side (a server thread may accept requests
    while the serving loop runs); the slot-transition methods are meant to
    be called from the single serving loop."""

    def __init__(self, num_slots: int, max_len: int, queue_limit: int = 0,
                 shed_wait_s: float | None = None,
                 shed_priority: int | None = None,
                 kv_ledger: KVLedger | None = None):
        assert num_slots >= 1 and max_len >= 2
        self.num_slots = num_slots
        self.max_len = max_len
        #: Block ledger (None only in unit tests of the bare scheduler: a
        #: ``max_len`` budget check then). When set, ``join_free_slots``
        #: reserves each request's block chain atomically with admission.
        self.kv_ledger = kv_ledger
        self.queue_limit = queue_limit  # 0 = unbounded
        #: Global projected-wait shed budget, seconds (0 = only per-request
        #: TTFT deadlines trigger overload shedding).
        self.shed_wait_s = (
            get_float_env("TDT_SHED_WAIT_S", 0.0)
            if shed_wait_s is None else float(shed_wait_s)
        )
        #: Minimum priority class eligible for overload shedding.
        self.shed_priority = (
            get_int_env("TDT_SHED_PRIORITY", 1)
            if shed_priority is None else int(shed_priority)
        )
        #: /healthz stays not-ready this long after the last shed.
        self.shed_health_s = get_float_env("TDT_SHED_HEALTH_S", 5.0)
        self.slots = [Slot(idx=i) for i in range(num_slots)]
        self._pending: collections.deque[Request] = collections.deque()
        self._next_id = 0
        self._lock = threading.Lock()
        #: WFQ virtual time: the queue clock advances to each admitted
        #: request's tag; per-tenant last-finish tags serialize one
        #: tenant's requests while letting weights split the clock across
        #: tenants (classic virtual-finish-time fair queueing).
        self._wfq_clock = 0.0
        self._wfq_last: dict[str, float] = {}
        self._ewma_tps = 0.0
        self._last_shed_now_s: float | None = None
        #: Set by ``InferenceServer.shutdown``: every subsequent submit is
        #: rejected with reason "shutting_down" while admitted work drains.
        self.shutting_down = False

    def _new_id(self) -> int:
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            return rid

    # ------------------------------------------------------------- admission
    def submit(self, prompt, max_new: int, arrival_time_s: float = 0.0,
               on_token=None, on_finish=None, now_s: float | None = None,
               priority: int = 1, ttft_deadline_s: float | None = None,
               deadline_s: float | None = None,
               tokens=None,
               trace_ctx: "tracing.SpanContext | None" = None,
               tenant: str = "default", weight: float = 1.0,
               prefill_only: bool = False) -> Request:
        """Admission-check and enqueue one request (FCFS). Returns the
        request handle; a rejected request comes back with
        ``state=REJECTED`` and ``reject_reason`` set — it is NOT queued.
        Deadlines default to ``TDT_DEADLINE_TTFT_S`` / ``TDT_DEADLINE_TOTAL_S``
        when not given (unset/non-positive env = no bound). ``tokens``
        seeds an already-generated history (fleet migration): the request
        enters the queue with it attached, so the join sweep re-prefills
        from ``prompt + tokens`` — seeded before enqueue, never racing the
        serving loop. ``trace_ctx`` is an extracted remote trace context
        (``tracing.extract``): when given, the request trace CONTINUES the
        sender's trace — same trace_id, root span parented under the
        sender's span (the fleet router's placement span), sender's
        sampling decision — instead of opening a fresh local one."""
        prompt = [int(t) for t in prompt]
        req = Request(
            req_id=self._new_id(), prompt=prompt, max_new=int(max_new),
            arrival_time_s=float(arrival_time_s),
            on_token=on_token, on_finish=on_finish,
            priority=int(priority),
            tenant=str(tenant), weight=float(weight),
            prefill_only=bool(prefill_only),
            tokens=[int(t) for t in tokens] if tokens else [],
            ttft_deadline_s=(
                _env_deadline("TDT_DEADLINE_TTFT_S")
                if ttft_deadline_s is None else float(ttft_deadline_s)
            ),
            deadline_s=(
                _env_deadline("TDT_DEADLINE_TOTAL_S")
                if deadline_s is None else float(deadline_s)
            ),
        )
        now = time.monotonic() if now_s is None else now_s
        req.submitted_at = now
        req.trace = tracing.continue_trace(
            trace_ctx, "tdt_serving_request", req_id=req.req_id,
            prompt_len=len(prompt), max_new=req.max_new,
        )
        telemetry.inc("tdt_serving_requests_total")
        telemetry.inc("tdt_tenant_requests_total", tenant=req.tenant)
        if self.shutting_down:
            # Graceful shutdown: admitted work drains, new joins bounce with
            # a distinct reason so clients can retry against another server.
            return self._reject(req, "shutting_down")
        if not prompt or req.max_new < 1:
            return self._reject(req, "empty")
        if self.kv_ledger is not None:
            if len(prompt) + req.max_new > self.max_len or (
                not self.kv_ledger.can_ever_fit(len(prompt), req.max_new)
            ):
                # Hard block budget: the chain exceeds the slot's block
                # table or the ENTIRE pool — no amount of frees or
                # evictions can ever admit it, so reject at submit.
                return self._reject(req, "kv_budget_hard")
        elif len(prompt) + req.max_new > self.max_len:
            # KV budget: the whole generation must fit the slot's fixed
            # max_len KV row — admitting anything larger would guarantee an
            # out-of-cache abort mid-decode.
            return self._reject(req, "kv_budget")
        if (req.ttft_deadline_s is not None and req.ttft_deadline_s <= 0) or (
            req.deadline_s is not None and req.deadline_s <= 0
        ):
            # Already-expired budget: doomed on arrival, never spend a slot.
            return self._shed(req, "shed_deadline", now)
        if req.priority >= self.shed_priority:
            est = self.est_wait_s()
            budgets = [
                b for b in (req.ttft_deadline_s, self.shed_wait_s or None)
                if b is not None
            ]
            if est is not None and budgets and est > min(budgets) and (
                not self._tenant_under_share(req)
            ):
                # The EWMA capacity projection says this request would blow
                # its TTFT budget (or the global shed budget) just queueing.
                # A tenant holding less than its weighted fair share of the
                # backlog is exempt: the wait it would blow is other
                # tenants' work, and the WFQ walk will lift it past them —
                # overload sheds the aggressor's tail, not the victim's.
                return self._shed(req, "shed_overload", now)
        with self._lock:
            if self.queue_limit and len(self._pending) >= self.queue_limit:
                return self._reject(req, "queue_full")
            self._assign_wfq_tag_locked(req)
            self._pending.append(req)
            depth = len(self._pending)
        telemetry.set_gauge("tdt_serving_queue_depth", float(depth))
        return req

    def restore(self, req: Request) -> Request:
        """Re-admit a journal-recovered request (``InferenceServer.recover``).

        Bypasses admission — the request was admitted before the crash —
        and preserves its original ``req_id``, advancing the id counter
        past it so post-recovery submissions never collide. Call in
        ``req_id`` order to preserve the original FCFS order."""
        req.state = RequestState.QUEUED
        with self._lock:
            self._next_id = max(self._next_id, req.req_id + 1)
            self._assign_wfq_tag_locked(req)
            self._pending.append(req)
            depth = len(self._pending)
        telemetry.set_gauge("tdt_serving_queue_depth", float(depth))
        return req

    def requeue(self, req: Request) -> None:
        """Put an admitted request back where its tag places it in the
        queue, ahead of everything submitted after it: a join ahead undone
        because the landing it waited behind failed. Its chain is the
        caller's to release first."""
        req.state = RequestState.QUEUED
        with self._lock:
            self._pending.append(req)
            depth = len(self._pending)
        telemetry.set_gauge("tdt_serving_queue_depth", float(depth))

    def _assign_wfq_tag_locked(self, req: Request) -> None:
        """Stamp ``req``'s WFQ virtual finish tag: start at the later of
        the queue clock and the tenant's previous tag (serializing a
        tenant's own requests), finish ``max_new / weight`` later — heavier
        weights advance a tenant's virtual time more slowly, earning it a
        proportionally larger admission share."""
        start = max(self._wfq_clock, self._wfq_last.get(req.tenant, 0.0))
        tag = start + req.max_new / max(req.weight, 1e-6)
        self._wfq_last[req.tenant] = tag
        req.wfq_tag = tag

    def _tenant_under_share(self, req: Request) -> bool:
        """True when ``req``'s tenant holds strictly less than its
        weight-proportional share of the pending queue. Single-tenant
        queues (and empty queues) return False, so the overload-shed path
        is byte-identical to the pre-tenant scheduler until a second
        tenant shows up."""
        with self._lock:
            if not self._pending:
                return False
            counts: dict[str, int] = {}
            weights: dict[str, float] = {}
            for r in self._pending:
                counts[r.tenant] = counts.get(r.tenant, 0) + 1
                weights[r.tenant] = max(
                    weights.get(r.tenant, 0.0), r.weight
                )
            weights.setdefault(req.tenant, max(req.weight, 1e-6))
            if len(weights) < 2:
                return False
            total_w = sum(weights.values()) or 1.0
            share = len(self._pending) * weights[req.tenant] / total_w
            return counts.get(req.tenant, 0) < share

    def _reject(self, req: Request, reason: str) -> Request:
        req.state = RequestState.REJECTED
        req.reject_reason = reason
        telemetry.inc("tdt_serving_admission_rejects_total", reason=reason)
        telemetry.emit("serving_reject", req_id=req.req_id, reason=reason)
        slo.record_reject(req, reason)
        req.trace.finish(status="rejected", reason=reason)
        return req

    def _shed(self, req: Request, reason: str, now_s: float) -> Request:
        self._last_shed_now_s = now_s
        telemetry.inc(
            "tdt_serving_shed_total", reason=reason, priority=req.priority
        )
        telemetry.inc(
            "tdt_tenant_shed_total", tenant=req.tenant, reason=reason
        )
        return self._reject(req, reason)

    # ---------------------------------------------------- capacity estimate
    def note_decode_rate(self, tokens: int, wall_s: float) -> None:
        """Feed one decode-chunk observation into the EWMA tokens/s
        estimate (called by the server after every chunk dispatch)."""
        if tokens <= 0 or wall_s <= 0:
            return
        inst = tokens / wall_s
        self._ewma_tps = (
            inst if self._ewma_tps <= 0
            else EWMA_ALPHA * inst + (1.0 - EWMA_ALPHA) * self._ewma_tps
        )
        telemetry.set_gauge("tdt_serving_ewma_tokens_per_s", self._ewma_tps)

    def backlog_tokens(self) -> int:
        """Decode tokens committed ahead of a new arrival: every queued
        request's full budget plus the unfinished remainder of each running
        slot (worst-case, since admission guarantees the budget fits)."""
        with self._lock:
            pending = sum(r.max_new for r in self._pending)
        running = sum(
            max(s.request.max_new - len(s.request.tokens), 0)
            for s in self.slots
            if s.request is not None
        )
        return pending + running

    def est_wait_s(self) -> float | None:
        """Projected queue wait from the EWMA capacity (None until the
        first decode chunk has been observed — never shed blind)."""
        if self._ewma_tps <= 0:
            return None
        return self.backlog_tokens() / self._ewma_tps

    def shedding(self, now_s: float) -> bool:
        """True inside the ``TDT_SHED_HEALTH_S`` window after the last shed
        — the /healthz not-ready signal under overload."""
        if self._last_shed_now_s is None:
            return False
        return (now_s - self._last_shed_now_s) <= self.shed_health_s

    # ---------------------------------------------------------- cancellation
    def cancel(self, req_id: int) -> bool:
        """Client cancellation. A QUEUED request is removed and finalized
        here; a RUNNING one is only flagged — the serving loop frees its
        slot at the next chunk boundary (`InferenceServer._reap_slots`).
        Terminal requests return False untouched, so a double cancel (or a
        cancel racing completion) can never double-free a slot."""
        with self._lock:
            req = None
            for i, r in enumerate(self._pending):
                if r.req_id == req_id:
                    req = r
                    del self._pending[i]
                    depth = len(self._pending)
                    break
        if req is not None:
            req.state = RequestState.CANCELLED
            req.finish_reason = "cancelled"
            telemetry.set_gauge("tdt_serving_queue_depth", float(depth))
            telemetry.inc("tdt_serving_cancelled_total", where="queued")
            telemetry.emit("serving_cancel", req_id=req_id, where="queued")
            req.trace.finish(status="cancelled", where="queued")
            if req.on_finish is not None:
                try:
                    req.on_finish(req)
                except Exception:
                    telemetry.inc(
                        "tdt_serving_callback_errors_total", kind="on_finish"
                    )
            return True
        for slot in self.slots:
            r = slot.request
            if r is not None and r.req_id == req_id:
                if r.state is not RequestState.RUNNING:
                    return False
                if not r.cancel_requested:
                    r.cancel_requested = True
                    telemetry.emit("serving_cancel", req_id=req_id, where="running")
                return True
        return False

    # ------------------------------------------------------------------ joins
    def join_free_slots(self, now_s: float, slots=None, fits=None) -> list[Slot]:
        """Admit arrived requests into free slots in WFQ-tag order
        (weighted-fair across tenants, FCFS within one — a single-tenant
        queue's tags are monotone in submission order, so the walk is
        exactly the old FCFS); each admitted request's slot moves
        FREE→PREFILL. Returns the slots to prefill.

        The walk doubles as the queue-time expiry sweep: requests whose
        TTFT/total budget lapsed while queued are rejected here (with
        ``shed_deadline``) and requests cancelled while queued are dropped
        — both run even when no slot is free, so a hopeless request never
        waits for capacity it can no longer use.

        ``slots`` are the slots the walk fills, in that order (the free
        ones by default). With ``fits`` given the walk admits in strict
        order: the first arrived request that ``fits`` refuses or the ledger
        cannot hold ends the admissions, keeps its place and is not parked
        for blocks. That is the server's join ahead, offered the slots a
        decode chunk in flight finishes (their tenants still hold their
        chains): it must not admit past a request that the walk after the
        landing would have admitted."""
        # The scheduler's side of the boundary the server's join crosses.
        with tracing.span_current("tdt_scheduler_join_free_slots"):
            joined: list[Slot] = []
            expired: list[Request] = []
            free = ([s for s in self.slots if s.state is SlotState.FREE]
                    if slots is None else list(slots))
            with self._lock:
                deferred: collections.deque[Request] = collections.deque()
                # Stable sort: ties (same tag — impossible within a tenant,
                # rare across) keep submission order.
                queue = collections.deque(
                    sorted(self._pending, key=lambda r: r.wfq_tag)
                )
                while queue:
                    req = queue.popleft()
                    if req.state is RequestState.CANCELLED:
                        continue  # finalized by cancel() racing this sweep
                    if self._queue_expired(req, now_s):
                        expired.append(req)
                        continue
                    if req.arrival_time_s > now_s or not free:
                        deferred.append(req)  # not offered yet / no capacity —
                        continue              # keep its order
                    if fits is not None:
                        if not (fits(req) and (self.kv_ledger is None
                                               or self.kv_ledger.reserve(req))):
                            free = []  # the strict walk admits no one past it
                            deferred.append(req)
                            continue
                    elif self.kv_ledger is not None and not self.kv_ledger.reserve(req):
                        # Pool dry even after prefix-index eviction. Blocks WILL
                        # free as running slots finish, so this is a deferral
                        # (kv_budget_wait), not a reject; the walk keeps going —
                        # a smaller request behind may still fit (work-conserving
                        # at the cost of strict FCFS under block pressure).
                        if not req.kv_wait:
                            req.kv_wait = True
                            telemetry.inc("tdt_serving_kv_budget_wait_total")
                        deferred.append(req)
                        continue
                    req.kv_wait = False
                    slot = free.pop(0)
                    req.state = RequestState.RUNNING
                    req.arrived_at = max(req.submitted_at, req.arrival_time_s)
                    req.admitted_at = now_s
                    slot.state = SlotState.PREFILL
                    slot.request = req
                    self._wfq_clock = max(self._wfq_clock, req.wfq_tag)
                    joined.append(slot)
                self._pending = deferred
                depth = len(self._pending)
            for req in expired:
                self._expire(req, now_s)  # telemetry + callbacks outside the lock
            if joined or expired:
                telemetry.set_gauge("tdt_serving_queue_depth", float(depth))
                self._occupancy_gauge()
                # Queue wait = effective arrival → admission. Recorded here (not
                # in TTFT) so queueing delay and prefill latency stop conflating.
                # The span is retroactive: anchor its END at the tracing clock's
                # now and stretch back by the wait measured in the caller's
                # clock (both monotonic-derived, so durations transfer).
                t_adm = tracing.now_s()
                for slot in joined:
                    req = slot.request
                    wait = max(0.0, now_s - req.arrived_at)
                    telemetry.observe("tdt_serving_queue_wait_seconds", wait)
                    req.trace.record(
                        "tdt_serving_queue_wait", t_adm - wait, t_adm,
                        slot=slot.idx,
                    )
            return joined

    def _queue_expired(self, req: Request, now_s: float) -> bool:
        """Queue-time deadline check: has an arrived request already waited
        past its TTFT (or total) budget? Not-yet-arrived requests cannot
        expire — their clock has not started."""
        if req.arrival_time_s > now_s:
            return False
        if req.kv_wait:
            # Parked for blocks, not for capacity it can't use: the request
            # is one eviction/free away from admission — expiring it here
            # would shed work the pool is about to be able to serve.
            return False
        waited = now_s - max(req.submitted_at, req.arrival_time_s)
        # A seeded (migration-resumed) request already produced its first
        # token on the donor replica: TTFT was met there, only the total
        # budget still binds here.
        return (
            not req.tokens
            and req.ttft_deadline_s is not None
            and waited > req.ttft_deadline_s
        ) or (req.deadline_s is not None and waited > req.deadline_s)

    def _expire(self, req: Request, now_s: float) -> None:
        waited = now_s - max(req.submitted_at, req.arrival_time_s)
        limit = min(
            b for b in (
                None if req.tokens else req.ttft_deadline_s, req.deadline_s
            ) if b is not None
        )
        telemetry.inc("tdt_serving_deadline_expiries_total", where="queue")
        telemetry.observe(
            "tdt_serving_deadline_overrun_seconds", max(waited - limit, 0.0)
        )
        self._shed(req, "shed_deadline", now_s)
        if req.on_finish is not None:
            try:
                req.on_finish(req)
            except Exception:
                telemetry.inc(
                    "tdt_serving_callback_errors_total", kind="on_finish"
                )

    # ------------------------------------------------------------ transitions
    def start_decode(self, slot: Slot) -> None:
        assert slot.state is SlotState.PREFILL, slot.state
        slot.state = SlotState.DECODE

    def finish(self, slot: Slot) -> None:
        assert slot.state in (SlotState.PREFILL, SlotState.DECODE), slot.state
        slot.state = SlotState.DONE

    def release(self, slot: Slot) -> Request:
        """Evict a finished slot: DONE→FREE, detach and return the tenant."""
        assert slot.state is SlotState.DONE, slot.state
        req = slot.request
        slot.state = SlotState.FREE
        slot.request = None
        self._occupancy_gauge()
        return req

    # --------------------------------------------------------------- queries
    def decoding_slots(self) -> list[Slot]:
        return [s for s in self.slots if s.state is SlotState.DECODE]

    def occupied_slots(self) -> list[Slot]:
        return [s for s in self.slots if s.request is not None]

    def occupancy(self) -> int:
        return len(self.occupied_slots())

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._pending)

    def next_arrival_s(self) -> float | None:
        """Earliest pending arrival time (None when the queue is empty)."""
        with self._lock:
            if not self._pending:
                return None
            return min(r.arrival_time_s for r in self._pending)

    def queued_summary(self, now_s: float, limit: int = 32) -> list[dict]:
        """JSON-safe head of the pending queue (the `/requests` payload)."""
        with self._lock:
            head = list(self._pending)[:limit]
        return [
            {
                "req_id": r.req_id,
                "waited_s": round(
                    max(now_s - max(r.submitted_at, r.arrival_time_s), 0.0), 3
                ),
                "n_tokens": len(r.tokens),
                "priority": r.priority,
                "tenant": r.tenant,
                "kv_wait": r.kv_wait,
            }
            for r in head
        ]

    def _occupancy_gauge(self) -> None:
        telemetry.set_gauge("tdt_serving_slot_occupancy", float(self.occupancy()))
