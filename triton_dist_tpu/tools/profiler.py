"""Profiling: device op timelines (XProf/perfetto) + host span traces +
in-kernel event markers.

Reference threefold:

* Intra-kernel profiler (``tools/profiler/language.py:37-128``) — CUDA
  kernels write (sm_id, task, globaltimer) records to a host buffer,
  exported to perfetto. Two TPU answers:

  - **Per-kernel**: XLA's TPU profiler already records every op — including
    each named Pallas kernel — on the device timeline with sub-kernel
    DMA/compute breakdowns. ``trace()`` wraps ``jax.profiler.trace``.
  - **Intra-kernel**: ``KernelTrace`` — kernels append (seq, step, tag, aux)
    records to an SMEM event buffer via ``KernelTrace.mark`` (``prof_mark``
    is the reference-named alias). Mosaic exposes no
    cycle counter to Pallas, so records carry a per-core SEQUENCE number
    instead of a wall time; because a TPU core executes its grid serially,
    the sequence IS the schedule, which is exactly what overlap claims need
    ("expert 0's compute ran before source 3's arrival wait" is an ordering
    statement). The reference's (sm_id, start, end) rows answer the same
    question with timestamps because GPU SMs run concurrently.

* Host tracing (``profiler_utils.py:205-290`` ``group_profile``) — the
  reference gathers per-rank torch traces to rank0 and merges them. JAX on
  TPU is single-controller: one process drives every device, so one capture
  *is* the merged trace. ``ChromeTrace`` additionally records host-measured
  spans (block-until-ready walls) into a chrome://tracing JSON for
  environments without XProf (e.g. the CPU sim).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time


def trace(log_dir: str, **kw):
    """Start an XProf capture (perfetto-compatible): context manager.
    View with xprof/tensorboard or ui.perfetto.dev."""
    import jax

    return jax.profiler.trace(log_dir, **kw)


class ChromeTrace:
    """Host-measured span recorder → chrome://tracing JSON.

    Spans are wall-clock with ``block_until_ready`` fencing — coarser than
    XProf but dependency-free and sim-friendly. ``pid`` labels a logical
    rank/stream so multi-op timelines read like the reference's merged
    per-rank trace."""

    def __init__(self):
        self.events = []
        self._t0 = time.perf_counter()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, pid: int = 0, tid: int = 0, block=None):
        """Record one span; ``block`` (a pytree) is block_until_ready'd
        before closing so the span covers device completion."""
        import jax

        start = self._now_us()
        out = {}
        try:
            yield out
        finally:
            if out.get("block") is not None:
                jax.block_until_ready(out["block"])
            elif block is not None:
                jax.block_until_ready(block)
            self.events.append({
                "name": name, "ph": "X", "ts": start,
                "dur": self._now_us() - start, "pid": pid, "tid": tid,
            })

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events, "displayTimeUnit": "ms"}, f)
        return path


def profile_op(fn, args, log_dir: str, iters: int = 3):
    """Capture an XProf trace of ``iters`` runs of a jitted op; returns the
    log dir (reference ``group_profile`` usage shape)."""
    import jax

    fn = jax.jit(fn) if not hasattr(fn, "lower") else fn
    out = fn(*args)  # compile outside the capture
    jax.block_until_ready(out)
    with trace(log_dir):
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
    return log_dir


# --------------------------------------------------------------------------
# In-kernel event markers (the reference intra-kernel profiler's TPU analog)
# --------------------------------------------------------------------------

TRACE_COLS = 3  # (step_id, tag, aux) per event; seq is the row index

# Shared phase tags for the kernels wired up behind TDT_KERNEL_TRACE=1
# (allgather, gemm_allreduce): a small fixed vocabulary so one merged trace
# reads uniformly across kernels. Kernel-specific tags may extend upward.
TAG_BARRIER = 1  # entry/exit rendezvous
TAG_COMPUTE = 2  # compute step (GEMM tile / chunk) entry
TAG_SEND = 3  # remote DMA push started
TAG_WAIT = 4  # bounded wait entered
TAG_RECV = 5  # bounded wait satisfied (arrival consumed)

TRACE_TAGS = {
    TAG_BARRIER: "barrier",
    TAG_COMPUTE: "compute",
    TAG_SEND: "send",
    TAG_WAIT: "wait",
    TAG_RECV: "recv",
}


@dataclasses.dataclass(frozen=True)
class KernelTrace:
    """Static descriptor for an in-kernel event buffer.

    Usage (kernel author):

        kt = KernelTrace(capacity=256)
        ... pallas_call(..., out_shape=[..., kt.out_shape],
                        out_specs=[..., kt.out_spec()])
        # in the kernel body, with ``ev_ref`` the matching output ref:
        kt.init(ev_ref)                    # once, at the first grid step
        kt.mark(ev_ref, step, TAG, aux)    # anywhere, any number of times

    Events append in execution order; the row index is the core's schedule
    sequence. ``decode()`` turns the returned array into dicts; under
    shard_map each rank returns its own buffer (stack → merged per-rank
    trace, the reference's per-SM rows). Overflow beyond ``capacity`` drops
    events but keeps the count (``n_dropped`` in ``decode``)."""

    capacity: int = 256

    @property
    def out_shape(self):
        import jax
        import jax.numpy as jnp

        # Row 0 is the header [n_events, 0, 0]; events live in rows 1..cap.
        return jax.ShapeDtypeStruct((self.capacity + 1, TRACE_COLS), jnp.int32)

    def out_spec(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        return pl.BlockSpec(memory_space=pltpu.SMEM)

    def init(self, ev_ref, rank=None):
        """Zero the header. Call exactly once (guard with the first grid
        step); SMEM outputs start uninitialized. ``rank`` (optional) is
        stamped into the header so a host callback that sees only the
        buffer (telemetry's kernel-trace collector) can attribute it."""
        import jax.numpy as jnp

        for c in range(TRACE_COLS):
            ev_ref[0, c] = 0
        if rank is not None:
            ev_ref[0, 1] = jnp.asarray(rank, jnp.int32)

    def mark(self, ev_ref, step, tag: int, aux=0):
        """Append one (step, tag, aux) event at the next free row.

        The header count increments unconditionally; events past
        ``capacity`` are DROPPED (the row write is predicated) and are
        visible only as ``decode()['n_dropped']`` — summing tag counts from
        ``decode()['events']`` alone undercounts on overflow, so check
        ``n_dropped == 0`` before treating the event list as complete."""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        n = ev_ref[0, 0]
        ev_ref[0, 0] = n + 1

        @pl.when(n < self.capacity)
        def _():
            row = n + 1
            ev_ref[row, 0] = jnp.asarray(step, jnp.int32)
            ev_ref[row, 1] = jnp.asarray(tag, jnp.int32)
            ev_ref[row, 2] = jnp.asarray(aux, jnp.int32)

    # Reference intra-kernel profiler name (``language.py:37``): the module
    # docstring historically advertised ``prof_mark`` — keep it callable.
    prof_mark = mark

    def decode(self, events, tags: dict[int, str] | None = None) -> dict:
        """Host-side: events (cap+1, 3) int32 (one rank's buffer) →
        {"events": [{seq, step, tag, aux}...], "n_dropped": int, "rank":
        int} (rank is whatever ``init`` stamped — 0 unless given)."""
        import numpy as np

        ev = np.asarray(events)
        n = int(ev[0, 0])
        kept = min(n, self.capacity)
        out = []
        for i in range(kept):
            step, tag, aux = (int(v) for v in ev[1 + i])
            out.append({
                "seq": i, "step": step,
                "tag": tags.get(tag, tag) if tags else tag, "aux": aux,
            })
        return {
            "events": out,
            "n_dropped": max(0, n - self.capacity),
            "rank": int(ev[0, 1]),
        }


def decode_to_chrome(records, chrome: ChromeTrace | None = None) -> ChromeTrace:
    """Merge decoded kernel-trace records into one :class:`ChromeTrace`.

    ``records``: iterables shaped like ``KernelTrace.decode`` output plus a
    ``kernel`` (and optionally ``rank``) key — exactly what
    ``runtime.telemetry.kernel_traces()`` returns. Each in-kernel event
    becomes a 1-unit span at ``ts = seq``: KernelTrace carries sequence
    numbers, not wall times (a TPU core runs its grid serially, so the
    sequence IS the schedule — see the module doc), which makes the merged
    timeline an ORDERING view. pid = rank (one chrome row per rank, the
    reference's merged per-rank trace), tid = 0, and host-measured
    ``ChromeTrace.span`` events coexist in the same JSON on their own pids.
    Overflowed buffers get one ``dropped`` marker event so a truncated
    timeline is never mistaken for a complete one.
    """
    ct = chrome if chrome is not None else ChromeTrace()
    for rec in records:
        kernel = rec.get("kernel", "kernel")
        pid = int(rec.get("rank", 0))
        for e in rec.get("events", ()):
            ct.events.append({
                "name": f"{kernel}:{e['tag']}", "ph": "X",
                "ts": float(e["seq"]), "dur": 1.0, "pid": pid, "tid": 0,
                "args": {"step": e["step"], "aux": e["aux"]},
            })
        if rec.get("n_dropped"):
            ct.events.append({
                "name": f"{kernel}:dropped={rec['n_dropped']}", "ph": "X",
                "ts": float(len(rec.get("events", ()))), "dur": 1.0,
                "pid": pid, "tid": 0,
            })
    return ct


def device_memory_stats(device=None) -> dict:
    """Live/peak HBM accounting for one device (reference megakernel memory
    metrics, ``model_builder.py:135-164``). Returns {} on backends that don't
    report allocator stats (e.g. the CPU sim)."""
    import jax

    d = device if device is not None else jax.devices()[0]
    stats = getattr(d, "memory_stats", None)
    stats = stats() if callable(stats) else None
    if not stats:
        return {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size", "num_allocs")
    return {k: stats[k] for k in keep if k in stats}
