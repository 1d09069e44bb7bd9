"""GEMM-RS: GEMM → ReduceScatter with comm/compute overlap.

Reference: ``python/triton_dist/kernels/nvidia/gemm_reduce_scatter.py`` — the
producer GEMM notifies per-tile scatter signals; an RS consumer on a second
stream scatters, locally reduces, and ring-reduces across nodes
(:122,:273,:492-616). TPU redesign:

* **xla_ring** — reduce-scatter matmul: the running partial-sum chunk travels
  the ring; each of the ``world`` unrolled steps computes one
  ``(m/world, k_local) @ (k_local, n)`` chunk-GEMM and adds it to the
  incoming accumulator. XLA overlaps each step's ``ppermute`` with the next
  chunk-GEMM — compute hides the scatter exactly like the reference's
  per-tile-signal consumer.
* **pallas_fused** — ONE grid-tiled kernel (grid ``(world, Mt, Nt, Kt)``):
  the fp32 accumulator chunk travels the ring while the K-loop runs — each
  output tile's final K-iteration adds the incoming partial tile and DMAs
  the result into the outgoing send buffer, so ring traffic interleaves with
  GEMM progress at tile granularity (the TPU analog of the reference's
  per-tile scatter signals, ``gemm_reduce_scatter.py:122,273`` +
  ``reduce_scatter.py:822``). Credit semaphores give the ring backpressure.
* **pallas** — pallas GEMM producing the full partial, then the one-sided
  ring-RS kernel (kernel-granular overlap only; kept as a baseline).
* **xla** — ``dot + psum_scatter`` unoverlapped baseline.

Accumulation is fp32 on-chip; the fused ring wire carries fp32 partials
(exactness parity with the fp32-accum RS kernel).
"""

from __future__ import annotations

import dataclasses
import enum
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

import triton_dist_tpu.language as tpl
from triton_dist_tpu.runtime import resilience, telemetry
from triton_dist_tpu.runtime.mesh import DistContext
from triton_dist_tpu.kernels.allgather_gemm import (
    SCALE_LANES,
    _dequant_chunk,
    _is_quant,
    note_quant_dispatch,
)
from triton_dist_tpu.kernels.gemm import SUBLANES, gemm, GemmConfig
from triton_dist_tpu.kernels.reduce_scatter import reduce_scatter_shard
from triton_dist_tpu.shmem import kernel as sk
from triton_dist_tpu.shmem.kernel import collective_id_for, dist_pallas_call
from triton_dist_tpu.tools import profiler


class GemmRSMethod(enum.Enum):
    AUTO = "auto"
    XLA_RING = "xla_ring"
    PALLAS_FUSED = "pallas_fused"
    PALLAS = "pallas"
    XLA = "xla"


@dataclasses.dataclass(frozen=True)
class GemmRSContext:
    """Reference ``create_gemm_rs_context`` (``gemm_reduce_scatter.py:560``)."""

    ctx: DistContext
    axis: str = "tp"
    method: GemmRSMethod = GemmRSMethod.AUTO
    gemm_config: GemmConfig | None = None


def create_gemm_rs_context(
    ctx: DistContext, axis: str = "tp", method: GemmRSMethod = GemmRSMethod.AUTO
) -> GemmRSContext:
    return GemmRSContext(ctx=ctx, axis=axis, method=method)


#: Static fallback crossover (rows of the FULL M): at or below it the XLA
#: ring wins (per-chunk GEMMs are too small to hide the fused kernel's
#: workspace traffic and launch cost); above it the fused ring's tile-granular
#: overlap takes over. 256 rows is the analytic guess the bench's
#: ``prefill_overlap`` section refines.
DEFAULT_GEMM_RS_CROSSOVER_M = 256


def gemm_rs_crossover_m(world: int, wire: str | None = None) -> int:
    """xla_ring↔pallas_fused routing threshold (rows of M), fed from the
    tune cache (``gemm_rs_crossover|world=<w>``, emitted by bench.py's
    ``prefill_overlap`` section) through ``agreed_cfg_value`` — resolved once
    per process and gated by cross-rank agreement, because the two sides of
    the crossover are different collective programs (see
    ``allreduce.ar_crossover_bytes`` for the deadlock argument).

    ``wire`` selects the dtype-aware entry
    (``gemm_rs_crossover|world=<w>|wire=<wire>``): the RS wire itself stays
    fp32 partials, but a quantized A operand shifts the GEMM:HBM ratio (the
    fused kernel reads 2–4x fewer A bytes per tile), so the profitable
    crossover differs from the bf16 one."""
    from triton_dist_tpu.tools.tune import agreed_cfg_value

    key = f"gemm_rs_crossover|world={world}"
    if wire:
        key += f"|wire={wire}"
    return agreed_cfg_value(key, "crossover_m", DEFAULT_GEMM_RS_CROSSOVER_M)


def get_auto_gemm_rs_method(
    m: int, world: int, wire: str | None = None
) -> GemmRSMethod:
    """Reference ``get_auto_method`` analog for GEMM-RS: ragged M (the fused
    ring chunks rows over ranks, in whole sublane tiles) or small M → the
    XLA ring's
    compiler-scheduled overlap; prefill-sized M above the tuned crossover →
    the tile-granular fused ring.

    Degradation check FIRST — before the crossover lookup, which is itself
    a collective (``agreed_cfg_value``) that must not be dispatched once
    the process is degraded. Sticky: AUTO keeps routing ``dot +
    psum_scatter`` until ``resilience.reset_degradation()``."""
    if resilience.is_degraded("gemm_rs"):
        resilience.note_fallback_once(
            "gemm_rs.auto", "routing AUTO gemm+reduce_scatter to XLA dot+psum_scatter"
        )
        method = GemmRSMethod.XLA
    elif m % (world * SUBLANES) != 0 or m <= gemm_rs_crossover_m(world, wire):
        method = GemmRSMethod.XLA_RING
    else:
        method = GemmRSMethod.PALLAS_FUSED
    telemetry.inc(
        "tdt_kernels_auto_route_total", collective="gemm_rs", method=method.value
    )
    return method


def _gemm_rs_xla_ring(a, b, *, axis, accum_dtype=jnp.float32):
    """Ring reduce-scatter matmul (see module doc). Chunk ``c`` finishes on
    rank ``c`` after visiting every rank once. ``a`` may be a QuantTensor —
    each row chunk is then dequantized right before its chunk-GEMM (fp32
    accumulate); the ring wire carries fp32 partials either way."""
    quant = _is_quant(a)
    out_dt = b.dtype if quant else a.dtype
    world = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    m = a.shape[0]
    k = a.shape[1]
    assert m % world == 0, (m, world)
    chunk = m // world
    perm = [(i, (i + 1) % world) for i in range(world)]

    def chunk_gemm(idx):
        if quant:
            q = jax.lax.dynamic_slice(a.q, (idx * chunk, 0), (chunk, k))
            sc = jax.lax.dynamic_slice(a.scale, (idx * chunk, 0), (chunk, 1))
            rows = _dequant_chunk(q, sc, out_dt)
        else:
            rows = jax.lax.dynamic_slice(a, (idx * chunk, 0), (chunk, k))
        return jnp.dot(rows, b, preferred_element_type=accum_dtype)

    first = jnp.mod(me - 1, world)
    acc = chunk_gemm(first)
    for s in range(world - 1):  # static unroll
        acc = jax.lax.ppermute(acc, axis, perm)
        incoming = jnp.mod(me - s - 2, world)
        acc = acc + chunk_gemm(incoming)
    return acc.astype(out_dt)


def _gemm_rs_fused_kernel(
    sched_ref,  # SMEM (world,) int32 — sched[s] = (me - 1 - s) % world
    a_ref,  # (bm, bk) VMEM — pipelined A tile (rows of chunk sched[s]);
    #         wire dtype under ``quant``, then the row-aligned scale tile
    #         follows as the next input:
    #   a_scale_ref, (bm, SCALE_LANES) f32 VMEM — per-row scales of this tile
    # then:
    #   b_ref,      (bk, bn) VMEM — pipelined B tile
    #   o_ref,      (chunk, n) ANY — final reduced chunk, tile-DMA'd at
    #               s==world-1
    #   send_buf,   (2, chunk, n) f32 ANY — outgoing partial chunk, per-slot
    #   recv_buf,   (2, chunk, n) f32 ANY — incoming partial chunk, per-slot
    #   status_ref, SMEM (STATUS_WORDS,) bounded-wait abort record
    # With ``trace`` set, its SMEM event buffer follows status_ref (the last
    # output); then the scratch operands below in order:
    #   acc,          VMEM (bm, bn) f32
    #   recv_tile,    VMEM (bm, bn) f32 — staged incoming tile
    #   send_stage,   VMEM (2, bm, bn) f32 — outgoing tile, double-buffered
    #   out_stage,    VMEM (2, bm, bn) out dtype — final tile, double-buffered
    #   recv_sem,     DMA (2,)
    #   send_sem,     DMA (2,) — remote send completion
    #   tile_out_sem, DMA (2,) — local copies into send_buf (byte-counted)
    #   tile_in_sem,  DMA (1,) — recv tile staging
    #   out_sem,      DMA (2,) — final tile copies into o_ref
    #   credit_sem,   REGULAR (2,) — receiver → left: slot consumed
    *rest,
    axis,
    mesh_axes,
    n_m: int,
    n_n: int,
    n_k: int,
    quant: bool = False,
    trace=None,
):
    """Fused ring reduce-scatter matmul (see module doc). Step ``s`` computes
    the chunk-GEMM for chunk ``sched[s]``, adding the partial received from
    the left neighbor; every finished tile is DMA'd into the outgoing buffer
    immediately (K-loop-interleaved ring traffic), and the chunk-complete
    remote send overlaps the next step's GEMM. Cross-rank waits are bounded
    and carry the SMEM status-buffer abort protocol (phase + peer named on
    timeout); LOCAL DMA drains stay unbounded by design."""
    rest = list(rest)
    a_scale_ref = rest.pop(0) if quant else None
    b_ref = rest.pop(0)
    o_ref = rest.pop(0)
    send_buf = rest.pop(0)
    recv_buf = rest.pop(0)
    status_ref = rest.pop(0)
    ev_ref = rest.pop(0) if trace is not None else None
    (acc, recv_tile, send_stage, out_stage, recv_sem, send_sem, tile_out_sem,
     tile_in_sem, out_sem, credit_sem) = rest
    s, im, jn, kk = (pl.program_id(i) for i in range(4))
    me = tpl.rank(axis)
    world = tpl.num_ranks(axis)
    right = tpl.ring_neighbor(axis, +1, mesh_axes=mesh_axes)
    left = tpl.ring_neighbor(axis, -1, mesh_axes=mesh_axes)
    # Peer attribution is by rank index along `axis` (not logical device id):
    # a left neighbour that dies after the entry barrier starves rs_recv,
    # which names the exact peer in the abort record.
    left_rank = jax.lax.rem(me - 1 + world, world)
    right_rank = jax.lax.rem(me + 1, world)
    bm, bn = acc.shape
    cur = jax.lax.rem(s, 2)  # outgoing slot of this step
    prev = jax.lax.rem(s - 1 + 2, 2)  # incoming slot (left's step s-1)

    @pl.when(jnp.logical_and(im == 0, jnp.logical_and(jn == 0, kk == 0)))
    def _step_start():
        @pl.when(s == 0)
        def _():
            sk.init_status(status_ref, axis=axis)
            if trace is not None:
                trace.init(ev_ref, rank=me)
            # Nobody pushes before everybody is IN this kernel: a remote DMA
            # signals a scratch semaphore by its address on the peer, which
            # belongs to another kernel until the peer gets here (see
            # ``_gemm_ar_fused_kernel``).
            sk.bounded_barrier_all(
                status_ref, axis, mesh_axes=mesh_axes, phase="barrier"
            )

        if trace is not None:
            trace.mark(ev_ref, s, profiler.TAG_COMPUTE, 0)

        @pl.when(s > 0)
        def _():
            # Incoming partial chunk fully arrived (dl.wait analog).
            if trace is not None:
                trace.mark(ev_ref, s, profiler.TAG_WAIT, prev)
            sk.bounded_wait_recv(
                recv_sem.at[prev], recv_buf.at[prev], status_ref,
                phase="rs_recv", peer=left_rank,
            )
            if trace is not None:
                trace.mark(ev_ref, s, profiler.TAG_RECV, prev)

        @pl.when(s >= 2)
        def _():
            # Slot reuse: our send of step s-2 completed locally (LOCAL DMA
            # completion — unbounded by design), and the right neighbor
            # consumed it (credit backpressure — bounded).
            tpl.wait_send(send_sem.at[cur], send_buf.at[cur])
            sk.bounded_wait(
                credit_sem.at[cur], status_ref,
                phase="rs_credit", peer=right_rank,
            )

    # Stage the incoming tile for this (im, jn) early — overlaps the K-loop.
    @pl.when(jnp.logical_and(s > 0, kk == 0))
    def _():
        pltpu.make_async_copy(
            recv_buf.at[prev, pl.ds(im * bm, bm), pl.ds(jn * bn, bn)],
            recv_tile,
            tile_in_sem.at[0],
        ).start()

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    a_tile = a_ref[...]
    if quant:
        # Dequantize during the VMEM tile consume: exact power-of-two
        # ``q * scale`` in f32, cast to the weight dtype — the ring wire
        # stays fp32 partials, only the A operand arrives quantized.
        a_tile = (a_tile.astype(jnp.float32) * a_scale_ref[:, :1]).astype(
            b_ref.dtype
        )
    acc[...] += jax.lax.dot_general(
        a_tile, b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == n_k - 1)
    def _tile_done():
        @pl.when(s > 0)
        def _():
            pltpu.make_async_copy(
                recv_buf.at[prev, pl.ds(im * bm, bm), pl.ds(jn * bn, bn)],
                recv_tile,
                tile_in_sem.at[0],
            ).wait()

        # where(), not arithmetic: recv_tile is uninitialized garbage at s==0
        # and garbage*0 could be NaN.
        val = acc[...] + jnp.where(s > 0, recv_tile[...], jnp.zeros_like(recv_tile))

        tile_idx = im * n_n + jn

        @pl.when(s == world - 1)
        def _():
            # Output must be an ANY buffer written by tile DMAs: a pipelined
            # out BlockSpec would revisit its blocks once per ring step,
            # which Pallas forbids.
            t = jax.lax.rem(tile_idx, 2)

            @pl.when(tile_idx >= 2)
            def _():
                pltpu.make_async_copy(
                    out_stage.at[t], out_stage.at[t], out_sem.at[t]
                ).wait()

            out_stage[t] = val.astype(out_stage.dtype)
            pltpu.make_async_copy(
                out_stage.at[t],
                o_ref.at[pl.ds(im * bm, bm), pl.ds(jn * bn, bn)],
                out_sem.at[t],
            ).start()

        @pl.when(s < world - 1)
        def _():
            # Ship this tile into the outgoing chunk buffer right away — the
            # per-tile producer signal analog; the byte-counting semaphore
            # doubles as the chunk-complete signal.
            t = jax.lax.rem(im * n_n + jn, 2)

            @pl.when(im * n_n + jn >= 2)
            def _():
                pltpu.make_async_copy(
                    send_stage.at[t], send_stage.at[t], tile_out_sem.at[t]
                ).wait()

            send_stage[t] = val
            pltpu.make_async_copy(
                send_stage.at[t],
                send_buf.at[cur, pl.ds(im * bm, bm), pl.ds(jn * bn, bn)],
                tile_out_sem.at[t],
            ).start()

        is_chunk_end = jnp.logical_and(im == n_m - 1, jn == n_n - 1)

        @pl.when(jnp.logical_and(is_chunk_end, s < world - 1))
        def _chunk_send():
            # Drain outstanding tile copies (the last tile's, and — when the
            # chunk has ≥2 tiles — the second-to-last tile's on the other
            # slot; everything older was waited before slot reuse), then push
            # the whole chunk. Tile count is static, so slots are too.
            t_last = (n_m * n_n - 1) % 2
            if n_m * n_n >= 2:
                pltpu.make_async_copy(
                    send_stage.at[1 - t_last], send_stage.at[1 - t_last],
                    tile_out_sem.at[1 - t_last],
                ).wait()
            pltpu.make_async_copy(
                send_stage.at[t_last], send_stage.at[t_last], tile_out_sem.at[t_last]
            ).wait()
            if trace is not None:
                trace.mark(ev_ref, s, profiler.TAG_SEND, cur)
            pltpu.make_async_remote_copy(
                src_ref=send_buf.at[cur],
                dst_ref=recv_buf.at[cur],
                send_sem=send_sem.at[cur],
                recv_sem=recv_sem.at[cur],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            ).start()

        @pl.when(jnp.logical_and(is_chunk_end, s > 0))
        def _():
            # Free the consumed slot back to the left neighbor.
            tpl.notify(credit_sem.at[prev], left)

    is_last = jnp.logical_and(
        s == world - 1,
        jnp.logical_and(im == n_m - 1, jnp.logical_and(jn == n_n - 1, kk == n_k - 1)),
    )

    @pl.when(is_last)
    def _():
        # Drain: outstanding output-tile copies, our last send (step
        # world-2; LOCAL completion — unbounded by design), and the credit
        # the right neighbor signalled when consuming it (its step world-1
        # chunk end runs before this wait on every rank —
        # signal-before-wait, no cycle).
        t_last = (n_m * n_n - 1) % 2
        if n_m * n_n >= 2:
            pltpu.make_async_copy(
                out_stage.at[1 - t_last], out_stage.at[1 - t_last],
                out_sem.at[1 - t_last],
            ).wait()
        pltpu.make_async_copy(
            out_stage.at[t_last], out_stage.at[t_last], out_sem.at[t_last]
        ).wait()
        tpl.wait_send(send_sem.at[(world - 2) % 2], send_buf.at[0])
        sk.bounded_wait(
            credit_sem.at[(world - 2) % 2], status_ref,
            phase="rs_credit_drain", peer=right_rank,
        )
        # Peers must not start a next launch that reuses these buffers while
        # stragglers still forward chunks.
        sk.bounded_barrier_all(
            status_ref, axis, mesh_axes=mesh_axes, phase="exit_barrier"
        )


def _gemm_rs_fused(a, b, *, axis, mesh_axes, config=None):
    world = jax.lax.axis_size(axis)
    # The ring's final drain waits on the step-(world-2) send and its
    # credit; at world=1 neither is ever signaled — the kernel would
    # deadlock (and crash the TPU watchdog). Callers go through
    # gemm_rs_shard's world==1 shortcut.
    assert world > 1, "fused GEMM-RS needs world > 1 (use gemm_rs_shard)"
    me = jax.lax.axis_index(axis)
    quant = _is_quant(a)
    a_q = a.q if quant else a
    out_dt = b.dtype if quant else a.dtype
    m, k = a_q.shape
    n = b.shape[1]
    assert m % world == 0, (m, world)
    chunk = m // world
    from triton_dist_tpu.kernels.gemm import fit_block

    # Same tile shape the fused AG-GEMM measured fastest on v5e (wider
    # K-tile halves accumulator flushes); VMEM need ≈9 MiB at these tiles.
    cfg = config or GemmConfig(512, 512, 1024)
    bm = fit_block(chunk, cfg.block_m)
    bn = fit_block(n, cfg.block_n)
    bk = fit_block(k, cfg.block_k)
    n_m, n_n, n_k = chunk // bm, n // bn, k // bk
    sched = jnp.mod(me - 1 - jnp.arange(world, dtype=jnp.int32), world).astype(jnp.int32)
    kernel_name = "_gemm_rs_fused_kernel" + ("_quant" if quant else "")

    trace = telemetry.maybe_kernel_trace()
    out_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        sk.status_out_spec(),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((chunk, n), out_dt),
        jax.ShapeDtypeStruct((2, chunk, n), jnp.float32),
        jax.ShapeDtypeStruct((2, chunk, n), jnp.float32),
        sk.status_out_shape(),
    ]
    if trace is not None:
        out_specs.append(trace.out_spec())
        out_shape.append(trace.out_shape)
    in_specs = [
        pl.BlockSpec(
            (bm, bk), lambda s, im, jn, kk, sched: (sched[s] * n_m + im, kk)
        ),
    ]
    if quant:
        # Per-row scale tile rides next to its A tile; the index map mirrors
        # the A map's row walk so scale rows stay aligned with q rows.
        in_specs.append(
            pl.BlockSpec(
                (bm, SCALE_LANES),
                lambda s, im, jn, kk, sched: (sched[s] * n_m + im, 0),
            )
        )
    in_specs.append(pl.BlockSpec((bk, bn), lambda s, im, jn, kk, sched: (kk, jn)))
    operands = (sched, a_q, a.scale, b) if quant else (sched, a_q, b)
    out, _, _, status, *ev = dist_pallas_call(
        functools.partial(
            _gemm_rs_fused_kernel,
            axis=axis,
            mesh_axes=mesh_axes,
            n_m=n_m,
            n_n=n_n,
            n_k=n_k,
            quant=quant,
            trace=trace,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(world, n_m, n_n, n_k),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=[
                pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.VMEM((2, bm, bn), jnp.float32),
                pltpu.VMEM((2, bm, bn), out_dt),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR((2,)),
            ],
        ),
        out_shape=tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary", "arbitrary"),
            has_side_effects=True,
            collective_id=collective_id_for(kernel_name),
        ),
    )(*operands)
    resilience.consume_status(status, feature="gemm_rs", kernel=kernel_name)
    if trace is not None:
        telemetry.consume_kernel_trace(trace, ev[0], kernel=kernel_name)
    return out


def gemm_rs_shard(
    a: jax.Array,  # (m, k_shard) — A column-shard of this rank
    b: jax.Array,  # (k_shard, n) — B row-shard of this rank
    *,
    axis: str = "tp",
    mesh_axes=None,
    method: GemmRSMethod = GemmRSMethod.AUTO,
    gemm_config: GemmConfig | None = None,
) -> jax.Array:
    """Compute ``reduce_scatter(A_local @ B_local)`` → this rank's
    ``(m/world, n)`` row-chunk of the summed product. Usable inside shard_map.
    Reference host op ``gemm_rs`` (``gemm_reduce_scatter.py:593``)."""
    world = jax.lax.axis_size(axis)
    quant = _is_quant(a)
    out_dt = b.dtype if quant else a.dtype
    if world == 1:
        a1 = _dequant_chunk(a.q, a.scale, b.dtype) if quant else a
        return jnp.dot(a1, b, preferred_element_type=jnp.float32).astype(out_dt)
    if quant:
        # RS wire stays fp32 partials: no wire_hops — the win is the
        # quantized A operand's HBM/VMEM footprint.
        note_quant_dispatch("gemm_rs", a, world)
    if method is GemmRSMethod.AUTO:
        m_rows = a.q.shape[0] if quant else a.shape[0]
        method = get_auto_gemm_rs_method(
            m_rows, world, wire=a.wire if quant else None
        )

    if method is GemmRSMethod.XLA:
        a1 = _dequant_chunk(a.q, a.scale, b.dtype) if quant else a
        partial = jnp.dot(a1, b, preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(
            partial, axis, scatter_dimension=0, tiled=True
        ).astype(out_dt)

    if method is GemmRSMethod.PALLAS_FUSED:
        return _gemm_rs_fused(a, b, axis=axis, mesh_axes=mesh_axes, config=gemm_config)

    if method is GemmRSMethod.PALLAS:
        a1 = _dequant_chunk(a.q, a.scale, b.dtype) if quant else a
        partial = gemm(a1, b, config=gemm_config)
        return reduce_scatter_shard(partial, axis=axis, mesh_axes=mesh_axes)

    return _gemm_rs_xla_ring(a, b, axis=axis)


def gemm_rs(rs_ctx: GemmRSContext, a: jax.Array, b: jax.Array) -> jax.Array:
    """Standalone host op: A sharded on cols, B sharded on rows over ``axis``;
    returns ``A @ B`` sharded on rows (the TP down-projection shape)."""
    axis = rs_ctx.axis
    mesh_axes = rs_ctx.ctx.axis_names

    def fn(a_shard, b_shard):
        return gemm_rs_shard(
            a_shard,
            b_shard,
            axis=axis,
            mesh_axes=mesh_axes,
            method=rs_ctx.method,
            gemm_config=rs_ctx.gemm_config,
        )

    shard_f = jax.shard_map(
        fn,
        mesh=rs_ctx.ctx.mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(shard_f)(a, b)


def gemm_rs_2d_shard(
    a: jax.Array,  # (m, k_shard) — A column-shard of this (dcn, ici) rank
    b: jax.Array,  # (k_shard, n) — B row-shard of this rank
    *,
    axes: tuple[str, str],  # (outer/DCN axis, inner/ICI axis)
    mesh_axes=None,
    method: GemmRSMethod = GemmRSMethod.AUTO,
    gemm_config: GemmConfig | None = None,
) -> jax.Array:
    """DCN-aware hierarchical GEMM-RS (reference inter-node GEMM-RS,
    ``reduce_scatter.py:472-640``): the fused ICI kernel overlaps the GEMM
    with an intra-axis ring reduce-scatter (partial sums over this ici
    group's K range), then ONE XLA reduce-scatter over the slow (DCN) axis
    finishes the sum with wi-times-fewer, bigger messages — the same
    intra-then-inter split as the reference's 2D reduce-scatter context.

    K is sharded over BOTH axes; returns this rank's
    ``(m / (wo*wi), n)`` row-chunk of the fully-summed product, rows
    assigned inner-major then outer (rank (d, i) holds global row block
    ``i*wo + d``). Inside shard_map over both axes.

    .. warning:: **Layout asymmetry vs ``ag_gemm_2d_shard``.** This
       function's output is INNER-major — assembling it under
       ``out_specs=P((outer, inner))`` silently row-permutes the result.
       Use ``out_specs=P((inner, outer))``, or permute with
       ``reorder_2d_rows_inner_to_outer_major`` (extra copy).
       ``ag_gemm_2d_shard`` pays a local block transpose to return
       outer-major because its permutation is rank-local; here the row
       OWNERSHIP itself is inner-major (``psum_scatter`` over the outer
       axis scatters the inner leg's output), so outer-major ownership
       would need an extra cross-rank exchange — callers choose."""
    outer, inner = axes
    if mesh_axes is None:
        mesh_axes = axes  # full-mesh addressing, see ag_gemm_2d_shard
    wo = jax.lax.axis_size(outer)
    m = a.shape[0]
    assert m % (wo * jax.lax.axis_size(inner)) == 0, (m, wo)

    # ICI leg: fused GEMM + ring RS over the inner axis → (m/wi, n) rows,
    # partially summed (this ici group's K contribution only).
    part = gemm_rs_shard(
        a, b, axis=inner, mesh_axes=mesh_axes, method=method,
        gemm_config=gemm_config,
    )
    # DCN leg: finish the sum and scatter the rows over the outer axis.
    return jax.lax.psum_scatter(
        part.astype(jnp.float32), outer, scatter_dimension=0, tiled=True
    ).astype(a.dtype)


def reorder_2d_rows_inner_to_outer_major(x: jax.Array, *, axes) -> jax.Array:
    """Move ``gemm_rs_2d_shard``'s inner-major row ownership (rank (d, i)
    holds global block ``i*wo + d``) to outer-major ``P((outer, inner))``
    order (rank (d, i) holds block ``d*wi + i``) with ONE
    collective-permute — each rank forwards its whole block exactly once.
    Use when composing with outer-major consumers such as
    ``ag_gemm_2d_shard`` (see the layout warnings on both)."""
    outer, inner = axes
    wo = jax.lax.axis_size(outer)
    wi = jax.lax.axis_size(inner)
    # Linear rank over (outer, inner) is d*wi + i; it holds block i*wo + d,
    # which outer-major order places on linear rank i*wo + d.
    perm = [(d * wi + i, i * wo + d) for d in range(wo) for i in range(wi)]
    return jax.lax.ppermute(x, (outer, inner), perm)
