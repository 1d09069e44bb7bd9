"""GEMM-AR: fused GEMM + AllReduce for the small-M decode regime.

Reference: ``python/triton_dist/kernels/nvidia/gemm_allreduce.py`` —
persistent GEMM with per-tile notify + consumer AR kernel (multimem / ring),
low-latency double-buffer phase contexts (:44-831); headline 1.26-1.44×
decode-path wins (``e2e_dense.md:34-38``). TPU redesign:

* **pallas_fused** — ONE grid-tiled kernel (grid ``(world, Mt, Nt, Kt)``):
  the fp32 accumulator chunk rides the ICI ring during the K-loop (the
  reduce-scatter phase, with credit-semaphore backpressure on slot reuse —
  same tile-granular overlap as ``gemm_reduce_scatter.py``'s fused path),
  then the finished chunk is ring-broadcast back out of the SAME kernel
  (the all-gather phase, per-step semaphore slots so ranks may drift).
  Bandwidth-optimal for larger M; requires ``m % world == 0``.
* **ll_one_shot** — fused low-latency kernel for tiny/ragged M (decode):
  the local partial GEMM's epilogue DMAs each finished output tile directly
  into ALL peers' symmetric landing zones (one-shot push, the multimem
  analog) and the reducer waits per-SOURCE on byte-counting semaphore
  slots. One ICI hop; fp32 partials on the wire, so the result matches the
  fp32-accum ``dot + psum`` reference exactly.
* **rs_ag** — ring reduce-scatter matmul followed by a separate ring
  all-gather kernel: the unfused composition baseline for larger M.
* **one_shot** — local full dot, then the one-shot push AR kernel: the
  unfused composition baseline for tiny M.
* **xla** — ``dot + psum`` baseline.

AUTO picks ``ll_one_shot`` for small M (latency-bound decode, ragged or not),
``dot + psum`` for large ragged M, and
``pallas_fused`` above the crossover; the crossover row count is a tune-cache
entry (``gemm_ar_crossover|world=N``) read through
``tools.tune.agreed_cfg_value`` — cross-rank agreement from day one, since a
rank-local read of a stale cache would route the same call into two
different collective kernels and deadlock.
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
from triton_dist_tpu.kernels.allgather import all_gather_shard, AllGatherMethod
from triton_dist_tpu.kernels.allreduce import all_reduce_shard, AllReduceMethod
from triton_dist_tpu.kernels.allgather_gemm import (
    SCALE_LANES,
    _dequant_chunk,
    _is_quant,
    note_quant_dispatch,
)
from triton_dist_tpu.kernels.gemm import SUBLANES, GemmConfig, fit_block
from triton_dist_tpu.kernels.gemm_reduce_scatter import _gemm_rs_xla_ring
from triton_dist_tpu.shmem import kernel as sk
from triton_dist_tpu.shmem.kernel import collective_id_for, dist_pallas_call
from triton_dist_tpu.tools import profiler


class GemmARMethod(enum.Enum):
    AUTO = "auto"
    PALLAS_FUSED = "pallas_fused"
    LL_ONE_SHOT = "ll_one_shot"
    RS_AG = "rs_ag"
    ONE_SHOT = "one_shot"
    XLA = "xla"


#: Static fallback crossover (rows of M): at or below it the one-hop
#: ll_one_shot kernel wins (kernel-launch + per-step ring latency dominates);
#: above it the fused ring's 2·(w−1)/w bandwidth advantage takes over. 64
#: rows is the analytic guess the bench's ``gemm_ar_decode`` section refines.
DEFAULT_GEMM_AR_CROSSOVER_M = 64


def gemm_ar_crossover_m(world: int, wire: str | None = None) -> int:
    """ll_one_shot↔pallas_fused routing threshold (rows of M), fed from the
    tune cache (``gemm_ar_crossover|world=<w>``, emitted by bench.py's
    ``gemm_ar_decode`` section) through ``agreed_cfg_value`` — the lookup is
    resolved once per process and gated by cross-rank agreement, because the
    two sides of the crossover are different collective kernels (see
    ``allreduce.ar_crossover_bytes`` for the deadlock argument).

    ``wire`` keys a dtype-aware entry (``…|wire=fp8``): a quantized A operand
    leaves the fp32 partial wire untouched but shifts the GEMM-side HBM
    traffic, so the tuned crossover differs from the bf16/f32 one."""
    from triton_dist_tpu.tools.tune import agreed_cfg_value

    key = f"gemm_ar_crossover|world={world}"
    if wire is not None:
        key += f"|wire={wire}"
    return agreed_cfg_value(key, "crossover_m", DEFAULT_GEMM_AR_CROSSOVER_M)


def get_auto_gemm_ar_method(
    m: int, world: int, wire: str | None = None
) -> GemmARMethod:
    """Reference ``get_auto_method`` analog for GEMM-AR: decode-sized M
    (ragged or not) → the low-latency one-shot kernel, which carries all of
    M in VMEM; larger M → the tile-granular fused ring, when every rank's
    row chunk is whole sublane tiles (the only row blocks Mosaic takes);
    larger ragged M — a server's prompt lengths are arbitrary — fits
    neither kernel and takes ``dot + psum``.

    Degradation check FIRST — before the crossover lookup, which is itself
    a collective (``agreed_cfg_value``) that must not be dispatched once
    the process is degraded. Sticky: AUTO keeps routing ``dot + psum``
    until ``resilience.reset_degradation()``."""
    if resilience.is_degraded("gemm_ar"):
        resilience.note_fallback_once(
            "gemm_ar.auto", "routing AUTO gemm+allreduce to XLA dot+psum"
        )
        method = GemmARMethod.XLA
    elif m <= gemm_ar_crossover_m(world, wire):
        method = GemmARMethod.LL_ONE_SHOT
    elif m % (world * SUBLANES) != 0:
        method = GemmARMethod.XLA
    else:
        method = GemmARMethod.PALLAS_FUSED
    telemetry.inc(
        "tdt_kernels_auto_route_total", collective="gemm_ar", method=method.value
    )
    return method


@dataclasses.dataclass(frozen=True)
class GemmARContext:
    """Reference ``GemmARContext`` / ``LLGemmARContext``
    (``gemm_allreduce.py:44,:80``)."""

    ctx: DistContext
    axis: str = "tp"
    method: GemmARMethod = GemmARMethod.AUTO
    gemm_config: GemmConfig | None = None


def create_gemm_ar_context(
    ctx: DistContext, axis: str = "tp", method: GemmARMethod = GemmARMethod.AUTO
) -> GemmARContext:
    return GemmARContext(ctx=ctx, axis=axis, method=method)


def _gemm_ar_fused_kernel(
    sched_ref,  # SMEM (world,) int32 — sched[s] = (me - 1 - s) % world
    a_ref,  # (bm, bk) VMEM — pipelined A tile (rows of chunk sched[s])
    # When ``quant``, an ``a_scale_ref`` — (bm, SCALE_LANES) VMEM f32 per-row
    # scales walked in lockstep with a_ref — precedes b_ref in ``rest``.
    # Then, in order:
    #   b_ref,      (bk, bn) VMEM — pipelined B tile
    #   o_ref,      (m, n) ANY — full product; my chunk tile-DMA'd at
    #               s==world-1, the rest ring-broadcast in the AG phase
    #   send_buf,   (2, chunk, n) f32 ANY — outgoing partial chunk, per-slot
    #   recv_buf,   (2, chunk, n) f32 ANY — incoming partial chunk, per-slot
    #   status_ref, SMEM (STATUS_WORDS,) bounded-wait abort record
    #   acc,        VMEM (bm, bn) f32
    #   recv_tile,  VMEM (bm, bn) f32 — staged incoming tile
    #   send_stage, VMEM (2, bm, bn) f32 — outgoing tile, double-buffered
    #   out_stage,  VMEM (2, bm, bn) out dtype — final tile, double-buffered
    #   recv_sem,   DMA (2,)
    #   send_sem,   DMA (2,) — remote send completion
    #   tile_out_sem,  DMA (2,) — local copies into send_buf (byte-counted)
    #   tile_in_sem,   DMA (1,) — recv tile staging
    #   out_sem,    DMA (2,) — final tile copies into o_ref
    #   ag_send_sem,  DMA (world-1,) — AG-phase sends, one slot per step
    #   ag_recv_sem,  DMA (world-1,) — AG-phase arrivals, one slot per step
    #   credit_sem,   REGULAR (2,) — receiver → left: RS slot consumed
    *rest,
    axis,
    mesh_axes,
    n_m: int,
    n_n: int,
    n_k: int,
    quant: bool = False,
):
    """Fused GEMM + all-reduce in one kernel: ring reduce-scatter matmul
    (identical structure to ``_gemm_rs_fused_kernel`` — step ``s`` computes
    the chunk-GEMM for chunk ``sched[s]``, adds the partial received from the
    left neighbor, ships every finished tile into the outgoing buffer
    immediately), then — once this rank's chunk is reduced and landed in
    ``o_ref`` — the AG phase ring-broadcasts the finished chunks with the
    per-step-slot protocol of ``_ring_ag_kernel``. The RS leg keeps the
    credit-semaphore backpressure on its two send slots; the AG leg needs no
    credits because each of its ``world-1`` steps owns a dedicated slot and
    the destination rows are disjoint per chunk."""
    rest = list(rest)
    a_scale_ref = rest.pop(0) if quant else None
    (
        b_ref, o_ref, send_buf, recv_buf, status_ref,
        acc, recv_tile, send_stage, out_stage,
        recv_sem, send_sem, tile_out_sem, tile_in_sem, out_sem,
        ag_send_sem, ag_recv_sem, credit_sem,
    ) = rest
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
    chunk = n_m * bm  # rows per rank
    cur = jax.lax.rem(s, 2)  # outgoing slot of this step
    prev = jax.lax.rem(s - 1 + 2, 2)  # incoming slot (left's step s-1)

    @pl.when(jnp.logical_and(im == 0, jnp.logical_and(jn == 0, kk == 0)))
    def _step_start():
        @pl.when(s == 0)
        def _():
            sk.init_status(status_ref, axis=axis)
            # Nobody pushes before everybody is IN this kernel. A remote
            # DMA signals a scratch semaphore by its address on the peer,
            # and until the peer enters this kernel that address belongs to
            # whatever kernel it is still running (found on four chips: a
            # fast rank's step-0 push landed in a neighbour's attention
            # kernel between two layers, and the ring stalled in rs_recv).
            sk.bounded_barrier_all(
                status_ref, axis, mesh_axes=mesh_axes, phase="barrier"
            )

        @pl.when(s > 0)
        def _():
            # Incoming partial chunk fully arrived (dl.wait analog).
            sk.bounded_wait_recv(
                recv_sem.at[prev], recv_buf.at[prev], status_ref,
                phase="rs_recv", peer=left_rank,
            )

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
            # My chunk's final tiles go straight into the full-size output at
            # this rank's row offset (o_ref must be ANY + tile DMAs: a
            # pipelined out BlockSpec would revisit blocks once per ring
            # step, which Pallas forbids).
            t = jax.lax.rem(tile_idx, 2)

            @pl.when(tile_idx >= 2)
            def _():
                pltpu.make_async_copy(
                    out_stage.at[t], out_stage.at[t], out_sem.at[t]
                ).wait()

            out_stage[t] = val.astype(out_stage.dtype)
            pltpu.make_async_copy(
                out_stage.at[t],
                o_ref.at[pl.ds(me * chunk + im * bm, bm), pl.ds(jn * bn, bn)],
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
        # Drain the RS leg: outstanding output-tile copies (my chunk must be
        # fully in o_ref before the AG ring forwards it), our last send
        # (step world-2), and the credit the right neighbor signalled when
        # consuming it (its step world-1 chunk end runs before this wait on
        # every rank — signal-before-wait, no cycle).
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

        # AG phase: ring-broadcast the finished chunks out of the same
        # kernel (``_ring_ag_kernel``'s step protocol over o_ref row-slices).
        # No rendezvous before step 0: I only forward rows that are complete
        # (my own chunk, drained above; later steps forward what already
        # arrived), destination rows are disjoint per chunk, and arrivals
        # are byte-counted on per-step slots — ranks may drift freely.
        def ag_step(s2, _):
            src = jax.lax.rem(me - s2 + world, world)  # chunk I forward
            rows = pl.ds(src * chunk, chunk)
            dma = pltpu.make_async_remote_copy(
                src_ref=o_ref.at[rows],
                dst_ref=o_ref.at[rows],
                send_sem=ag_send_sem.at[s2],
                recv_sem=ag_recv_sem.at[s2],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            dma.start()
            # Chunk (me-s2-1)%world arrives from the left on the same slot.
            arriving = jax.lax.rem(me - s2 - 1 + world, world)
            arows = pl.ds(arriving * chunk, chunk)
            sk.bounded_wait_recv(
                ag_recv_sem.at[s2], o_ref.at[arows], status_ref,
                phase="ag_recv", peer=left_rank,
            )
            # Send drain is a LOCAL completion — unbounded by design.
            dma.wait_send()
            return 0

        jax.lax.fori_loop(0, world - 1, ag_step, 0)
        # Peers must not start a next kernel that reuses these buffers (or
        # this kernel again) while stragglers still forward chunks.
        sk.bounded_barrier_all(
            status_ref, axis, mesh_axes=mesh_axes, phase="exit_barrier"
        )


def _gemm_ar_fused(a, b, *, axis, mesh_axes, config=None):
    world = jax.lax.axis_size(axis)
    # The RS leg's final drain waits on the step-(world-2) send and its
    # credit; at world=1 neither is ever signaled — the kernel would
    # deadlock. Callers go through gemm_ar_shard's world==1 shortcut.
    assert world > 1, "fused GEMM-AR needs world > 1 (use gemm_ar_shard)"
    me = jax.lax.axis_index(axis)
    quant = _is_quant(a)
    a_q = a.q if quant else a
    out_dt = b.dtype if quant else a.dtype
    m, k = a_q.shape
    n = b.shape[1]
    assert m % world == 0, (m, world)
    chunk = m // world

    # Same tile shape the fused RS/AG GEMMs measured fastest on v5e.
    cfg = config or GemmConfig(512, 512, 1024)
    bm = fit_block(chunk, cfg.block_m)
    bn = fit_block(n, cfg.block_n)
    bk = fit_block(k, cfg.block_k)
    n_m, n_n, n_k = chunk // bm, n // bn, k // bk
    sched = jnp.mod(me - 1 - jnp.arange(world, dtype=jnp.int32), world).astype(jnp.int32)
    kernel_name = "_gemm_ar_fused_kernel" + ("_quant" if quant else "")

    in_specs = [
        pl.BlockSpec(
            (bm, bk), lambda s, im, jn, kk, sched: (sched[s] * n_m + im, kk)
        ),
    ]
    if quant:
        # Per-row scale tile walks the same row schedule as its A tile.
        in_specs.append(
            pl.BlockSpec(
                (bm, SCALE_LANES),
                lambda s, im, jn, kk, sched: (sched[s] * n_m + im, 0),
            )
        )
    in_specs.append(pl.BlockSpec((bk, bn), lambda s, im, jn, kk, sched: (kk, jn)))
    operands = (sched, a_q, a.scale, b) if quant else (sched, a_q, b)
    out, _, _, status = dist_pallas_call(
        functools.partial(
            _gemm_ar_fused_kernel,
            axis=axis,
            mesh_axes=mesh_axes,
            n_m=n_m,
            n_n=n_n,
            n_k=n_k,
            quant=quant,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(world, n_m, n_n, n_k),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                sk.status_out_spec(),
            ),
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
                pltpu.SemaphoreType.DMA((max(world - 1, 1),)),
                pltpu.SemaphoreType.DMA((max(world - 1, 1),)),
                pltpu.SemaphoreType.REGULAR((2,)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, n), out_dt),
            jax.ShapeDtypeStruct((2, chunk, n), jnp.float32),
            jax.ShapeDtypeStruct((2, chunk, n), jnp.float32),
            sk.status_out_shape(),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary", "arbitrary"),
            has_side_effects=True,
            collective_id=collective_id_for(kernel_name),
        ),
    )(*operands)
    resilience.consume_status(status, feature="gemm_ar", kernel=kernel_name)
    return out


def _gemm_ar_ll_kernel(
    a_ref,  # (m, bk) VMEM — pipelined A panel (full M: ragged/tiny is fine)
    # When ``quant``, an ``a_scale_ref`` — (m, SCALE_LANES) VMEM f32 per-row
    # scales, constant across the grid — precedes b_ref in ``rest``. Then:
    #   b_ref,     (bk, bn) VMEM — pipelined B tile
    #   out_ref,   (m, n) VMEM — full reduced product (flushed once, at end)
    #   gather_buf, (world, m, n) f32 ANY — symmetric landing zones (dummy)
    #   status_ref, SMEM (STATUS_WORDS,) bounded-wait abort record
    # With ``trace`` set, its SMEM event buffer follows status_ref (the last
    # output); then the scratch operands below in order:
    #   acc,       VMEM (m, bn) f32
    #   stage,     VMEM (m, bn) f32 — finished tile staging (reused after wait)
    #   red,       VMEM (m, n) f32 — reduce accumulator
    #   tmp,       VMEM (m, n) f32 — per-slot staging for the reduce
    #   tile_sem,  DMA — stage → my landing-zone slot (waited inline)
    #   send_sem,  DMA — remote tile pushes (drained before reduce)
    #   recv_sem,  DMA (world,) — per-SOURCE slots: sender ``p`` signals slot p
    #   copy_sem,  DMA — slot → tmp during the reduce
    *rest,
    axis,
    mesh_axes,
    n_n: int,
    n_k: int,
    quant: bool = False,
    trace=None,
):
    """Fused low-latency GEMM-AR (grid ``(Nt, Kt)``): the partial GEMM's
    epilogue pushes each finished fp32 output tile straight into every peer's
    symmetric landing zone (reference multimem double-buffer phases,
    ``gemm_allreduce.py:44-831``), so later tiles' K-loops overlap earlier
    tiles' ICI pushes. The reducer waits per-source: ALL of a source's tile
    pushes land on that source's byte-counting semaphore slot, so one wait
    per peer covers its whole (m, n) contribution. fp32 on the wire → exact
    parity with the fp32-accum ``dot + psum`` reference."""
    rest = list(rest)
    a_scale_ref = rest.pop(0) if quant else None
    b_ref, out_ref, gather_buf, status_ref = (
        rest.pop(0), rest.pop(0), rest.pop(0), rest.pop(0)
    )
    ev_ref = rest.pop(0) if trace is not None else None
    acc, stage, red, tmp, tile_sem, send_sem, recv_sem, copy_sem = rest
    jn, kk = pl.program_id(0), pl.program_id(1)
    me = tpl.rank(axis)
    world = tpl.num_ranks(axis)

    @pl.when(jnp.logical_and(jn == 0, kk == 0))
    def _():
        sk.init_status(status_ref, axis=axis)
        if trace is not None:
            trace.init(ev_ref, rank=me)
            trace.mark(ev_ref, 0, profiler.TAG_BARRIER, 0)
        # Peers may still be in a previous kernel using gather_buf (or a
        # previous call of this one); rendezvous before the first push.
        sk.bounded_barrier_all(
            status_ref, axis, mesh_axes=mesh_axes, phase="barrier"
        )
        if trace is not None:
            trace.mark(ev_ref, 0, profiler.TAG_BARRIER, 1)

    @pl.when(kk == 0)
    def _():
        # Compute-step entry: one mark per output tile's K-loop start — the
        # ordering evidence that tile jn's GEMM ran before/after peers'
        # pushes (the overlap claim the LL design makes).
        if trace is not None:
            trace.mark(ev_ref, jn, profiler.TAG_COMPUTE, kk)
        acc[...] = jnp.zeros_like(acc)

    a_panel = a_ref[...]
    if quant:
        # Dequantize the full-M panel during the VMEM consume — exact
        # power-of-two ``q * scale`` in f32, cast to the weight dtype. The
        # fp32 landing-zone wire is unchanged; only A arrives quantized.
        a_panel = (a_panel.astype(jnp.float32) * a_scale_ref[:, :1]).astype(
            b_ref.dtype
        )
    acc[...] += jax.lax.dot_general(
        a_panel, b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == n_k - 1)
    def _tile_done():
        m, bn = acc.shape
        # Land the finished tile in MY slot locally (remote DMA sources from
        # HBM, and my slot doubles as my own contribution in the reduce)...
        stage[...] = acc[...]
        dst = gather_buf.at[me, :, pl.ds(jn * bn, bn)]
        cp = pltpu.make_async_copy(stage, dst, tile_sem)
        cp.start()
        cp.wait()

        # ... then push it to every peer's slot ``me`` — per-tile epilogue
        # sends, skew-started so links stay balanced. The sender signals the
        # DESTINATION's recv slot ``me``: per-source accounting.
        def send(i, _):
            peer = jax.lax.rem(me + i, world)
            if trace is not None:
                trace.mark(ev_ref, jn, profiler.TAG_SEND, peer)
            tpl.putmem_signal(
                dst, dst, send_sem, recv_sem.at[me], peer,
                axis=axis, mesh_axes=mesh_axes,
            ).start()
            return 0

        jax.lax.fori_loop(1, world, send, 0)

    is_last = jnp.logical_and(jn == n_n - 1, kk == n_k - 1)

    @pl.when(is_last)
    def _reduce():
        m, bn = acc.shape

        # Per-source waits: source src's n_n tile pushes sum to one full
        # (m, n) f32 slot on its semaphore — so a timeout names the exact
        # peer whose contribution never arrived.
        def wait_one(i, _):
            src = jax.lax.rem(me + i, world)
            if trace is not None:
                trace.mark(ev_ref, i, profiler.TAG_WAIT, src)
            sk.bounded_wait_recv(
                recv_sem.at[src], gather_buf.at[src], status_ref,
                phase="fanin_recv", peer=src,
            )
            if trace is not None:
                trace.mark(ev_ref, i, profiler.TAG_RECV, src)
            return 0

        jax.lax.fori_loop(1, world, wait_one, 0)

        # Drain my own sends: n_n tiles × (world-1) peers, all tile-sized.
        def drain(i, _):
            pltpu.make_async_copy(stage, stage, send_sem).wait()
            return 0

        jax.lax.fori_loop(0, n_n * (world - 1), drain, 0)

        # Local reduce in slot order 0..world-1 (HBM slots → VMEM → fp32
        # accumulate; HBM refs cannot be loaded directly by the VPU).
        red[...] = jnp.zeros_like(red)

        def add(i, _):
            cp2 = pltpu.make_async_copy(gather_buf.at[i], tmp, copy_sem)
            cp2.start()
            cp2.wait()
            red[...] += tmp[...]
            return 0

        jax.lax.fori_loop(0, world, add, 0)
        out_ref[...] = red[...].astype(out_ref.dtype)
        if trace is not None:
            trace.mark(ev_ref, 1, profiler.TAG_BARRIER, 0)
        sk.bounded_barrier_all(
            status_ref, axis, mesh_axes=mesh_axes, phase="exit_barrier"
        )
        if trace is not None:
            trace.mark(ev_ref, 1, profiler.TAG_BARRIER, 1)


def gemm_ar_ll_call(a, b, *, axis, mesh_axes=None, config=None):
    """Direct entry to the fused low-latency GEMM-AR kernel, bypassing AUTO
    routing and ``gemm_ar_shard``'s world==1 dot shortcut — lets the
    decode-size bench time the KERNEL itself at world=1 (pushes degenerate
    to the local landing-zone copy; the measured time is the kernel-overhead
    floor, symmetric with ``allreduce.one_shot_ar_call``)."""
    world = jax.lax.axis_size(axis)
    quant = _is_quant(a)
    a_q = a.q if quant else a
    out_dt = b.dtype if quant else a.dtype
    m, k = a_q.shape
    n = b.shape[1]
    cfg = config or GemmConfig(512, 512, 1024)
    bn = fit_block(n, cfg.block_n)
    bk = fit_block(k, cfg.block_k)
    n_n, n_k = n // bn, k // bk
    kernel_name = "_gemm_ar_ll_kernel" + ("_quant" if quant else "")

    trace = telemetry.maybe_kernel_trace()
    out_specs = [
        # Constant index map: the block is revisited, written once at the
        # last grid cell, flushed once after it.
        pl.BlockSpec((m, n), lambda jn, kk: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        sk.status_out_spec(),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((m, n), out_dt),
        jax.ShapeDtypeStruct((world, m, n), jnp.float32),
        sk.status_out_shape(),
    ]
    if trace is not None:
        out_specs.append(trace.out_spec())
        out_shape.append(trace.out_shape)
    in_specs = [pl.BlockSpec((m, bk), lambda jn, kk: (0, kk))]
    if quant:
        # Whole-panel scales, constant across the (Nt, Kt) grid — the LL
        # kernel keeps the full M rows resident, so the scales do too.
        in_specs.append(pl.BlockSpec((m, SCALE_LANES), lambda jn, kk: (0, 0)))
    in_specs.append(pl.BlockSpec((bk, bn), lambda jn, kk: (kk, jn)))
    operands = (a_q, a.scale, b) if quant else (a_q, b)
    out, _, status, *ev = dist_pallas_call(
        functools.partial(
            _gemm_ar_ll_kernel, axis=axis, mesh_axes=mesh_axes, n_n=n_n, n_k=n_k,
            quant=quant, trace=trace,
        ),
        grid=(n_n, n_k),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[
            pltpu.VMEM((m, bn), jnp.float32),
            pltpu.VMEM((m, bn), jnp.float32),
            pltpu.VMEM((m, n), jnp.float32),
            pltpu.VMEM((m, n), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((world,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True,
            collective_id=collective_id_for(kernel_name),
        ),
    )(*operands)
    resilience.consume_status(status, feature="gemm_ar", kernel=kernel_name)
    if trace is not None:
        telemetry.consume_kernel_trace(trace, ev[0], kernel=kernel_name)
    return out


def gemm_ar_shard(
    a: jax.Array,  # (m, k_shard)
    b: jax.Array,  # (k_shard, n)
    *,
    axis: str = "tp",
    mesh_axes=None,
    method: GemmARMethod = GemmARMethod.AUTO,
    gemm_config: GemmConfig | None = None,
) -> jax.Array:
    """``all_reduce(A_local @ B_local)`` — every rank gets the full (m, n)
    product. Usable inside shard_map. Reference host ops
    ``gemm_ar_op``/``ll_gemm_ar_op`` (``gemm_allreduce.py:660,:722``)."""
    world = jax.lax.axis_size(axis)
    quant = _is_quant(a)
    out_dt = b.dtype if quant else a.dtype
    m = a.q.shape[0] if quant else a.shape[0]
    if world == 1:
        a1 = _dequant_chunk(a.q, a.scale, b.dtype) if quant else a
        return jnp.dot(a1, b, preferred_element_type=jnp.float32).astype(out_dt)
    if quant:
        # AR wire stays fp32 partials: no wire_hops — the win is the
        # quantized A operand's HBM/VMEM footprint.
        note_quant_dispatch("gemm_ar", a, world)
    if method is GemmARMethod.AUTO:
        method = get_auto_gemm_ar_method(m, world, wire=a.wire if quant else None)

    if method is GemmARMethod.XLA:
        a1 = _dequant_chunk(a.q, a.scale, b.dtype) if quant else a
        partial = jnp.dot(a1, b, preferred_element_type=jnp.float32)
        return jax.lax.psum(partial, axis).astype(out_dt)

    if method is GemmARMethod.LL_ONE_SHOT:
        return gemm_ar_ll_call(
            a, b, axis=axis, mesh_axes=mesh_axes, config=gemm_config
        )

    if method is GemmARMethod.PALLAS_FUSED:
        return _gemm_ar_fused(a, b, axis=axis, mesh_axes=mesh_axes, config=gemm_config)

    if method is GemmARMethod.ONE_SHOT:
        a1 = _dequant_chunk(a.q, a.scale, b.dtype) if quant else a
        partial = jnp.dot(a1, b, preferred_element_type=jnp.float32).astype(out_dt)
        return all_reduce_shard(
            partial, axis=axis, mesh_axes=mesh_axes, method=AllReduceMethod.ONE_SHOT
        )

    # RS_AG: _gemm_rs_xla_ring handles a quantized A itself.
    scattered = _gemm_rs_xla_ring(a, b, axis=axis)
    gathered = all_gather_shard(
        scattered, axis=axis, mesh_axes=mesh_axes, method=AllGatherMethod.RING_1D
    )
    return gathered.reshape(m, b.shape[1])


def gemm_ar(ar_ctx: GemmARContext, a: jax.Array, b: jax.Array) -> jax.Array:
    """Standalone host op: A sharded on cols, B sharded on rows; returns the
    replicated full product."""
    axis = ar_ctx.axis
    mesh_axes = ar_ctx.ctx.axis_names

    def fn(a_shard, b_shard):
        return gemm_ar_shard(
            a_shard, b_shard, axis=axis, mesh_axes=mesh_axes, method=ar_ctx.method,
            gemm_config=ar_ctx.gemm_config,
        )

    shard_f = jax.shard_map(
        fn,
        mesh=ar_ctx.ctx.mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(shard_f)(a, b)
