"""Tensor-parallel layers: attention, MLP, MoE (+ RMSNorm).

Reference: ``layers/nvidia/tp_attn.py:80-321``, ``tp_mlp.py:52-270``,
``tp_moe.py:48-279``. Weight layout (per rank, inside shard_map):

* ``TP_Attn``: ``wqkv`` (d, (hq+2·hkv)_local·hd) column-shard — heads split
  over tp; ``wo`` (hq_local·hd, d) row-shard.
* ``TP_MLP``: ``w_gate``/``w_up`` (d, ff_local) column-shards; ``w_down``
  (ff_local, d) row-shard.

Forward modes: ``xla`` — plain matmuls + psum/psum_scatter (compiler
collectives); ``dist`` — AG-GEMM + GEMM-RS overlapped path (x arrives
sequence-sharded, returns sequence-sharded); ``dist_ar`` — GEMM-AR replicated
path (x replicated, decode regime). Mode per call, like the reference's
``set_fwd`` switch.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.allgather_gemm import (
    ag_gemm_shard,
    ag_gemm_swiglu_shard,
    AGGemmMethod,
)
from triton_dist_tpu.kernels.gemm_reduce_scatter import gemm_rs_shard, GemmRSMethod
from triton_dist_tpu.kernels.gemm_allreduce import gemm_ar_shard, GemmARMethod
from triton_dist_tpu.kernels.flash_attn import flash_attention
from triton_dist_tpu.kernels.flash_decode import (
    flash_decode,
    paged_flash_decode,
    paged_kv_append,
)
from triton_dist_tpu.kernels.moe_utils import (
    capacity_for,
    make_routing_plan,
    dispatch,
    combine,
    topk_routing,
)
from triton_dist_tpu.kernels.group_gemm import group_gemm
from triton_dist_tpu.runtime import resilience, telemetry


def _tp_mode(mode: str) -> str:
    """Degraded-mode remap for the per-call forward switch (trace time).

    Once any collective is marked degraded (bounded-wait abort or watchdog
    trip), ``dist_ar`` calls run as ``xla`` — the two modes share the
    replicated-input contract, so the swap is transparent to callers.
    ``dist`` takes SEQUENCE-SHARDED inputs (a different data contract), so
    it is NOT remapped here; its collectives degrade kernel-by-kernel via
    their own routing gates."""
    resolved = mode
    if mode == "dist_ar" and resilience.any_degraded():
        resilience.note_fallback_once(
            "layers.tp", "running dist_ar layers on the xla backend"
        )
        resolved = "xla"
    telemetry.inc(
        "tdt_layers_tp_mode_total", requested=mode, resolved=resolved
    )
    return resolved


def _pytree_dataclass(cls):
    cls = dataclasses.dataclass(cls)
    fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    meta = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=meta)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@_pytree_dataclass
class RMSNorm:
    """RMSNorm (reference models use Qwen3 RMSNorm semantics)."""

    weight: jax.Array  # (d,)
    eps: float = static_field(default=1e-6)

    def __call__(self, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * self.weight


def apply_rope(x: jax.Array, pos: jax.Array, theta: float = 1e6) -> jax.Array:
    """Rotary embedding, interleaved-half convention (reference
    ``apply_rotary_pos_emb`` ``tp_attn.py:165``; Qwen3 uses rotate-half).

    x: (B, H, S, D); pos: (B, S) absolute positions."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    xr1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
    xr2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


@_pytree_dataclass
class TP_MLP:
    """Reference ``TP_MLP`` (``tp_mlp.py:52``)."""

    w_gate: jax.Array  # (d, ff_local)
    w_up: jax.Array  # (d, ff_local)
    w_down: jax.Array  # (ff_local, d)
    axis: str = static_field(default="tp")
    mesh_axes: tuple | None = static_field(default=None)

    def __call__(self, x: jax.Array, mode: str = "dist") -> jax.Array:
        """x: (m_shard, d) for 'dist' (seq-sharded), (m, d) for
        'xla'/'dist_ar' (replicated input). Output matches input sharding."""
        mode = _tp_mode(mode)
        axis = self.axis
        if mode == "xla":
            g = jnp.dot(x, self.w_gate, preferred_element_type=jnp.float32)
            u = jnp.dot(x, self.w_up, preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(x.dtype)
            out = jnp.dot(h, self.w_down, preferred_element_type=jnp.float32)
            return jax.lax.psum(out, axis).astype(x.dtype)
        if mode == "dist":
            # One AG pass feeding BOTH gate and up chunk-GEMMs with a fused
            # SwiGLU (x seq-sharded), then GEMM-RS down — no unoverlapped
            # matmul anywhere in the MLP. Both AUTO-route by their tuned
            # crossovers (ag_gemm_crossover / gemm_rs_crossover): prefill
            # shards take the one-kernel gather→matmul→gate fused path.
            h = ag_gemm_swiglu_shard(
                x, self.w_gate, self.w_up, axis=axis, mesh_axes=self.mesh_axes
            )
            return gemm_rs_shard(h, self.w_down, axis=axis, mesh_axes=self.mesh_axes)
        if mode == "dist_ar":
            g = jnp.dot(x, self.w_gate, preferred_element_type=jnp.float32)
            u = jnp.dot(x, self.w_up, preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(x.dtype)
            # Row-parallel down-proj through GEMM-AR AUTO: decode-sized or
            # ragged token counts take the fused ll_one_shot kernel, larger
            # batches the fused RS+AG ring (gemm_allreduce crossover).
            return gemm_ar_shard(h, self.w_down, axis=axis, mesh_axes=self.mesh_axes)
        raise ValueError(f"unknown mode {mode}")


@_pytree_dataclass
class TP_Attn:
    """Reference ``TP_Attn`` (``tp_attn.py:80``): QKV proj → RoPE → flash
    attention / decode → O proj, head-sharded over tp."""

    wqkv: jax.Array  # (d, (hq_l + 2*hkv_l) * hd)
    wo: jax.Array  # (hq_l * hd, d)
    q_norm: RMSNorm | None  # per-head-dim q/k norms (Qwen3)
    k_norm: RMSNorm | None
    num_q_heads_local: int = static_field(default=0)
    num_kv_heads_local: int = static_field(default=0)
    head_dim: int = static_field(default=128)
    rope_theta: float = static_field(default=1e6)
    axis: str = static_field(default="tp")
    mesh_axes: tuple | None = static_field(default=None)

    def _split_qkv(self, qkv: jax.Array, bsz: int, seq: int):
        hq, hkv, hd = self.num_q_heads_local, self.num_kv_heads_local, self.head_dim
        qkv = qkv.reshape(bsz, seq, (hq + 2 * hkv), hd)
        q = qkv[:, :, :hq]
        k = qkv[:, :, hq : hq + hkv]
        v = qkv[:, :, hq + hkv :]
        if self.q_norm is not None:
            q = self.q_norm(q)
        if self.k_norm is not None:
            k = self.k_norm(k)
        # (B, H, S, D)
        return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def prefill(self, x: jax.Array, pos: jax.Array, mode: str = "dist", bsz: int = 1):
        """x: (bsz·seq[_shard], d) tokens; pos: (bsz, seq) positions.
        Returns (out, (k, v)) — out sharded like x, k/v local heads (B,H,S,D).
        """
        mode = _tp_mode(mode)
        axis = self.axis
        seq = pos.shape[1]
        if mode == "dist":
            qkv, _ = ag_gemm_shard(x, self.wqkv, axis=axis, mesh_axes=self.mesh_axes, return_gathered=True)
        elif mode in ("xla", "dist_ar"):
            qkv = jnp.dot(x, self.wqkv, preferred_element_type=jnp.float32).astype(x.dtype)
        else:
            raise ValueError(mode)
        q, k, v = self._split_qkv(qkv, bsz, seq)
        q = apply_rope(q, pos, self.rope_theta)
        k = apply_rope(k, pos, self.rope_theta)
        o = flash_attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(bsz * seq, -1)
        if mode == "dist":
            out = gemm_rs_shard(o, self.wo, axis=axis, mesh_axes=self.mesh_axes)
        elif mode == "xla":
            out = jax.lax.psum(
                jnp.dot(o, self.wo, preferred_element_type=jnp.float32), axis
            ).astype(x.dtype)
        else:
            out = gemm_ar_shard(o, self.wo, axis=axis, mesh_axes=self.mesh_axes)
        return out, (k, v)

    def prefill_chunk(self, x, pos, k_buf, v_buf, off, mode: str = "dist_ar",
                      bsz: int = 1):
        """One prefill CHUNK against a running per-request KV buffer.

        x: (bsz·C, d) replicated chunk tokens; pos: (bsz, C) absolute
        positions (``off + arange(C)``); ``k_buf``/``v_buf``: (B, Hkv_l, P,
        D) context buffers holding every previously prefilled row of this
        prompt; ``off``: traced int32 chunk start. Inserts the chunk's K/V
        rows at ``off + arange(C)`` (``mode="drop"`` — a partial final
        chunk's padding rows index past P and must vanish, where a clamping
        ``dynamic_update_slice`` would overwrite real rows) and attends the
        chunk's queries over the WHOLE buffer with the dynamic-offset causal
        mask (``q_offset=off``): rows past ``off + C`` are zeros but sit in
        the causal future, so they never contribute. Replicated modes only
        (``xla``/``dist_ar``) — chunks are decode-regime sized, the
        seq-sharded ``dist`` contract does not apply. Returns
        (out (bsz·C, d), (k_buf, v_buf) updated)."""
        mode = _tp_mode(mode)
        if mode not in ("xla", "dist_ar"):
            raise ValueError(f"prefill_chunk supports xla/dist_ar, got {mode}")
        seq = pos.shape[1]
        qkv = jnp.dot(x, self.wqkv, preferred_element_type=jnp.float32).astype(x.dtype)
        q, k, v = self._split_qkv(qkv, bsz, seq)
        q = apply_rope(q, pos, self.rope_theta)
        k = apply_rope(k, pos, self.rope_theta)
        idx = off + jnp.arange(seq, dtype=jnp.int32)
        k_buf = k_buf.at[:, :, idx].set(k, mode="drop")
        v_buf = v_buf.at[:, :, idx].set(v, mode="drop")
        o = flash_attention(
            q, k_buf, v_buf, causal=True,
            q_offset=off.astype(jnp.int32), kv_offset=jnp.int32(0),
        )
        o = o.transpose(0, 2, 1, 3).reshape(bsz * seq, -1)
        if mode == "xla":
            out = jax.lax.psum(
                jnp.dot(o, self.wo, preferred_element_type=jnp.float32), self.axis
            ).astype(x.dtype)
        else:
            out = gemm_ar_shard(o, self.wo, axis=self.axis, mesh_axes=self.mesh_axes)
        return out, (k_buf, v_buf)

    def decode(self, x, pos, k_cache, v_cache, lengths, mode: str = "dist_ar"):
        """One-token decode. x: (bsz, d) replicated; pos: (bsz,) positions;
        caches (B, Hkv_l, S, D) fixed-size. Writes the new k/v into the cache
        at ``lengths`` (static shapes — the XLA analog of the reference's
        CUDA-graph-safe ``KV_Cache.inc_offset``) and returns
        (out (bsz, d) replicated, (k_cache, v_cache) updated)."""
        mode = _tp_mode(mode)
        q, k, v = self._decode_qkv(x, pos)
        batch_ids = jnp.arange(x.shape[0])
        k_cache = k_cache.at[batch_ids, :, lengths].set(k)
        v_cache = v_cache.at[batch_ids, :, lengths].set(v)
        o = flash_decode(
            q, k_cache, v_cache, lengths + 1,
            block_k=min(256, k_cache.shape[2]),
        )
        return self._decode_out(o, mode, x.dtype), (k_cache, v_cache)

    def decode_paged(self, x, pos, pk, pv, layer, tables, lengths, active,
                     mode: str = "dist_ar"):
        """``decode`` against the block pool where it lies. ``pk``/``pv``
        are the STACKED pools (L, num_blocks, Hkv_l, bs, D), or ``QuantPool``
        pairs, and ``layer`` this layer's index as data: the one new K/V row
        of each slot is written through ``tables`` (an inactive slot's to
        the NULL block) and attention reads K/V through the table inside the
        kernel, at ``decode``'s tile, so the two agree bit for bit. Nothing
        of the pool's size, nor of one layer's slice of it, is copied.
        Returns (out (bsz, d) replicated, (pk, pv) updated)."""
        mode = _tp_mode(mode)
        q, k, v = self._decode_qkv(x, pos)
        pk, pv = paged_kv_append(pk, pv, layer, k, v, tables, lengths, active)
        o = paged_flash_decode(
            q, pk, pv, tables, lengths + active.astype(lengths.dtype),
            layer=layer, block_k=256,
        )
        return self._decode_out(o, mode, x.dtype), (pk, pv)

    def _decode_qkv(self, x, pos):
        """One decode step's projection: roped q (B, Hq_l, D) and k, and v,
        (B, Hkv_l, D) for the token each slot holds at ``pos``."""
        qkv = jnp.dot(x, self.wqkv, preferred_element_type=jnp.float32).astype(x.dtype)
        q, k, v = self._split_qkv(qkv, x.shape[0], 1)
        q = apply_rope(q, pos[:, None], self.rope_theta)
        k = apply_rope(k, pos[:, None], self.rope_theta)
        return q[:, :, 0], k[:, :, 0], v[:, :, 0]

    def _decode_out(self, o, mode: str, dtype):
        """The decode step's o-projection and its reduction over tp."""
        o = o.reshape(o.shape[0], -1)
        if mode == "dist_ar":
            # bsz rows is decode-tiny (≤ the M crossover), so AUTO lands on
            # the fused ll_one_shot GEMM-AR kernel here.
            return gemm_ar_shard(o, self.wo, axis=self.axis, mesh_axes=self.mesh_axes)
        if mode == "xla":
            return jax.lax.psum(
                jnp.dot(o, self.wo, preferred_element_type=jnp.float32), self.axis
            ).astype(dtype)
        raise ValueError(f"decode supports xla/dist_ar, got {mode}")


#: Shared TP-MoE routing capacity factor — governs BOTH prefill and decode
#: (DenseLLM._mlp serves both) and the mega backend's moe task: every caller
#: must route tokens identically or backends diverge on dropped tokens.
MOE_CAPACITY_FACTOR = 2.0

#: Backwards-compatible alias (pre-r3 name).
DECODE_MOE_CAPACITY_FACTOR = MOE_CAPACITY_FACTOR


@_pytree_dataclass
class TP_MoE:
    """Tensor-parallel MoE: experts replicated across ranks, the ff dim of
    every expert column-sharded (reference ``TP_MoE`` ``tp_moe.py:48`` with
    ag-moe + moe-rs contexts). Routing is computed identically on all ranks;
    the down-projection partial sums reduce over tp."""

    w_router: jax.Array  # (d, E)
    w_gate: jax.Array  # (E, d, ff_local)
    w_up: jax.Array  # (E, d, ff_local)
    w_down: jax.Array  # (E, ff_local, d)
    top_k: int = static_field(default=8)
    capacity_factor: float = static_field(default=1.5)
    axis: str = static_field(default="tp")
    mesh_axes: tuple | None = static_field(default=None)

    def __call__(self, x: jax.Array, mode: str = "dist_ar") -> jax.Array:
        """Modes (matching the reference ag-moe / moe-rs / moe-ar contexts):

        * ``xla`` — x (T, d) replicated → (T, d) replicated; plain grouped
          GEMMs + psum (compiler-collective baseline).
        * ``dist_ar`` — x (T, d) replicated → (T, d) replicated; chunked
          ring-RS overlapped with the down grouped GEMMs + final AG
          (``moe_reduce_ar`` analog). Falls back to grouped-GEMM + one-sided
          AR when T isn't divisible by world.
        * ``dist`` — x (Tc, d) **seq-sharded** → (Tc, d) seq-sharded; the
          fully overlapped AG-MoE → MoE-RS ring pair
          (``allgather_group_gemm`` + ``moe_reduce_rs`` analog).

        Capacity semantics: the chunked ring paths apply the capacity limit
        **per token chunk** (GShard/Switch-style per-group capacity — the
        idiomatic TPU MoE contract), so under capacity pressure they drop
        different tokens than the global-capacity ``xla``/fallback paths.
        With ample capacity (no drops) all modes agree exactly.
        """
        from triton_dist_tpu.kernels.moe_comm import tp_moe_ar_shard, tp_moe_rs_shard

        mode = _tp_mode(mode)
        world = jax.lax.axis_size(self.axis)
        t, d = x.shape
        from triton_dist_tpu.kernels.moe_utils import CAPACITY_ALIGN

        if mode == "dist":
            if t < CAPACITY_ALIGN:
                # Tiny seq-shards: per-chunk capacity padding (align-up to
                # CAPACITY_ALIGN) would multiply the grouped-GEMM work —
                # gather once, run the replicated path, take my chunk back.
                x_full = jax.lax.all_gather(x, self.axis, tiled=True)
                out_full = self(x_full, mode="dist_ar")
                me = jax.lax.axis_index(self.axis)
                return jax.lax.dynamic_slice(out_full, (me * t, 0), (t, d))
            return tp_moe_rs_shard(
                x, self.w_router, self.w_gate, self.w_up, self.w_down,
                top_k=self.top_k, capacity_factor=self.capacity_factor,
                axis=self.axis,
            )
        # Chunked AR only when per-chunk tokens are large enough that the
        # capacity padding doesn't multiply the grouped-GEMM work
        # (small-T decode stays on the unchunked grouped-GEMM + AR path).
        if mode == "dist_ar" and t % world == 0 and t // world >= CAPACITY_ALIGN:
            return tp_moe_ar_shard(
                x, self.w_router, self.w_gate, self.w_up, self.w_down,
                top_k=self.top_k, capacity_factor=self.capacity_factor,
                axis=self.axis,
            )

        e = self.w_router.shape[1]
        logits = jnp.dot(x, self.w_router, preferred_element_type=jnp.float32)
        idx, w = topk_routing(logits, self.top_k)
        cap = capacity_for(t, self.top_k, e, self.capacity_factor)
        plan = make_routing_plan(idx, e, cap)
        xe = dispatch(x, plan)  # (E, C, d)
        from triton_dist_tpu.kernels.group_gemm import group_gemm_swiglu

        if mode == "xla":
            g = group_gemm(xe, self.w_gate)
            u = group_gemm(xe, self.w_up)
            h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
        else:
            h = group_gemm_swiglu(xe, self.w_gate, self.w_up)
        y = group_gemm(h, self.w_down)  # (E, C, d) partial over tp (ff shard)
        # fp32 partials on the wire in every mode: bf16-rounded per-rank
        # partials would make dist_ar diverge from the fp32 psum baseline.
        out = combine(y, plan, w, t, out_dtype=jnp.float32)
        if mode == "xla":
            return jax.lax.psum(out, self.axis).astype(x.dtype)
        from triton_dist_tpu.kernels.allreduce import all_reduce_shard, AllReduceMethod

        return all_reduce_shard(
            out, axis=self.axis, mesh_axes=self.mesh_axes, method=AllReduceMethod.AUTO
        ).astype(x.dtype)
