"""Qwen3-class dense LLM (and the MoE variant) — SPMD forward over a mesh.

Reference: ``python/triton_dist/models/dense.py:117`` (``DenseLLM``, per-layer
``set_fwd`` mode switch :84, per-mode ctx init :169-201) and
``qwen_moe.py:108`` (``Qwen3MoE``). TPU redesign:

* One parameter pytree with **stacked layers** (leading L dim) so the whole
  depth compiles as one ``lax.scan`` — the XLA analog of the reference's
  CUDA-graph capture (``engine.py:75``): trace once, replay forever.
* The forward runs inside a single ``shard_map`` over the tp axis; per-mode
  behavior matches the reference backends: ``xla`` (= torch eager),
  ``dist`` (AG-GEMM + GEMM-RS overlapped), ``dist_ar`` (GEMM-AR decode path).
* KV caches are fixed-shape (L, B, Hkv_local, S_max, D) arrays donated
  through jit — in-place on TPU.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import kv_rows
from triton_dist_tpu.layers.tp import MOE_CAPACITY_FACTOR, TP_Attn, TP_MLP, TP_MoE, RMSNorm, _pytree_dataclass, static_field
from triton_dist_tpu.runtime.mesh import DistContext


@_pytree_dataclass
class DenseParams:
    """Stacked-layer parameter pytree (arrays are global, mesh-sharded)."""

    embed: jax.Array  # (V, d) replicated
    ln1: jax.Array  # (L, d)
    wqkv: jax.Array  # (L, d, (hq_l+2hkv_l)*hd · world) — col-sharded on tp
    wo: jax.Array  # (L, hq·hd, d) — row-sharded on tp
    q_norm: jax.Array  # (L, hd) (Qwen3 per-head RMS) or ones
    k_norm: jax.Array  # (L, hd)
    ln2: jax.Array  # (L, d)
    mlp_gate: jax.Array  # dense: (L, d, ff) col-sharded | moe: (L, E, d, ff_e)
    mlp_up: jax.Array
    mlp_down: jax.Array  # dense: (L, ff, d) row-sharded | moe: (L, E, ff_e, d)
    router: jax.Array | None  # moe only: (L, d, E)
    final_norm: jax.Array  # (d,)
    lm_head: jax.Array  # (d, V) col-sharded


def _specs(config: ModelConfig) -> DenseParams:
    """PartitionSpec pytree matching DenseParams over a ("tp",) mesh."""
    moe = config.is_moe
    return DenseParams(
        embed=P(),
        ln1=P(),
        wqkv=P(None, None, "tp"),
        wo=P(None, "tp", None),
        q_norm=P(),
        k_norm=P(),
        ln2=P(),
        mlp_gate=P(None, None, None, "tp") if moe else P(None, None, "tp"),
        mlp_up=P(None, None, None, "tp") if moe else P(None, None, "tp"),
        mlp_down=P(None, None, "tp", None) if moe else P(None, "tp", None),
        router=P() if moe else None,
        final_norm=P(),
        lm_head=P(None, "tp"),
    )


def _build_params(config: ModelConfig, key: jax.Array) -> DenseParams:
    """The random init as one traceable function (see ``init_params``)."""
    c = config
    dt = jnp.dtype(c.dtype)
    L, d, hd = c.num_layers, c.hidden_size, c.head_dim
    qkv_cols = (c.num_q_heads + 2 * c.num_kv_heads) * hd
    keys = jax.random.split(key, 8)

    def mk(k, shape, scale=None):
        scale = scale if scale is not None else (1.0 / math.sqrt(shape[-2] if len(shape) > 1 else shape[-1]))
        x = jax.random.normal(k, shape, jnp.float32)
        if dt == jnp.float32:
            # Fused, XLA folds normal()'s own sqrt(2) factor into `scale` and
            # lands 1 ulp off the op-by-op route this replaced. float32
            # models are the toy presets whose values the parity tests pin,
            # so they pay a transient copy for the old rounding order; in
            # bfloat16 the whole draw stays one fusion with no float32
            # tensor in memory (4.5 GB of temp at 24 Qwen3-8B layers
            # otherwise).
            x = jax.lax.optimization_barrier(x)
        return (x * scale).astype(dt)

    if c.is_moe:
        e, ffe = c.num_experts, c.moe_intermediate_size
        mlp_gate = mk(keys[3], (L, e, d, ffe))
        mlp_up = mk(keys[4], (L, e, d, ffe))
        mlp_down = mk(keys[5], (L, e, ffe, d))
        router = mk(keys[6], (L, d, e), scale=0.02)
    else:
        ff = c.intermediate_size
        mlp_gate = mk(keys[3], (L, d, ff))
        mlp_up = mk(keys[4], (L, d, ff))
        mlp_down = mk(keys[5], (L, ff, d))
        router = None

    return DenseParams(
        embed=mk(keys[0], (c.vocab_size, d), scale=0.02),
        ln1=jnp.ones((L, d), dt),
        wqkv=mk(keys[1], (L, d, qkv_cols)),
        wo=mk(keys[2], (L, c.num_q_heads * hd, d)),
        q_norm=jnp.ones((L, hd), dt),
        k_norm=jnp.ones((L, hd), dt),
        ln2=jnp.ones((L, d), dt),
        mlp_gate=mlp_gate,
        mlp_up=mlp_up,
        mlp_down=mlp_down,
        router=router,
        final_norm=jnp.ones((d,), dt),
        lm_head=mk(keys[7], (d, c.vocab_size)),
    )


def init_params(config: ModelConfig, key: jax.Array, ctx: DistContext,
                specs: DenseParams | None = None) -> DenseParams:
    """Random init, created ON the mesh (test/bench weights; real weights
    come from ``AutoLLM``/HF loading, ``models/__init__.py``): one jitted
    program whose ``out_shardings`` are the placement specs, so each device
    draws only its own shard (the partitionable threefry PRNG makes the
    values independent of the sharding) in the target dtype — nothing of
    full size is ever resident on one device or on the host.
    ``specs`` overrides the placement pytree — the EP MoE model passes its
    expert-sharded layout (``models/moe.py:ep_specs``) so each rank holds
    ``(E_local, …)`` expert slabs instead of ffe-sharded slices."""
    specs = specs if specs is not None else _specs(config)
    shardings = jax.tree.map(
        lambda s: ctx.sharding(*s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.jit(
        partial(_build_params, config), out_shardings=shardings
    )(key)


class DenseLLM:
    """Qwen3-dense-style model. ``Qwen3MoE`` below shares the machinery with
    MoE MLP blocks (reference keeps two classes; the forward here switches on
    ``config.is_moe``)."""

    def __init__(self, config: ModelConfig, ctx: DistContext, params: DenseParams | None = None, key=None):
        self.config = config
        self.ctx = ctx
        self.axis = "tp"
        self.world = ctx.num_ranks(self.axis)
        assert config.num_q_heads % self.world == 0
        assert config.num_kv_heads % self.world == 0
        if params is None:
            params = init_params(config, key if key is not None else jax.random.PRNGKey(0), ctx)
        self.params = params

    def cache_rows(self):
        """The two kinds of cache row a token keeps (the engine sizes the
        prompt buffers and the paged pools from this): K rows and V rows,
        alike, on every layer."""
        c = self.config
        return kv_rows(c.num_layers, c.num_kv_heads, c.head_dim)

    def step_stats(self):
        """Zeros of what the step programs return beside their result and
        sum over a chunk on the device; this model counts nothing there."""
        return ()

    def publish_step_stats(self, stats) -> None:
        """Host side, after a step program's fence: feed the counters from
        its ``stats``. Nothing to feed here."""

    # ------------------------------------------------------------ shard-local
    def _attn(self, lp, mode_decode=False) -> TP_Attn:
        c = self.config
        return TP_Attn(
            wqkv=lp["wqkv"],
            wo=lp["wo"],
            q_norm=RMSNorm(weight=lp["q_norm"], eps=c.rms_eps),
            k_norm=RMSNorm(weight=lp["k_norm"], eps=c.rms_eps),
            num_q_heads_local=c.num_q_heads // self.world,
            num_kv_heads_local=c.num_kv_heads // self.world,
            head_dim=c.head_dim,
            rope_theta=c.rope_theta,
            axis=self.axis,
            mesh_axes=self.ctx.axis_names,
        )

    def _mlp(self, lp):
        c = self.config
        if c.is_moe:
            return TP_MoE(
                w_router=lp["router"], w_gate=lp["mlp_gate"], w_up=lp["mlp_up"],
                w_down=lp["mlp_down"], top_k=c.top_k,
                capacity_factor=MOE_CAPACITY_FACTOR, axis=self.axis,
                mesh_axes=self.ctx.axis_names,
            )
        return TP_MLP(
            w_gate=lp["mlp_gate"], w_up=lp["mlp_up"], w_down=lp["mlp_down"],
            axis=self.axis, mesh_axes=self.ctx.axis_names,
        )

    def _layer_stack(self, p: DenseParams):
        lp = {
            "ln1": p.ln1, "wqkv": p.wqkv, "wo": p.wo, "q_norm": p.q_norm,
            "k_norm": p.k_norm, "ln2": p.ln2, "mlp_gate": p.mlp_gate,
            "mlp_up": p.mlp_up, "mlp_down": p.mlp_down,
        }
        if self.config.is_moe:
            lp["router"] = p.router
        return lp

    def prefill_shard(self, p: DenseParams, tokens: jax.Array, mode: str):
        """Inside shard_map. tokens (B, S) replicated → (last-token logits
        (B, V_local), stacked caches (L, B, Hkv_l, S, D))."""
        c = self.config
        bsz, seq = tokens.shape
        me = jax.lax.axis_index(self.axis)
        x = p.embed[tokens].reshape(bsz * seq, c.hidden_size)
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (bsz, seq))
        if mode == "dist":
            chunk = (bsz * seq) // self.world
            x = jax.lax.dynamic_slice(x, (me * chunk, 0), (chunk, x.shape[1]))

        eps = c.rms_eps

        def layer_fn(x, lp):
            attn = self._attn(lp)
            h = RMSNorm(weight=lp["ln1"], eps=eps)(x)
            a, (k, v) = attn.prefill(h, pos, mode=mode, bsz=bsz)
            x = x + a
            h = RMSNorm(weight=lp["ln2"], eps=eps)(x)
            if c.is_moe and mode == "dist":
                # Seq-sharded MoE: the AG-MoE → MoE-RS ring pair gathers
                # chunks into the gate/up grouped GEMMs and reduce-scatters
                # the down partials — no replicated compute, no full-T AR
                # (reference ag_moe + moe_rs contexts, tp_moe.py).
                m = self._mlp(lp)(h, mode="dist")
            elif c.is_moe:
                m = self._mlp(lp)(h, mode="xla" if mode == "xla" else "dist_ar")
            else:
                m = self._mlp(lp)(h, mode=mode)
            return x + m, (k, v)

        x, (ks, vs) = jax.lax.scan(
            lambda carry, lp: layer_fn(carry, lp), x, self._layer_stack(p)
        )
        x = RMSNorm(weight=p.final_norm, eps=eps)(x)
        if mode == "dist":
            # Gather the sequence back; last token logits only.
            x = jax.lax.all_gather(x, self.axis, tiled=True)
        x = x.reshape(bsz, seq, -1)[:, -1]
        logits = jnp.dot(x, p.lm_head, preferred_element_type=jnp.float32)
        return logits, (ks, vs)

    def prefill_chunk_shard(self, p: DenseParams, tokens: jax.Array, kbufs, vbufs,
                            off: jax.Array, last_idx: jax.Array, mode: str):
        """Inside shard_map. One chunk of an incremental prefill.

        tokens (B, C) replicated chunk; ``kbufs``/``vbufs`` (L, B, Hkv_l, P,
        D) running context buffers carried across chunks; ``off`` traced
        int32 absolute start of this chunk; ``last_idx`` traced int32 row
        (within the chunk) whose logits the caller wants — the prompt's
        final token on the last chunk, ignored elsewhere. Returns (logits
        (B, V_local), updated (kbufs, vbufs), ``step_stats``). Replicated modes only —
        chunks are small, so this rides the decode-regime collectives; the
        per-row math (RoPE at absolute positions, causal attention over the
        buffer, rowwise norms/MLP) matches ``prefill_shard`` row for row,
        which is what makes chunked prefill byte-parity with one-shot
        prefill testable rather than aspirational. (MoE capacity is the
        exception: routing is per-call, so an over-capacity MoE prefill may
        drop different tokens chunked vs one-shot.)"""
        c = self.config
        bsz, seq = tokens.shape
        x = p.embed[tokens].reshape(bsz * seq, c.hidden_size)
        pos = jnp.broadcast_to(
            off.astype(jnp.int32) + jnp.arange(seq, dtype=jnp.int32)[None], (bsz, seq)
        )
        eps = c.rms_eps

        def layer_fn(x, layer):
            lp, k_b, v_b = layer
            attn = self._attn(lp)
            h = RMSNorm(weight=lp["ln1"], eps=eps)(x)
            a, (k_b, v_b) = attn.prefill_chunk(
                h, pos, k_b, v_b, off, mode=mode, bsz=bsz
            )
            x = x + a
            h = RMSNorm(weight=lp["ln2"], eps=eps)(x)
            if c.is_moe:
                m = self._mlp(lp)(h, mode="xla" if mode == "xla" else "dist_ar")
            else:
                m = self._mlp(lp)(h, mode=mode)
            return x + m, (k_b, v_b)

        x, (kbufs, vbufs) = jax.lax.scan(
            lambda carry, layer: layer_fn(carry, layer),
            x, (self._layer_stack(p), kbufs, vbufs),
        )
        x = RMSNorm(weight=p.final_norm, eps=eps)(x)
        x = x.reshape(bsz, seq, -1)
        x_last = jax.lax.dynamic_slice(
            x, (0, jnp.clip(last_idx.astype(jnp.int32), 0, seq - 1), 0),
            (bsz, 1, x.shape[-1]),
        )[:, 0]
        logits = jnp.dot(x_last, p.lm_head, preferred_element_type=jnp.float32)
        return logits, (kbufs, vbufs), self.step_stats()

    def split_layer_params(self) -> list[dict]:
        """Materialize per-layer parameter dicts from the stacked pytree —
        ONCE, outside jit. The megakernel decode path needs this: a Pallas
        custom call can't consume a sliced view lazily, so slicing inside
        the decode loop would re-materialize every weight every token
        (measured 2.7× slower); pre-split buffers are read in place."""
        stack = self._layer_stack(self.params)
        return [
            jax.tree.map(lambda a: a[i], stack) for i in range(self.config.num_layers)
        ]

    def _mega_moe_impl(self):
        """Lowering callback for the graph's ``moe`` task, or None to use
        the builder's default (fused routed-experts TP path). The EP model
        overrides this to route its a2a decode path through the graph."""
        return None

    def _mega_builder(self, *, paged: bool = False):
        from triton_dist_tpu.megakernel.builder import ModelBuilder

        return ModelBuilder(
            self.config, axis=self.axis, world=self.world,
            mesh_axes=self.ctx.axis_names, paged=paged,
            moe_impl=self._mega_moe_impl(),
        )

    def decode_shard_mega(self, p: DenseParams, mega_layers: list, token, ks, vs, lengths):
        """Megakernel decode: the WHOLE model's step is one recorded task
        graph (``build_step_fn``) — fused Pallas kernels per group, the
        scoreboard policy interleaving a layer's deferred cache scatter
        with the next layer's attn-front. MoE models lower their MLP
        through the graph's ``moe`` task (``_mega_moe_impl`` hook; the EP
        model routes its AUTO a2a decode path through it)."""
        c = self.config
        step_fn = self._mega_builder().build_step_fn(c.num_layers)
        x = p.embed[token]
        x, ks, vs = step_fn(mega_layers, x, ks, vs, lengths)
        from triton_dist_tpu.megakernel.kernels import fused_norm_head

        logits = fused_norm_head(x, p.final_norm, p.lm_head, eps=c.rms_eps)
        return logits, ks, vs

    def decode_shard_mega_paged(self, p: DenseParams, mega_layers: list, token,
                                pk, pv, tables, lengths, active):
        """Paged megakernel decode: same persistent-step graph, but the
        cache tasks scatter into / walk the stacked block POOLS directly —
        ``tables`` (B, max_blocks) and ``active`` (B,) are DATA operands,
        so one compiled program serves every batch composition with no
        whole-pool gather/scatter per chunk. Inactive slots write to the
        NULL block (0) and their logits are masked by the caller. Returns
        what ``decode_shard_paged`` does."""
        c = self.config
        step_fn = self._mega_builder(paged=True).build_step_fn(c.num_layers)
        x = p.embed[token]
        x, pk, pv = step_fn(mega_layers, x, pk, pv, lengths, active=active,
                            tables=tables)
        from triton_dist_tpu.megakernel.kernels import fused_norm_head

        logits = fused_norm_head(x, p.final_norm, p.lm_head, eps=c.rms_eps)
        return logits, pk, pv, self.step_stats()

    def decode_shard(self, p: DenseParams, token: jax.Array, ks, vs, lengths, mode: str):
        """Inside shard_map. token (B,) → (logits (B, V_local), updated caches).
        mode: "xla" | "dist_ar" | "mega" (fused per-block megakernel path)."""
        c = self.config
        bsz = token.shape[0]
        x = p.embed[token]
        pos = lengths
        eps = c.rms_eps

        if mode == "mega":
            raise ValueError(
                "mega decode needs pre-split per-layer params: use decode_shard_mega"
            )

        def layer_fn(x, layer):
            lp, k_c, v_c = layer
            attn = self._attn(lp)
            h = RMSNorm(weight=lp["ln1"], eps=eps)(x)
            a, (k_c, v_c) = attn.decode(h, pos, k_c, v_c, lengths, mode=mode)
            x = x + a
            h = RMSNorm(weight=lp["ln2"], eps=eps)(x)
            if c.is_moe:
                m = self._mlp(lp)(h, mode="xla" if mode == "xla" else "dist_ar")
            else:
                m = self._mlp(lp)(h, mode="dist_ar" if mode != "xla" else "xla")
            return x + m, (k_c, v_c)

        x, (ks, vs) = jax.lax.scan(
            lambda carry, layer: layer_fn(carry, layer), x, (self._layer_stack(p), ks, vs)
        )
        x = RMSNorm(weight=p.final_norm, eps=eps)(x)
        logits = jnp.dot(x, p.lm_head, preferred_element_type=jnp.float32)
        return logits, ks, vs

    def decode_shard_paged(self, p: DenseParams, token: jax.Array, pk, pv,
                           tables, lengths, active, mode: str):
        """``decode_shard`` against the stacked block POOLS (L, num_blocks,
        Hkv_l, bs, D; or ``QuantPool`` pairs). The pool pair is the CARRY of
        the loop over layers, never its ``xs`` / ``ys``: a scan hands each
        layer a slice of its ``xs`` and stacks the ``ys`` anew, which is one
        copy of the whole cache a step, while a carry is updated where it
        lies. The layer's index reaches the row write and the kernel as
        data. ``tables`` (B, max_blocks) and ``active`` (B,) are data too:
        an inactive slot writes to the NULL block and its logits are the
        caller's to mask. Returns (logits (B, V_local), pk, pv,
        ``step_stats``)."""
        c = self.config
        x = p.embed[token]
        eps = c.rms_eps
        mlp_mode = "xla" if mode == "xla" else "dist_ar"

        def layer_fn(carry, layer):
            x, pk, pv = carry
            lp, li = layer
            h = RMSNorm(weight=lp["ln1"], eps=eps)(x)
            a, (pk, pv) = self._attn(lp).decode_paged(
                h, lengths, pk, pv, li, tables, lengths, active, mode=mode
            )
            x = x + a
            h = RMSNorm(weight=lp["ln2"], eps=eps)(x)
            return (x + self._mlp(lp)(h, mode=mlp_mode), pk, pv), None

        (x, pk, pv), _ = jax.lax.scan(
            layer_fn, (x, pk, pv),
            (self._layer_stack(p), jnp.arange(c.num_layers, dtype=jnp.int32)),
        )
        x = RMSNorm(weight=p.final_norm, eps=eps)(x)
        logits = jnp.dot(x, p.lm_head, preferred_element_type=jnp.float32)
        return logits, pk, pv, self.step_stats()

    # -- speculative k-wide verify -----------------------------------------

    def verify_shard(self, p: DenseParams, tokens, ks, vs, lengths, steps, mode: str):
        """k-wide greedy verify inside shard_map: score every slot's draft
        window ``tokens`` (B, k) in one launch by sequencing k sub-steps of
        the EXACT ``decode_shard`` program — sub-step j runs at position
        ``lengths + min(j, steps)`` so every accepted token's logits are
        bitwise what plain decode would have produced. ``steps`` (B,) is
        the per-slot participating width (0 for inactive slots: they re-run
        at their frozen position, same as non-speculative decode). Returns
        (logits (B, k, V_local), ks, vs) — draft KV rows past the accepted
        prefix stay in the cache as garbage beyond the rewound length,
        overwritten by the next round before anything attends to them."""
        k = tokens.shape[1]
        outs = []
        for j in range(k):
            pos = lengths + jnp.minimum(jnp.int32(j), steps)
            logits, ks, vs = self.decode_shard(p, tokens[:, j], ks, vs, pos, mode)
            outs.append(logits)
        return jnp.stack(outs, axis=1), ks, vs

    def verify_shard_mega_paged(self, p: DenseParams, mega_layers: list, tokens,
                                pk, pv, tables, lengths, steps):
        """Megakernel k-wide verify: the persistent step graph replayed k
        times inside ONE launch (``build_verify_fn``) over the block pools,
        plus a single fused norm+head over all B·k scored positions.
        Per-sub-step masks derive from ``steps`` as data, so one compiled
        program serves every acceptance pattern and batch composition (jit
        cache keyed on k alone). Non-participating sub-steps write to the
        NULL block."""
        c = self.config
        k = tokens.shape[1]
        vfn = self._mega_builder(paged=True).build_verify_fn(c.num_layers, k)
        xs = p.embed[tokens]
        x2, pk, pv = vfn(mega_layers, xs, pk, pv, lengths, steps, tables=tables)
        from triton_dist_tpu.megakernel.kernels import fused_norm_head

        b = x2.shape[0]
        logits = fused_norm_head(
            x2.reshape(b * k, -1), p.final_norm, p.lm_head, eps=c.rms_eps
        )
        return logits.reshape(b, k, -1), pk, pv


class Qwen3MoE(DenseLLM):
    """Reference ``Qwen3MoE`` (``models/qwen_moe.py:108``): same skeleton,
    MoE MLP. Constructed with a MoE config (``config.num_experts`` set)."""

    def __init__(self, config: ModelConfig, ctx, params=None, key=None):
        assert config.is_moe, "Qwen3MoE needs a MoE config"
        super().__init__(config, ctx, params, key)
