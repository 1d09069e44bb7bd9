"""Inference engine: jit-compiled prefill + on-device decode loop.

Reference: ``python/triton_dist/models/engine.py:37-189`` — ``serve()`` does
HF prefill, switches the model to a triton_dist backend, captures the decode
step in a CUDA graph, then replays it per token (:75,:113,:166). TPU: jit
compilation *is* the graph capture, and the whole ``gen_len`` decode loop
runs **on device** as one ``lax.fori_loop`` — zero host round-trips per
token (one step further than the reference's per-token graph replay).

Backends (reference ``engine.py:80`` backend switch):
  "xla"      — compiler collectives everywhere (the torch-eager analog)
  "dist"     — AG-GEMM/GEMM-RS prefill + GEMM-AR/one-shot-AR decode
  "dist_ar"  — GEMM-AR replicated path for both

Sampling (reference ``sample_token``, ``engine.py:169``): greedy,
temperature, and nucleus (top-p).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.kv_cache import KVCache, PagedKVCache
from triton_dist_tpu.models.quant import QuantPool, dequantize_kv, quantize_kv_rows
from triton_dist_tpu.runtime import telemetry, tracing


_BACKENDS = ("xla", "dist", "dist_ar", "mega")

# Backend → per-program mode maps, as MODULE-LEVEL LITERALS so
# scripts/check_backend_maps.py can statically assert every _BACKENDS entry
# resolves in every map (the silent mega→dist_ar decode demotion this file
# once grew was exactly this drift). The chunk map's mega→dist_ar is
# deliberate and load-bearing: chunked PREFILL has no mega lowering — the
# megakernel graph is decode-shaped (one token per slot per step) — so a
# mega engine prefills op-by-op and decodes fused.
PREFILL_MODE = {"xla": "xla", "dist": "dist", "dist_ar": "dist_ar", "mega": "dist_ar"}
DECODE_MODE = {"xla": "xla", "dist": "dist_ar", "dist_ar": "dist_ar", "mega": "mega"}
CHUNK_MODE = {"xla": "xla", "dist": "dist_ar", "dist_ar": "dist_ar", "mega": "dist_ar"}
# Speculative k-wide verify: MUST track DECODE_MODE exactly — the verify
# program is k sequenced sub-steps of the decode program, and byte-identity
# of spec vs non-spec greedy decode depends on the two resolving to the same
# per-layer mode. In particular mega stays mega: demoting the verify path to
# per-token decode would silently discard the megakernel while spec is on.
VERIFY_MODE = {"xla": "xla", "dist": "dist_ar", "dist_ar": "dist_ar", "mega": "mega"}


def sample_token(
    logits: jax.Array,  # (B, V) fp32
    key: jax.Array | None,
    method: str = "greedy",
    temperature: float = 1.0,
    top_p: float = 1.0,
) -> jax.Array:
    """Greedy / temperature / nucleus sampling (static method switch —
    resolved at trace time, decode loop stays one compiled program)."""
    if method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert key is not None, "sampling needs a PRNG key"
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if method == "top_p" and top_p < 1.0:
        v = logits.shape[-1]
        sorted_logits, sorted_idx = jax.lax.top_k(logits, v)  # descending
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # Keep every token whose preceding cumulative mass is ≤ top_p (the
        # first token always survives).
        prev_mass = jnp.cumsum(probs, axis=-1) - probs
        masked = jnp.where(prev_mass <= top_p, sorted_logits, -jnp.inf)
        choice = jax.random.categorical(key, masked, axis=-1)
        return jnp.take_along_axis(sorted_idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class DecodeChunk:
    """One issued decode chunk (``Engine.issue_decode_chunk``): what the
    device holds, or will, of it."""

    out: jax.Array  # (B, chunk) sampled tokens, -1 where a slot was inactive
    tok: jax.Array  # (B,) each slot's last token: the next chunk's ``tokens``
    rem: jax.Array  # (B,) what is left of each slot: the next ``remaining``
    stats: object  # the step counters the landing publishes (None: a bounce)
    ticket: int = 0  # ``tracing.device_issued``'s, for the wait at the landing
    landed: bool = False


class Engine:
    """Reference ``Engine`` (``models/engine.py:37``). ``model`` is any
    class that brings what docs/serving.md lists under "What a model brings
    to be served" (``DenseLLM`` and its MoE subclasses, ``LatentSparseLLM``)."""

    def __init__(self, model, backend: str = "dist", max_len: int = 512,
                 sample: str = "greedy", temperature: float = 1.0, top_p: float = 1.0):
        assert backend in _BACKENDS, backend
        self.model = model
        self.max_len = max_len
        self.sample_method = sample
        self.temperature = temperature
        self.top_p = top_p
        # The backend this engine was ASKED for — never mutated by rebuild()
        # or degraded-mode fallback, so the serving layer's breaker probe
        # always knows the restore target even after mega → xla → probe
        # round-trips (self.backend tracks what is currently built).
        self.preferred_backend = backend
        self._drafter = None
        # (ticket, step stats) of the prefill chunks issued and not waited
        # for, oldest first: the device's promises until a wait for a later
        # program has returned (``_publish_unwaited``). Empty with telemetry
        # off, and for a model without stats.
        self._unwaited_stats: list = []
        tracing.watch_lowerings()
        self._build(backend)

    def rebuild(self, backend: str) -> None:
        """Re-resolve routing onto ``backend``: retrace every compiled
        program so the circuit-breaker state (``resilience.is_degraded``)
        is re-read at trace time. The serving layer calls this to probe and
        restore the preferred backend after a breaker closes; operators can
        call it directly after ``resilience.reset_degradation()``."""
        self._build(backend)

    def _build(self, backend: str) -> None:
        """(Re)build the compiled prefill/decode programs for ``backend``.

        Callable after construction: degraded-mode fallback rebuilds the
        engine on "xla" (fresh jit functions retrace, so the breaker state
        and the backend switch take effect) and serving continues on the
        same model/caches."""
        # Build cost dominates cold TTFT and dwarfs a recovery window — it
        # gets its own trace so a degraded rebuild shows up timed.
        with tracing.root_span("tdt_engine_build", backend=backend):
            self._build_impl(backend)

    def _build_impl(self, backend: str) -> None:
        assert backend in _BACKENDS, backend
        telemetry.inc("tdt_engine_rebuilds_total", backend=backend)
        model = self.model
        self.backend = backend
        ctx = model.ctx
        mesh = ctx.mesh
        axis = model.axis

        prefill_mode = PREFILL_MODE[backend]
        decode_mode = DECODE_MODE[backend]

        if backend == "dist":
            # Resolve the prefill routing crossovers ONCE at build time:
            # agreed_cfg_value's digest allgather is a host collective that
            # must not fire mid-trace on a cold cache, and surfacing the
            # resolved thresholds as gauges makes the AUTO routing the
            # compiled prefill will take auditable before the first serve.
            from triton_dist_tpu.kernels.allgather_gemm import ag_gemm_crossover_m
            from triton_dist_tpu.kernels.gemm_reduce_scatter import gemm_rs_crossover_m

            world = ctx.mesh.shape[axis]
            telemetry.set_gauge(
                "tdt_engine_prefill_crossover_rows",
                float(ag_gemm_crossover_m(world)), op="ag_gemm",
            )
            telemetry.set_gauge(
                "tdt_engine_prefill_crossover_rows",
                float(gemm_rs_crossover_m(world)), op="gemm_rs",
            )

        ep_xover = getattr(model, "ep_crossover_tokens", None)
        if ep_xover is not None and backend != "xla":
            # Same build-time contract for the EP MoE AUTO route: resolving
            # low_latency↔fused here warms agreed_cfg_value's memo (a host
            # collective that must not fire mid-trace) and surfaces the
            # threshold the compiled programs will route by.
            telemetry.set_gauge(
                "tdt_engine_prefill_crossover_rows",
                float(ep_xover()), op="ep_a2a",
            )

        p_specs = jax.tree.map(
            lambda s: s, modelspecs(model), is_leaf=lambda x: isinstance(x, P) or x is None
        )
        # Data parallelism: if the mesh has a "dp" axis, the batch dim of
        # tokens/caches shards over it (reference engine.py:80,127 splits the
        # batch by world size); tp groups replicate within each dp slice.
        dp = "dp" if "dp" in ctx.axis_names else None
        tok_spec = P(dp)
        len_spec = P(dp)
        kv_spec = P(None, dp, "tp")  # (L, B over dp, Hkv over tp, S, D)
        self._kv_sharding = ctx.sharding(*kv_spec)
        pool_spec = P(None, None, "tp")  # (L, blocks, Hkv over tp, bs, D)
        self._pool_sharding = ctx.sharding(*pool_spec)
        # What a model's step programs return beside their result (per-expert
        # row counts, say): small replicated arrays that leave the device
        # with the chunk's tokens, summed over the chunk's steps on the
        # device. ``model.step_stats()`` gives their zeros.
        stats_spec = jax.tree.map(lambda _: P(), jax.eval_shape(model.step_stats))
        # What a slot keeps whatever its length (recurrent state, a window's
        # ring): ``model.slot_state(num_slots)`` gives its zeros, the slot's
        # axis first on every array. A model without one has the empty
        # state ``()``, which adds no operand to any program below.
        self.stateful = hasattr(model, "slot_state")
        state_spec = (
            jax.tree.map(lambda _: P(), jax.eval_shape(lambda: model.slot_state(1)))
            if self.stateful else ()
        )
        if self.stateful and backend == "mega":
            raise ValueError("a model with per-slot state has no megakernel step")
        # One protocol below this point: the model's chunk and its paged step
        # take the state last and return it before the stats. A model that
        # keeps none is wrapped here, once, and handed the empty state back.
        if self.stateful:
            chunk_model, step_model = model.prefill_chunk_shard, model.decode_shard_paged
            self._state_zeros = jax.jit(
                model.slot_state, static_argnums=(0,),
                out_shardings=jax.tree.map(lambda _: ctx.replicated(), state_spec),
            )
        else:
            def chunk_model(*args):
                logits, kv, stats = model.prefill_chunk_shard(*args[:-1])
                return logits, kv, args[-1], stats

            def step_model(*args):
                logits, pk, pv, stats = model.decode_shard_paged(*args[:-1])
                return logits, pk, pv, args[-1], stats

            self._state_zeros = lambda num_slots: ()

        def prefill_fn(params, tokens):
            logits, (ks, vs) = model.prefill_shard(params, tokens, prefill_mode)
            return jax.lax.all_gather(logits, axis, axis=1, tiled=True), ks, vs

        self._prefill = jax.jit(
            jax.shard_map(
                prefill_fn, mesh=mesh,
                in_specs=(p_specs, tok_spec),
                out_specs=(tok_spec, kv_spec, kv_spec),
                check_vma=False,
            )
        )

        if backend == "mega":
            # Pre-split per-layer params (see DenseLLM.split_layer_params:
            # Pallas operands must be whole buffers, not loop-sliced views).
            # NOTE: this keeps a second copy of the layer weights resident
            # for the engine's lifetime (the stacked pytree still backs
            # prefill) — the price of roofline decode.
            self._mega_layers = model.split_layer_params()
            # Per-layer specs = the stacked specs minus the leading L dim
            # (derived, so DenseParams sharding changes can't drift).
            s = modelspecs(model)
            stacked = {
                "ln1": s.ln1, "wqkv": s.wqkv, "wo": s.wo, "q_norm": s.q_norm,
                "k_norm": s.k_norm, "ln2": s.ln2, "mlp_gate": s.mlp_gate,
                "mlp_up": s.mlp_up, "mlp_down": s.mlp_down,
            }
            if model.config.is_moe:
                stacked["router"] = s.router
            lspec = {k: P(*v[1:]) if len(v) > 1 else P() for k, v in stacked.items()}
            mega_specs = [dict(lspec) for _ in self._mega_layers]

            def decode_fn(params, mega, token, ks, vs, lengths):
                logits, ks, vs = model.decode_shard_mega(params, mega, token, ks, vs, lengths)
                return jax.lax.all_gather(logits, axis, axis=1, tiled=True), ks, vs

            sm = jax.shard_map(
                decode_fn, mesh=mesh,
                in_specs=(p_specs, mega_specs, tok_spec, kv_spec, kv_spec, len_spec),
                out_specs=(tok_spec, kv_spec, kv_spec),
                check_vma=False,
            )
            # The per-layer weights MUST flow through as a real argument —
            # a closure capture would bake ~GBs of weights into the traced
            # HLO as literal constants (unbounded compile payload).
            self._decode_extra = self._mega_layers
            self._decode_shard = sm

            # Paged persistent step: the block tables and per-slot active
            # mask enter the fused program as DATA, so the pool is decoded
            # in place — no whole-pool gather/scatter per chunk (the
            # contiguous-bounce path below pays ~2 pool copies per chunk).
            def decode_paged_fn(params, mega, token, pk, pv, tables, lengths, active):
                logits, pk, pv, stats = model.decode_shard_mega_paged(
                    params, mega, token, pk, pv, tables, lengths, active
                )
                return jax.lax.all_gather(logits, axis, axis=1, tiled=True), pk, pv, stats

            mega_psm = jax.shard_map(
                decode_paged_fn, mesh=mesh,
                in_specs=(p_specs, mega_specs, tok_spec, pool_spec, pool_spec,
                          P(dp), len_spec, len_spec),
                out_specs=(tok_spec, pool_spec, pool_spec, stats_spec),
                check_vma=False,
            )
            self._decode_shard_paged = (
                lambda p_, extra, t_, pk_, pv_, tab_, l_, a_, st_: mega_psm(
                    p_, extra, t_, pk_, pv_, tab_, l_, a_
                ) + (st_,)
            )

            # Speculative k-wide verify: the persistent step graph replayed
            # k times inside ONE shard_map launch (build_verify_fn) against
            # the block pool — the per-slot participating width rides as
            # data, so the jit cache above keys on (chunk, k) alone.
            def verify_paged_fn(params, mega, tokens, pk, pv, tables, lengths, steps):
                logits, pk, pv = model.verify_shard_mega_paged(
                    params, mega, tokens, pk, pv, tables, lengths, steps
                )
                return jax.lax.all_gather(logits, axis, axis=2, tiled=True), pk, pv

            self._verify_shard_paged = jax.shard_map(
                verify_paged_fn, mesh=mesh,
                in_specs=(p_specs, mega_specs, tok_spec, pool_spec, pool_spec,
                          P(dp), len_spec, len_spec),
                out_specs=(tok_spec, pool_spec, pool_spec),
                check_vma=False,
            )
            self._verify_shard = None
        else:
            def decode_fn(params, token, ks, vs, lengths):
                logits, ks, vs = model.decode_shard(params, token, ks, vs, lengths, decode_mode)
                return jax.lax.all_gather(logits, axis, axis=1, tiled=True), ks, vs

            sm = jax.shard_map(
                decode_fn, mesh=mesh,
                in_specs=(p_specs, tok_spec, kv_spec, kv_spec, len_spec),
                out_specs=(tok_spec, kv_spec, kv_spec),
                check_vma=False,
            )
            self._decode_extra = ()
            self._decode_shard = lambda p_, extra, t_, k_, v_, l_: sm(
                p_, t_, k_, v_, l_
            )

            # The same step against the block pool where it lies: the pool
            # pair is carried through the layers, one K/V row written a
            # layer, K/V read through the table inside the kernel.
            def decode_paged_fn(params, token, pk, pv, tables, lengths, active, state):
                logits, pk, pv, state, stats = step_model(
                    params, token, pk, pv, tables, lengths, active, decode_mode, state
                )
                logits = jax.lax.all_gather(logits, axis, axis=1, tiled=True)
                return logits, pk, pv, stats, state

            psm = jax.shard_map(
                decode_paged_fn, mesh=mesh,
                in_specs=(p_specs, tok_spec, pool_spec, pool_spec, P(dp),
                          len_spec, len_spec, state_spec),
                out_specs=(tok_spec, pool_spec, pool_spec, stats_spec, state_spec),
                check_vma=False,
            )
            self._decode_shard_paged = (
                lambda p_, extra, t_, pk_, pv_, tab_, l_, a_, st_: psm(
                    p_, t_, pk_, pv_, tab_, l_, a_, st_
                )
            )

            # Speculative k-wide verify: k sequenced sub-steps of the exact
            # decode program in one launch (DenseLLM.verify_shard) — byte
            # identity with plain decode is structural, not numerical luck.
            verify_mode = VERIFY_MODE[backend]

            def verify_fn(params, tokens, ks, vs, lengths, steps):
                logits, ks, vs = model.verify_shard(
                    params, tokens, ks, vs, lengths, steps, verify_mode
                )
                return jax.lax.all_gather(logits, axis, axis=2, tiled=True), ks, vs

            vsm = jax.shard_map(
                verify_fn, mesh=mesh,
                in_specs=(p_specs, tok_spec, kv_spec, kv_spec, len_spec, len_spec),
                out_specs=(tok_spec, kv_spec, kv_spec),
                check_vma=False,
            )
            self._verify_shard = lambda p_, extra, t_, k_, v_, l_, s_: vsm(
                p_, t_, k_, v_, l_, s_
            )
            self._verify_shard_paged = None

        # ---- TP×PP: pipeline the stack over a 2-D pp×tp mesh --------------
        # When the mesh carries a "pp" axis the one-shot prefill and the
        # dense decode step are swapped for the GPipe programs
        # (disagg/pp_engine.py) — same specs, so everything downstream
        # (generate, decode_chunk, the paged bounce, serve) composes
        # unchanged. Chunked prefill and verify stay the replicated
        # single-stage programs: correct (pp ranks compute redundantly),
        # just not pipelined.
        self.pp_world = (
            int(mesh.shape["pp"]) if "pp" in ctx.axis_names else 1
        )
        if self.pp_world > 1:
            if backend not in ("xla", "dist_ar"):
                raise ValueError(
                    f"pp>1 supports the xla/dist_ar backends, not "
                    f"{backend!r}: dist seq-shards prefill rows and mega "
                    "pre-splits layer params — neither composes with "
                    "stage-sliced layer blocks"
                )
            from triton_dist_tpu.disagg.pp_engine import build_pp_programs

            self._prefill, self._decode_shard = build_pp_programs(
                self, p_specs=p_specs, tok_spec=tok_spec,
                kv_spec=kv_spec, len_spec=len_spec,
            )
            # The stage-sliced step has no paged twin: a pp mesh keeps the
            # contiguous bounce round its decode chunks.
            self._decode_shard_paged = None

        # One compiled program per gen_len: the whole decode loop on device
        # (the XLA analog of replaying a captured CUDA graph gen_len times,
        # minus the per-token host dispatch).
        @partial(jax.jit, static_argnums=(6,), donate_argnums=(3, 4))
        def generate(params, extra, token0, ks, vs, lengths, gen_len, key):
            bsz = token0.shape[0]
            out0 = jnp.zeros((bsz, gen_len), jnp.int32).at[:, 0].set(token0)

            def body(i, carry):
                out, token, ks, vs, lengths, key = carry
                logits, ks, vs = self._decode_shard(params, extra, token, ks, vs, lengths)
                key, sub = jax.random.split(key)
                token = sample_token(
                    logits, sub, self.sample_method, self.temperature, self.top_p
                )
                return (out.at[:, i].set(token), token, ks, vs, lengths + 1, key)

            carry = (out0, token0, ks, vs, lengths, key)
            out, _, ks, vs, _, _ = jax.lax.fori_loop(1, gen_len, body, carry)
            return out, ks, vs

        self._generate = generate

        # The one-shot path's cache: prefill's K/V padded to max_len.
        max_len = self.max_len

        def pad_to_max(k, v):
            shape = k.shape[:3] + (max_len,) + k.shape[4:]
            return (
                jax.lax.dynamic_update_slice(jnp.zeros(shape, k.dtype), k, (0, 0, 0, 0, 0)),
                jax.lax.dynamic_update_slice(jnp.zeros(shape, v.dtype), v, (0, 0, 0, 0, 0)),
            )

        self._pad_to_max = jax.jit(
            pad_to_max, out_shardings=(self._kv_sharding, self._kv_sharding)
        )

        # ---- step-granular serving programs (serving/ subsystem) ----------
        # Everything below stays FIXED-SHAPE: block tables, lengths and the
        # active mask are data, pool and buffer shapes are static, and the
        # decode chunk is one compiled program per chunk size — batch
        # composition (which slots are live, how long each prompt was)
        # never recompiles. Defined in _build so a degraded-mode rebuild
        # refreshes them alongside prefill/generate (fresh closures retrace
        # with the new backend).

        # The contiguous chunk: what a pp mesh's paged decode bounces
        # through (see decode_steps_paged).
        @partial(jax.jit, static_argnums=(7,), donate_argnums=(3, 4))
        def decode_chunk(params, extra, token, ks, vs, lengths, remaining, chunk, key):
            bsz = token.shape[0]
            out0 = jnp.full((bsz, chunk), -1, jnp.int32)

            def body(i, carry):
                out, token, ks, vs, lengths, remaining, key = carry
                active = remaining > 0
                logits, ks, vs = self._decode_shard(params, extra, token, ks, vs, lengths)
                key, sub = jax.random.split(key)
                nxt = sample_token(
                    logits, sub, self.sample_method, self.temperature, self.top_p
                )
                # Inactive slots keep re-feeding their last token: their row
                # still flows through the fixed-shape batch, but the junk it
                # produces is masked out of the output, their lengths freeze
                # (the KVCache.inc_offset active-mask rule), and the only KV
                # it writes lands at the frozen `lengths` position of the
                # bounce buffer — a row the scatter-back masks to NULL.
                nxt = jnp.where(active, nxt, token)
                out = out.at[:, i].set(jnp.where(active, nxt, jnp.int32(-1)))
                step = active.astype(lengths.dtype)
                return (out, nxt, ks, vs, lengths + step, remaining - step, key)

            carry = (out0, token, ks, vs, lengths, remaining, key)
            out, token, ks, vs, lengths, remaining, _ = jax.lax.fori_loop(
                0, chunk, body, carry
            )
            return out, token, ks, vs, lengths, remaining

        self._decode_chunk = decode_chunk

        # The serving decode of every backend that has a paged step (all
        # but a pp mesh): decode_chunk's active-mask/re-feed/freeze
        # semantics per step, but the carry is the POOL pair and the block
        # tables ride as data — one compiled program per chunk size, zero
        # recompiles across batch compositions.
        @partial(jax.jit, static_argnums=(8,), donate_argnums=(3, 4, 10))
        def decode_chunk_paged(params, extra, token, pk, pv, tables, lengths,
                               remaining, chunk, key, state=()):
            bsz = token.shape[0]
            out0 = jnp.full((bsz, chunk), -1, jnp.int32)

            def body(i, carry):
                out, token, pk, pv, lengths, remaining, key, stats, state = carry
                active = remaining > 0
                logits, pk, pv, step, state = self._decode_shard_paged(
                    params, extra, token, pk, pv, tables, lengths, active, state
                )
                stats = jax.tree.map(jnp.add, stats, step)
                key, sub = jax.random.split(key)
                nxt = sample_token(
                    logits, sub, self.sample_method, self.temperature, self.top_p
                )
                # Inactive slots re-feed their last token and freeze their
                # lengths (decode_chunk's rule); their KV write redirects to
                # the NULL block inside the step — a freed slot's old
                # blocks may already belong to another tenant.
                nxt = jnp.where(active, nxt, token)
                out = out.at[:, i].set(jnp.where(active, nxt, jnp.int32(-1)))
                adv = active.astype(lengths.dtype)
                return (out, nxt, pk, pv, lengths + adv, remaining - adv, key, stats,
                        state)

            carry = (out0, token, pk, pv, lengths, remaining, key, model.step_stats(),
                     state)
            out, token, pk, pv, lengths, remaining, _, stats, state = jax.lax.fori_loop(
                0, chunk, body, carry
            )
            return out, token, pk, pv, lengths, remaining, stats, state

        self._decode_chunk_paged = decode_chunk_paged

        # The joins ahead's rows in the next chunk's operands
        # (``put_decode_rows``): one program whatever the slots, its outputs
        # placed as the server places the host's mirrors for a chunk it
        # feeds itself.
        def put_decode_rows(tok, rem, tokens, take, remaining):
            return (jnp.where(take, jnp.concatenate(tokens), tok),
                    jnp.where(take, remaining, rem))

        self._put_decode_rows = jax.jit(
            put_decode_rows, out_shardings=(ctx.replicated(), ctx.replicated()))
        self._row_pad = None  # placed at the first call: a build places nothing

        # One decode step's LOGITS (see decode_logits_paged): the same step
        # programs the two chunk loops above iterate, nothing donated.
        self._step_logits = jax.jit(
            lambda params, extra, token, ks, vs, lengths: self._decode_shard(
                params, extra, token, ks, vs, lengths
            )[0]
        )
        self._step_logits_paged = jax.jit(
            lambda params, extra, token, pk, pv, tables, lengths, active, state:
            self._decode_shard_paged(
                params, extra, token, pk, pv, tables, lengths, active, state
            )[0]
        )

        # ---- prefill into the pool, and the two bounces ------------------
        # Decode runs against the pool in place (decode_chunk_paged). Two
        # paths still bounce through the contiguous layout — gather →
        # contiguous chunk → masked scatter-back of the written rows: a pp
        # mesh (its stage-sliced step has no paged twin) and op-by-op
        # speculation (rejected draft rows must never reach the pool).
        chunk_mode = CHUNK_MODE[backend]

        def chunk_shard(params, toks, kb, vb, off, last_idx, state):
            logits, (kb, vb), state, stats = chunk_model(
                params, toks, kb, vb, off, last_idx, chunk_mode, state
            )
            logits = jax.lax.all_gather(logits, axis, axis=1, tiled=True)
            return logits, kb, vb, stats, state

        # One jitted object; jit's shape cache keys each (chunk_len, P)
        # combination. kbuf/vbuf (and a prompt's carried state) are donated
        # — the running context buffer threads through the chunk loop in
        # place.
        chunk_sm = jax.shard_map(
            chunk_shard, mesh=mesh,
            in_specs=(p_specs, tok_spec, kv_spec, kv_spec, P(), P(), state_spec),
            out_specs=(tok_spec, kv_spec, kv_spec, stats_spec, state_spec),
            check_vma=False,
        )

        def chunk_fn(params, toks, kb, vb, off, last_idx, state=()):
            return chunk_sm(params, toks, kb, vb, off, last_idx, state)

        self._prefill_chunk_prog = jax.jit(chunk_fn, donate_argnums=(2, 3, 6))

        cfg = model.config
        rows = model.cache_rows()

        # ONE jitted object keyed on the prompt length (and on which of the
        # model's two kinds of cache row): a fresh function per call would
        # retrace and recompile on every join. Named, so that its program
        # reads ``jit_paged_kbuf_zeros`` in a device trace.
        def paged_kbuf_zeros(p_len, which):
            r = rows[which]
            return jnp.zeros(
                (r.layers, 1, r.heads, p_len, r.width),
                jnp.dtype(cfg.dtype),
            )

        self._kbuf_zeros = jax.jit(
            paged_kbuf_zeros, static_argnums=(0, 1),
            out_shardings=self._kv_sharding,
        )

        def paged_gather(pk, pv, ks, vs, tables):
            nl, _, hkv_l, bs, _ = pk.shape
            b, mb = tables.shape

            def g(pool):
                hd = pool.shape[-1]
                x = jnp.take(pool, tables.reshape(-1), axis=1)
                x = x.reshape(nl, b, mb, hkv_l, bs, hd).transpose(0, 1, 3, 2, 4, 5)
                return x.reshape(nl, b, hkv_l, mb * bs, hd)

            kc, vc = g(pk), g(pv)
            if ks is not None:
                # Quantized pool: gather the parallel scale pool along the
                # same tables and dequantize to f32 — the same exact
                # (power-of-two) dequantization the in-kernel table walk
                # performs, so the contiguous bounce stays the mega path's
                # numerical twin.
                kc = dequantize_kv(kc, g(ks))
                vc = dequantize_kv(vc, g(vs))
            return kc, vc

        self._paged_gather = jax.jit(
            paged_gather, out_shardings=(self._kv_sharding, self._kv_sharding)
        )

        @partial(jax.jit, static_argnums=(8,), donate_argnums=(0, 1, 2, 3, 9))
        def paged_scatter_prefill(pk, pv, ks, vs, kbuf, vbuf, table_row,
                                  start_block, wire, state, prompt_state, slot):
            """Block-granular scatter of a COMPLETED prefill buffer into the
            pool: one advanced-index write per pool, not one per row.
            Blocks below ``start_block`` are prefix-shared (owned by the
            radix index, possibly by other slots) — they redirect to NULL
            instead of being rewritten (and, quantized, never re-quantized:
            only the freshly-computed owned tail picks up scales here)."""
            bs = pk.shape[3]
            p_len = kbuf.shape[3]
            mbf = -(-p_len // bs)
            pad = mbf * bs - p_len

            def blocks_of(buf):
                x = buf[:, 0]  # (L, Hkv, P, D)
                if pad:
                    x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                x = x.reshape(x.shape[0], x.shape[1], mbf, bs, x.shape[-1])
                return x.transpose(0, 2, 1, 3, 4)  # (L, MBf, Hkv, bs, D)

            owned = jnp.arange(mbf) >= start_block
            phys = jnp.where(owned, table_row[:mbf], 0)
            kb, vb = blocks_of(kbuf), blocks_of(vbuf)
            if wire is not None:
                kq, ksc = quantize_kv_rows(kb, wire)
                vq, vsc = quantize_kv_rows(vb, wire)
                pk = pk.at[:, phys].set(kq)
                pv = pv.at[:, phys].set(vq)
                ks = ks.at[:, phys].set(ksc)
                vs = vs.at[:, phys].set(vsc)
            else:
                pk = pk.at[:, phys].set(kb)
                pv = pv.at[:, phys].set(vb)
            # The finished prompt's own state takes the slot's place whole:
            # nothing of the slot's last tenant is left.
            state = jax.tree.map(
                lambda all_, one: jax.lax.dynamic_update_slice_in_dim(all_, one, slot, 0),
                state, prompt_state,
            )
            return pk, pv, ks, vs, state

        self._paged_scatter_prefill = paged_scatter_prefill

        cdtype = jnp.dtype(model.config.dtype)

        def paged_seed_kbuf(pk, pv, ks, vs, table_row, shared_rows, p_len):
            """Start a prefix-sharing prefill: gather the slot's table chain
            into a fresh (L, 1, Hkv, P, D) context buffer, keeping only the
            first ``shared_rows`` rows (the reused prefix) and zeroing the
            rest — recycled blocks hold stale tenants' values, and the
            chunk attention needs finite-but-masked garbage, not arbitrary
            reads standing in for zeros. A quantized pool dequantizes into
            the model-dtype buffer (the chunk program's operand dtype); the
            donor blocks themselves are read-only here."""
            bs = pk.shape[3]
            mbf = -(-p_len // bs)

            def g(pool):
                nl, _, hkv_l, _, hd = pool.shape
                x = jnp.take(pool, table_row[:mbf], axis=1)  # (L, MBf, Hkv, bs, D)
                x = x.transpose(0, 2, 1, 3, 4).reshape(nl, hkv_l, mbf * bs, hd)
                return x[:, :, :p_len]

            def seed(pool, spool):
                x = g(pool)
                if spool is not None:
                    x = dequantize_kv(x, g(spool), cdtype)
                row = jnp.arange(p_len)
                x = jnp.where(row[None, None, :, None] < shared_rows, x, 0)
                return x[:, None]  # (L, 1, Hkv, P, D)

            return seed(pk, ks), seed(pv, vs)

        self._paged_seed_kbuf = jax.jit(
            paged_seed_kbuf, static_argnums=(6,),
            out_shardings=(self._kv_sharding, self._kv_sharding),
        )

        @partial(jax.jit, static_argnums=(9, 10), donate_argnums=(0, 1, 2, 3))
        def paged_scatter_rows(pk, pv, ks, vs, kc, vc, tables, lengths0, nv,
                               max_rows, wire):
            """Write a bounced chunk's freshly-written contiguous rows back
            into the pool. Row r of slot b landed at position lengths0[b]+r
            and is real only while r < nv[b]; the per-slot valid row count
            ``nv`` is DATA — the speculative path writes back exactly the
            accepted prefix (``lengths' - lengths0``), so rejected draft
            rows in the contiguous bounce buffer never reach the pool, and
            a pp mesh's plain chunk the rows its active mask let through.
            Masked rows redirect to the NULL block — a freed slot's old
            blocks may already belong to another tenant, so a junk write
            through its table would be cross-slot corruption. With ``wire``
            set the pool is quantized: each NEW row quantizes exactly once
            here (payload + per-row scale scatter together); rows already
            in the pool are never touched, so shared prefix blocks stay
            bitwise-stable."""
            bs = pk.shape[3]
            b = tables.shape[0]
            smax = kc.shape[3]
            b_ids = jnp.arange(b)
            for r in range(max_rows):
                pos = jnp.minimum(lengths0 + r, smax - 1)
                blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
                phys = jnp.where(r < nv, blk, 0)
                sub = pos % bs
                krow = kc[:, b_ids, :, pos]
                vrow = vc[:, b_ids, :, pos]
                if wire is not None:
                    kq, ksc = quantize_kv_rows(krow, wire)
                    vq, vsc = quantize_kv_rows(vrow, wire)
                    pk = pk.at[:, phys, :, sub, :].set(kq)
                    pv = pv.at[:, phys, :, sub, :].set(vq)
                    ks = ks.at[:, phys, :, sub, :].set(ksc)
                    vs = vs.at[:, phys, :, sub, :].set(vsc)
                else:
                    pk = pk.at[:, phys, :, sub, :].set(krow)
                    pv = pv.at[:, phys, :, sub, :].set(vrow)
            return pk, pv, ks, vs

        self._paged_scatter_rows = paged_scatter_rows

        # A rebuild (degrade → xla, probe-restore → mega) must re-create the
        # spec programs on the new backend so speculation stays armed across
        # the whole recovery arc.
        if getattr(self, "_drafter", None) is not None:
            self._build_spec_programs()

    # ------------------------------------------------------------------ kv
    def _make_cache(self, ks: jax.Array, vs: jax.Array, seq: int) -> KVCache:
        """Pad prefill caches to max_len into a KVCache handle.

        ONE jitted ``dynamic_update_slice`` into a preallocated max_len
        buffer (``_pad_to_max``) — jit's own shape cache keys off the
        prefill seq, so serving many distinct prompt lengths reuses a single
        function object instead of the old per-pad-size concat-lambda dict
        that minted (and kept) a fresh executable per distinct pad."""
        if ks.shape[3] < self.max_len:
            ks, vs = self._pad_to_max(ks, vs)
        lengths = jnp.full((ks.shape[1],), seq, jnp.int32)
        return KVCache(k=ks, v=vs, lengths=lengths)

    # ---------------------------------------------------------------- serving
    def _phase(self, name: str, t0: float, *arrays) -> float:
        """Stamp one step-phase digest (``tdt_engine_phase_seconds``) and
        return a fresh timestamp for the next phase. When ``arrays`` are
        given they are fenced first, so the stamp covers device completion
        (host-sync phases); without them it covers host-side wall only
        (async dispatch issue). Callers gate on ``telemetry.enabled()`` —
        with ``TDT_TELEMETRY=0`` neither the stamps nor the extra fences
        exist and the serve path keeps its fully-async dispatch."""
        if arrays:
            jax.block_until_ready(arrays)
        now = time.perf_counter()
        telemetry.observe_digest(
            "tdt_engine_phase_seconds", now - t0,
            phase=name, backend=self.backend,
        )
        return now

    def alloc_paged(self, num_slots: int, *, block_size: int,
                    num_blocks: int, quant: str | None = None) -> PagedKVCache:
        """Fresh paged KV: a global (num_blocks, block_size) pool + per-slot
        block tables sized for ``max_len``. Block 0 is the reserved NULL
        block (see ``BlockAllocator``); the pool is zeroed so null reads are
        finite. ``quant`` ("int8"/"fp8") stores the pool in the wire dtype
        with a parallel per-row scale pool (``models/quant.py``)."""
        if self.stateful and quant is not None:
            raise NotImplementedError("a model with per-slot state keeps an unquantized pool")
        paged = PagedKVCache.create(
            self.model.cache_rows(), num_slots, block_size=block_size,
            num_blocks=num_blocks, max_len=self.max_len,
            dtype=jnp.dtype(self.model.config.dtype),
            sharding=self._pool_sharding, quant=quant,
        )
        return dataclasses.replace(paged, state=self.slots_state(num_slots))

    def slots_state(self, num_slots: int):
        """Zeros of what ``num_slots`` slots keep whatever their length:
        ``()`` for a model that keeps nothing."""
        return self._state_zeros(int(num_slots))

    def prompt_state(self):
        """What a prompt's first prefill chunk starts from: one slot's state,
        zeros, so that nothing of the slot's last tenant reaches the next.
        Each chunk is given the state the chunk before returned; the last
        goes to :meth:`complete_paged_prefill` with the slot it is for."""
        return self.slots_state(1)

    @staticmethod
    def _pool_pair(paged: PagedKVCache):
        """The (pk, pv) operands the paged step programs take: bare pools,
        or ``QuantPool`` pairs when quantized — ONE pytree per cache half,
        so the jit cache keys on structure and a quantized serve compiles
        once per chunk size, exactly like bf16."""
        if paged.quant is None:
            return paged.k, paged.v
        return (
            QuantPool(paged.k, paged.k_scale, paged.quant),
            QuantPool(paged.v, paged.v_scale, paged.quant),
        )

    @staticmethod
    def _pool_update(paged: PagedKVCache, pk, pv, lengths) -> PagedKVCache:
        """Fold a step program's returned pools back into the handle."""
        if isinstance(pk, QuantPool):
            return dataclasses.replace(
                paged, k=pk.q, k_scale=pk.scale, v=pv.q, v_scale=pv.scale,
                lengths=lengths,
            )
        return dataclasses.replace(paged, k=pk, v=pv, lengths=lengths)

    def paged_kbuf_zeros(self, p_len: int):
        """Zeroed (L, 1, Hkv, p_len, D) chunk-prefill context buffers, one
        for each of the model's two kinds of cache row (K and V rows for
        ``DenseLLM``). Two independent allocations — kbuf and vbuf are
        donated separately through the chunk program."""
        with tracing.span_current("tdt_engine_paged_kbuf", p_len=int(p_len)):
            return self._kbuf_zeros(int(p_len), 0), self._kbuf_zeros(int(p_len), 1)

    def paged_seed_kbuf(self, paged: PagedKVCache, table_row, shared_rows: int,
                        p_len: int):
        """Context buffers seeded with a reused prefix: the first
        ``shared_rows`` rows gathered from the slot's block chain, the rest
        zeros (see the in-jit docstring)."""
        with tracing.span_current(
            "tdt_engine_paged_kbuf", p_len=int(p_len), shared_rows=int(shared_rows)
        ):
            return self._paged_seed_kbuf(
                paged.k, paged.v, paged.k_scale, paged.v_scale,
                jnp.asarray(table_row, jnp.int32),
                jnp.int32(shared_rows), int(p_len),
            )

    def prefill_chunk_state(self, kbuf, vbuf, chunk_ids: jax.Array, off: int,
                            last_idx: int, state, *, wait: bool = True):
        """One chunk of an incremental prefill against the running context
        buffers. ``chunk_ids`` (1, C) — the final chunk arrives padded to C;
        ``off`` is the chunk's absolute start, ``last_idx`` the row whose
        logits matter (the prompt's last token, on the final chunk);
        ``state`` is the prompt's carried state, from :meth:`prompt_state`
        or the chunk before (``()`` for a model that keeps none). One
        compiled program per (C, P) shape pair; kbuf/vbuf/state are donated.
        Returns (logits (1, V), kbuf', vbuf', state').

        ``wait`` is whether the caller needs this chunk's result: it does of
        a prompt's last (its logits are sampled, its buffers scattered into
        the pool), and that chunk is fenced as every chunk was. A chunk that
        is not the last (``wait=False``) is issued and left to the device:
        what it returns are promises that the prompt's next chunk takes as
        they are, behind whatever else is in flight, and its step counters
        are kept (:attr:`_unwaited_stats`) for the next wait to publish."""
        timed = telemetry.enabled()
        t = time.perf_counter() if timed else 0.0
        # The engine's side of the boundary. The call is one phase
        # (admission), so one span: spans say where the host was, the
        # ``_phase`` stamp below stays what ``tdt_engine_phase_seconds`` reads.
        with tracing.span_current("tdt_engine_prefill_chunk"):
            logits, kb, vb, stats, state = self._prefill_chunk_prog(
                self.model.params, chunk_ids, kbuf, vbuf,
                jnp.int32(off), jnp.int32(last_idx), state,
            )
            ticket = tracing.device_issued()
            if timed and wait:
                # Admission: the cost of joining one request into the
                # running batch, stamped once a prompt: its last chunk from
                # the issue to the end of its compute, what was queued on
                # the device before it included.
                self._phase("admission", t, logits)
                tracing.device_waited(ticket, "prefill_chunk")
                self._publish_unwaited(ticket)
                self.model.publish_step_stats(stats)
            elif timed and (counters := jax.tree.leaves(stats)):
                # On their way to the host as soon as the chunk is done, as
                # a decode chunk's are.
                for fetched in counters:
                    fetched.copy_to_host_async()
                self._unwaited_stats.append((ticket, stats))
        return logits, kb, vb, state

    def _publish_unwaited(self, ticket: int) -> None:
        """Feed the counters from the unwaited chunks issued before the
        program ``ticket``, which a wait has just returned for: the device
        runs its programs in the order of their issue, so those are done and
        nothing here waits. A chunk issued behind that program stays for the
        next wait. Taken off the list before it is fetched: a fault the
        device kept for the fetch surfaces here once."""
        while self._unwaited_stats and self._unwaited_stats[0][0] < ticket:
            self.model.publish_step_stats(self._unwaited_stats.pop(0)[1])

    def prefill_chunk(self, kbuf, vbuf, chunk_ids: jax.Array, off: int,
                      last_idx: int):
        """:meth:`prefill_chunk_state` for a model whose prompts carry no
        state, as the callers from before per-slot state spell it (the
        benchmark's ``tests/benchmark/test_reference.py`` among them):
        (logits (1, V), kbuf', vbuf')."""
        return self.prefill_chunk_state(kbuf, vbuf, chunk_ids, off, last_idx, ())[:3]

    def complete_paged_prefill(self, paged: PagedKVCache, kbuf, vbuf, table_row,
                               start_block: int, slot: int = 0,
                               state=(), *, wait: bool = True) -> PagedKVCache:
        """Scatter a finished prefill's context buffer into the pool along
        the slot's block chain (blocks below ``start_block`` are shared and
        skipped), and the prompt's carried ``state`` (the last chunk's) into
        ``slot`` of the slots' state; the defaults are a stateless model's.
        Pool buffers are donated; tables/lengths are the host's to update
        (they travel as data with the next dispatch).

        With telemetry on the scatter is fenced, and the phase
        (``cache_scatter``) runs from its issue to the end of its compute.
        ``wait=False`` is a scatter issued behind a decode chunk still in
        flight (the server's join ahead): nobody waits for it, the device
        runs it before whatever is issued next, and the phase is its issue
        alone."""
        timed = telemetry.enabled()
        t = time.perf_counter() if timed else 0.0
        # One phase (cache_scatter), so one span, as in ``prefill_chunk``.
        with tracing.span_current("tdt_engine_complete_paged_prefill"):
            pk, pv, ks, vs, slots_state = self._paged_scatter_prefill(
                paged.k, paged.v, paged.k_scale, paged.v_scale, kbuf, vbuf,
                jnp.asarray(table_row, jnp.int32), jnp.int32(start_block),
                paged.quant, paged.state, state, jnp.int32(slot),
            )
            ticket = tracing.device_issued()
            if timed and wait:
                self._phase("cache_scatter", t, pk)
                tracing.device_waited(ticket, "cache_scatter")
            elif timed:
                self._phase("cache_scatter", t)
        return dataclasses.replace(
            paged, k=pk, v=pv, k_scale=ks, v_scale=vs, state=slots_state
        )

    def decode_steps_paged(self, paged: PagedKVCache, tokens: jax.Array,
                           remaining: jax.Array, chunk: int,
                           key: jax.Array | None = None, *,
                           in_flight: list | None = None):
        """Run ``chunk`` decode steps over the slot batch with a per-slot
        active mask (``remaining > 0``): finished/free slots neither advance
        their lengths nor contribute sampled tokens (their output cells hold
        -1). One compiled program per chunk size. The chunk runs DIRECTLY
        against the block pool: the chunk program carries the pool pair as
        its loop state and donates it (callers replace their handle with
        paged'), each layer of each step writes its one new K/V row through
        the table (an inactive slot's to the NULL block) and attention reads
        K/V through the table inside the kernel — no contiguous cache is
        built, copied or scattered back. Only a pp mesh,
        whose stage-sliced step has no paged twin, still gathers the pool
        into the contiguous layout, runs ``self._decode_chunk`` and scatters
        the chunk's written rows back with the null-block mask.
        ``tdt_engine_decode_chunks_total{path}`` says which ran.

        The call is :meth:`issue_decode_chunk` followed by
        :meth:`land_decode_chunk`. A caller that keeps the chunk in flight
        (the server's loop) passes a list as ``in_flight``: the chunk's
        handle is appended to it unlanded, and landing it is the caller's.
        Returns ``(out, last_tokens, paged', remaining')``."""
        handle, paged = self.issue_decode_chunk(paged, tokens, remaining, chunk, key)
        if in_flight is None:
            self.land_decode_chunk(handle)
        else:
            in_flight.append(handle)
        return handle.out, handle.tok, paged, handle.rem

    def issue_decode_chunk(self, paged: PagedKVCache, tokens: jax.Array,
                           remaining: jax.Array, chunk: int,
                           key: jax.Array | None = None):
        """Issue one decode chunk (see :meth:`decode_steps_paged`) without
        waiting for it: ``(handle, paged')``, both of them the device's
        promises. ``handle.tok`` and ``handle.rem`` are what the next chunk
        takes where the slot set does not change, so the next issue needs
        nothing from the host; ``handle.out`` is already on its way there.
        A pp mesh's bounce is not split: its chunk is whole, scatter-back
        included, when this returns, and the handle says so."""
        if key is None:
            key = jax.random.PRNGKey(0)
        with tracing.span_current(
            "tdt_engine_decode_steps_paged", chunk=int(chunk), backend=self.backend
        ):
            # Each phase is also a span (``tdt_engine_<phase>``) round the very
            # statements the ``_phase`` stamp times, fence included.
            timed = telemetry.enabled()
            t = time.perf_counter() if timed else 0.0
            pool = self._decode_shard_paged is not None
            telemetry.inc(
                "tdt_engine_decode_chunks_total", path="pool" if pool else "bounce"
            )
            with tracing.span_current("tdt_engine_dispatch"):
                if pool:
                    pk_in, pv_in = self._pool_pair(paged)
                    out, tok, pk, pv, lengths, rem, stats, state = (
                        self._decode_chunk_paged(
                            self.model.params, self._decode_extra, tokens, pk_in,
                            pv_in, paged.tables, paged.lengths, remaining,
                            int(chunk), key, paged.state,
                        )
                    )
                    if self.backend == "mega":
                        telemetry.set_gauge(
                            "tdt_mega_steps_per_launch", float(chunk), path="paged"
                        )
                    # What the landing fetches leaves for the host as soon
                    # as the chunk is done, whenever the landing comes.
                    for fetched in jax.tree.leaves((out, tok, stats)):
                        fetched.copy_to_host_async()
                else:
                    kc, vc = self._paged_gather(
                        paged.k, paged.v, paged.k_scale, paged.v_scale, paged.tables
                    )
                    out, tok, k2, v2, lengths, rem = self._decode_chunk(
                        self.model.params, self._decode_extra, tokens, kc, vc,
                        paged.lengths, remaining, int(chunk), key,
                    )
                ticket = tracing.device_issued()
                if timed:
                    # dispatch = host wall to ISSUE the chunk program
                    # (async); host_sync = the wait for the device to finish
                    # it, at the landing. In place there is nothing to
                    # scatter and no cache_scatter phase.
                    t = self._phase("dispatch", t)
            if pool:
                paged = dataclasses.replace(paged, state=state)
                return (DecodeChunk(out, tok, rem, stats, ticket),
                        self._pool_update(paged, pk, pv, lengths))
            handle = DecodeChunk(out, tok, rem, None, ticket)
            self.land_decode_chunk(handle, "bounce")
            t = time.perf_counter() if timed else 0.0
            with tracing.span_current("tdt_engine_cache_scatter"):
                pk, pv, ks, vs = self._paged_scatter_rows(
                    paged.k, paged.v, paged.k_scale, paged.v_scale, k2, v2,
                    paged.tables, paged.lengths, jnp.clip(remaining, 0, chunk),
                    int(chunk), paged.quant,
                )
                ticket = tracing.device_issued()
                if timed:
                    self._phase("cache_scatter", t, pk)
                    tracing.device_waited(ticket, "cache_scatter")
            return handle, dataclasses.replace(
                paged, k=pk, v=pv, k_scale=ks, v_scale=vs, lengths=lengths
            )

    def land_decode_chunk(self, handle: DecodeChunk, why: str | None = None):
        """Land an issued chunk: wait for the device to finish it (phase
        ``host_sync``: the wait alone, from the landing's start) and publish
        its step counters. The wait lies under ``tdt_engine_host_sync``
        whoever the caller is. ``why`` is the caller's reason for landing it
        before the next chunk is issued, where it has one: the device's
        ledger names the starved interval that begins here after it
        (``decode_land:<why>``). Landing a landed chunk does nothing. Returns
        ``(out, last_tokens)``, so that a watchdog round the call bounds the
        wait with telemetry off too."""
        if not handle.landed:
            with tracing.span_current("tdt_engine_host_sync"):
                if telemetry.enabled():
                    self._phase("host_sync", time.perf_counter(), handle.tok)
                    tracing.device_waited(
                        handle.ticket, f"decode_land:{why}" if why else "decode_land")
                    self._publish_unwaited(handle.ticket)
                    if handle.stats is not None:
                        self.model.publish_step_stats(handle.stats)
            handle.landed = True
        return handle.out, handle.tok

    def decode_logits_paged(self, paged: PagedKVCache, tokens: jax.Array):
        """(B, V) float32 logits of ONE decode step over the paged cache,
        through the step program ``decode_steps_paged`` iterates on this
        backend (the paged step against the pool; on a pp mesh, pool gather
        + the contiguous step). Every slot counts as active and the cache
        is left as it was — this is for holding two backends, or a
        reference forward, to the same state (``chip_smoke.py``)."""
        if self._decode_shard_paged is not None:
            pk, pv = self._pool_pair(paged)
            return self._step_logits_paged(
                self.model.params, self._decode_extra, tokens, pk, pv,
                paged.tables, paged.lengths,
                jnp.ones(tokens.shape, jnp.bool_), paged.state,
            )
        kc, vc = self._paged_gather(
            paged.k, paged.v, paged.k_scale, paged.v_scale, paged.tables
        )
        return self._step_logits(
            self.model.params, self._decode_extra, tokens, kc, vc,
            paged.lengths,
        )

    def put_decode_rows(self, tokens: jax.Array, remaining: jax.Array,
                        rows: dict, counts: np.ndarray):
        """``tokens`` and ``remaining`` (a chunk's device-resident last tokens
        and counts: the next chunk's operands) with each row ``slot`` of
        ``rows`` set to its token (token 0 of a join, (1,), as
        :meth:`sample_logits` returns it) and to ``counts[slot]``. The
        server's join ahead puts its requests into the chunk issued after
        the one that freed their slots this way, with nothing fetched. The
        slots and the counts are data, and a slot with no row takes a
        placeholder: one program, one call."""
        n = tokens.shape[0]
        if self._row_pad is None:
            self._row_pad = jax.device_put(
                np.zeros((1,), np.int32), self.model.ctx.replicated())
        take = np.zeros((n,), bool)
        take[list(rows)] = True
        row_tokens = jax.device_put(
            tuple(rows.get(i, self._row_pad) for i in range(n)),
            self.model.ctx.replicated(),
        )
        # A copy of the counts: the caller's mirror changes before the device
        # need have read it, and a zero-copy alias would let it see the change.
        return self._put_decode_rows(
            tokens, remaining, row_tokens, take, np.array(counts, np.int32))

    def sample_logits(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        """Sample with the engine's configured method: a join's token 0
        goes through the very ``sample_token`` call that ``serve`` and the
        decode chunk make, which byte parity with them rests on."""
        with tracing.span_current("tdt_engine_sample_logits"):
            return sample_token(
                logits, key, self.sample_method, self.temperature, self.top_p
            )

    # ------------------------------------------------- speculative decoding
    def attach_drafter(self, drafter) -> None:
        """Attach a speculative drafter (``models/drafter.py`` contract) and
        build the spec-decode programs. Greedy-only: the k-wide verify's
        acceptance rule IS greedy argmax comparison — every emitted token is
        the target's own argmax, which is what makes spec output
        byte-identical to plain greedy decode. Survives ``rebuild()``:
        ``_build_impl`` re-creates the spec programs for the new backend, so
        a mega → degraded-xla → probe-restore arc keeps speculation armed
        the whole way."""
        assert self.sample_method == "greedy", "speculative decoding is greedy-only"
        self._drafter = drafter
        self._build_spec_programs()

    def _build_spec_programs(self) -> None:
        """Jitted speculative chunk programs. Static keys are (chunk, k)
        ONLY — batch composition, acceptance patterns, and the per-slot
        adaptive-k state (``kcap``) all flow as data, so nothing recompiles
        while serving.

        Per spec round: the drafter proposes k tokens from the last
        committed token; the target scores the window [t_last, d_1..d_{k-1}]
        with k sequenced sub-steps of the exact decode program in ONE
        launch; the longest prefix where draft j equals the target's argmax
        at j-1 is accepted, plus the bonus token (the target's argmax is
        always correct), capped by the per-slot width. Emitted tokens are
        the TARGET's argmaxes, never the drafts. Rejected draft KV rows sit
        past the rewound length and are overwritten by the next round
        before anything attends to them — rollback is a lengths rewind, not
        a copy."""
        drafter = self._drafter

        def spec_round(r, carry, dparams, kcap, k, verify):
            out, token, store, lengths, remaining, dstate, stats = carry
            active = remaining > 0
            cols = jnp.arange(k, dtype=jnp.int32)[None, :]
            # Per-slot participating width: adaptive kcap, never past the
            # request's remaining budget, zero for inactive slots.
            ec = jnp.where(
                active, jnp.clip(jnp.minimum(kcap, remaining), 1, k), 0
            )
            drafts, pending = drafter.propose(dparams, token, dstate, active, k)
            win = jnp.concatenate([token[:, None], drafts[:, : k - 1]], axis=1)
            logits, store = verify(win, store, lengths, ec)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = (win[:, 1:] == g[:, :-1]).astype(jnp.int32)
            m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            a = jnp.minimum(m + 1, ec)
            emit = jnp.where(cols < a[:, None], g, jnp.int32(-1))
            out = jax.lax.dynamic_update_slice(out, emit, (jnp.int32(0), r * k))
            idx = jnp.maximum(a - 1, 0)[:, None]
            nxt = jnp.take_along_axis(g, idx, axis=1)[:, 0]
            token = jnp.where(a > 0, nxt, token)
            dstate = drafter.commit(dparams, dstate, pending, a)
            adv = a.astype(lengths.dtype)
            stats = stats + jnp.stack(
                [ec, a, (ec > 0).astype(jnp.int32)], axis=1
            )
            return (out, token, store, lengths + adv, remaining - adv, dstate, stats)

        if self._verify_shard_paged is None:
            # Op-by-op backends: the rounds run on the contiguous bounce of
            # spec_decode_steps_paged.
            @partial(jax.jit, static_argnums=(9, 10), donate_argnums=(4, 5))
            def spec_chunk(params, extra, dparams, token, ks, vs, lengths,
                           remaining, kcap, chunk, k, dstate):
                bsz = token.shape[0]
                out0 = jnp.full((bsz, chunk * k), -1, jnp.int32)
                stats0 = jnp.zeros((bsz, 3), jnp.int32)

                def verify(win, store, lengths, ec):
                    ks, vs = store
                    logits, ks, vs = self._verify_shard(
                        params, extra, win, ks, vs, lengths, ec
                    )
                    return logits, (ks, vs)

                def body(r, carry):
                    return spec_round(r, carry, dparams, kcap, k, verify)

                carry = (out0, token, (ks, vs), lengths, remaining, dstate, stats0)
                out, token, (ks, vs), lengths, remaining, dstate, stats = (
                    jax.lax.fori_loop(0, chunk, body, carry)
                )
                return out, token, ks, vs, lengths, remaining, dstate, stats

            self._spec_chunk = spec_chunk
        else:
            @partial(jax.jit, static_argnums=(10, 11), donate_argnums=(4, 5))
            def spec_chunk_paged(params, extra, dparams, token, pk, pv, tables,
                                 lengths, remaining, kcap, chunk, k, dstate):
                bsz = token.shape[0]
                out0 = jnp.full((bsz, chunk * k), -1, jnp.int32)
                stats0 = jnp.zeros((bsz, 3), jnp.int32)

                def verify(win, store, lengths, ec):
                    pk, pv = store
                    logits, pk, pv = self._verify_shard_paged(
                        params, extra, win, pk, pv, tables, lengths, ec
                    )
                    return logits, (pk, pv)

                def body(r, carry):
                    return spec_round(r, carry, dparams, kcap, k, verify)

                carry = (out0, token, (pk, pv), lengths, remaining, dstate, stats0)
                out, token, (pk, pv), lengths, remaining, dstate, stats = (
                    jax.lax.fori_loop(0, chunk, body, carry)
                )
                return out, token, pk, pv, lengths, remaining, dstate, stats

            self._spec_chunk_paged = spec_chunk_paged

    def spec_decode_steps_paged(self, paged: PagedKVCache, dstate,
                                tokens: jax.Array, remaining: jax.Array,
                                kcap: jax.Array, chunk: int, k: int,
                                key: jax.Array | None = None):
        """Speculative twin of ``decode_steps_paged``: ``chunk`` spec rounds,
        each accepting 1..k tokens per active slot. Mega runs the rounds
        directly against the block pool (tables + per-sub-step masks as
        data); op-by-op backends bounce through the contiguous layout and
        scatter back ONLY the accepted rows (``paged_scatter_rows`` with
        the data-driven count ``lengths' - lengths0``) — the pool never
        holds a rejected draft's KV. Returns ``(out (B, chunk·k) int32 with
        -1 holes, last_tokens, paged', remaining', dstate', stats (B, 3)
        [proposed, accepted, rounds])``. ``key`` is accepted for call-site
        symmetry and unused — spec decode is greedy-only."""
        del key
        assert self._drafter is not None, "attach_drafter first"
        timed = telemetry.enabled()
        t = time.perf_counter() if timed else 0.0
        if self.backend == "mega":
            pk_in, pv_in = self._pool_pair(paged)
            out, tok, pk, pv, lengths, rem, dstate, stats = self._spec_chunk_paged(
                self.model.params, self._decode_extra, self._drafter.params,
                tokens, pk_in, pv_in, paged.tables, paged.lengths,
                remaining, kcap, int(chunk), int(k), dstate,
            )
            ticket = tracing.device_issued()
            telemetry.set_gauge(
                "tdt_mega_steps_per_launch", float(chunk * k), path="spec_paged"
            )
            if timed:
                self._phase("spec_propose", t, tok)
                tracing.device_waited(ticket, "spec")
            return out, tok, self._pool_update(
                paged, pk, pv, lengths
            ), rem, dstate, stats
        kc, vc = self._paged_gather(
            paged.k, paged.v, paged.k_scale, paged.v_scale, paged.tables
        )
        out, tok, k2, v2, lengths, rem, dstate, stats = self._spec_chunk(
            self.model.params, self._decode_extra, self._drafter.params,
            tokens, kc, vc, paged.lengths, remaining, kcap,
            int(chunk), int(k), dstate,
        )
        ticket = tracing.device_issued()
        if timed:
            t = self._phase("spec_propose", t, tok)
            tracing.device_waited(ticket, "spec")
        nv = lengths - paged.lengths
        pk, pv, ks, vs = self._paged_scatter_rows(
            paged.k, paged.v, paged.k_scale, paged.v_scale, k2, v2,
            paged.tables, paged.lengths, nv, int(chunk) * int(k), paged.quant,
        )
        ticket = tracing.device_issued()
        if timed:
            # Commit: only the ACCEPTED rows scatter back into the pool.
            self._phase("spec_commit", t, pk)
            tracing.device_waited(ticket, "spec")
        return out, tok, dataclasses.replace(
            paged, k=pk, v=pv, k_scale=ks, v_scale=vs, lengths=lengths
        ), rem, dstate, stats

    # ----------------------------------------------------------------- serve
    def serve(self, input_ids: jax.Array, gen_len: int, key: jax.Array | None = None,
              profile_dir: str | None = None):
        """Generate ``gen_len`` tokens. Returns (B, gen_len) int32.
        ``profile_dir`` wraps the run in an XProf capture (the reference's
        ``trace_static.json`` export hook, ``engine.py:153-179``).
        Reference ``Engine.serve`` (``engine.py:113``)."""
        from triton_dist_tpu.runtime import resilience

        telemetry.inc("tdt_engine_serve_total", backend=self.backend)
        watchdog = resilience.CollectiveWatchdog(
            feature="collectives", name=f"engine.serve[{self.backend}]"
        )

        serve_once = self._serve_once
        if profile_dir is not None:
            from triton_dist_tpu.tools.profiler import trace

            def serve_once(ids, n, k):
                # The trace wraps ONLY the serve work; the serve counter and
                # the watchdog live outside, exactly once (the old recursive
                # profiled path re-entered serve(), nesting a second
                # watchdog inside the capture).
                with trace(profile_dir):
                    out = self._serve_once(ids, n, k)
                    # Dispatch is async: realize inside the capture or the
                    # trace stops before the device work runs.
                    jax.block_until_ready(out)
                    return out

        def fallback(ids, n, k):
            # The watchdog has already marked "collectives" degraded; rebuild
            # on the xla backend and serve the same request. Prefill re-runs
            # from input_ids, so the donated caches of the wedged attempt
            # are not needed.
            self._degrade_to_xla("serve timed out under the collective watchdog")
            # Plain re-serve: a timed-out attempt's abandoned thread may
            # still hold the profiler capture open, so the retry must not
            # try to start a second trace into the same directory.
            return self._serve_once(ids, n, k)

        try:
            return watchdog.call(
                serve_once, input_ids, gen_len, key, fallback=fallback
            )
        except Exception:
            # A bounded-wait abort surfaced mid-serve (CollectiveAbortError
            # via consume_status). The abort already flipped the sticky
            # degradation flag for the stalled collective — rebuild on xla
            # and retry once; further serves go straight to the fallback.
            if self.backend != "xla" and resilience.any_degraded():
                self._degrade_to_xla("a collective aborted mid-serve")
                return self._serve_once(input_ids, gen_len, key)
            raise

    def _degrade_to_xla(self, why: str) -> None:
        from triton_dist_tpu.runtime import resilience

        telemetry.inc("tdt_engine_fallbacks_total", from_backend=self.backend)
        telemetry.emit("engine_fallback", from_backend=self.backend, why=why)
        resilience.note_fallback_once(
            "engine.serve", f"rebuilding engine on the xla backend ({why})"
        )
        if self.backend != "xla":
            self._build("xla")

    def _serve_once(self, input_ids: jax.Array, gen_len: int, key: jax.Array | None):
        model = self.model
        bsz, seq = input_ids.shape
        assert seq + gen_len <= self.max_len
        if key is None:
            key = jax.random.PRNGKey(0)

        # Serve-path latency histograms. The extra block_until_ready fences
        # are gated on telemetry being enabled — with TDT_TELEMETRY=0 the
        # serve path keeps its fully-async dispatch (no added syncs).
        timed = telemetry.enabled()
        t0 = time.perf_counter() if timed else 0.0

        logits, ks, vs = self._prefill(model.params, input_ids)
        cache = self._make_cache(ks, vs, seq)

        key, sub = jax.random.split(key)
        token0 = sample_token(logits, sub, self.sample_method, self.temperature, self.top_p)
        if timed:
            jax.block_until_ready(token0)
            # TTFT: wall from serve entry to the first sampled token being
            # materialized (prefill + cache build + token-0 sample).
            telemetry.observe(
                "tdt_engine_ttft_seconds", time.perf_counter() - t0,
                backend=self.backend,
            )
            t1 = time.perf_counter()
        out, k2, v2 = self._generate(
            model.params, self._decode_extra, token0, cache.k, cache.v,
            cache.lengths, gen_len, key
        )
        if timed:
            jax.block_until_ready(out)
            # The decode loop is ONE on-device fori_loop dispatch — per-token
            # latency is host-derived: decode wall / steps (gen_len-1 steps
            # ran; token0 came from prefill). One observation per serve.
            steps = max(gen_len - 1, 1)
            telemetry.observe(
                "tdt_engine_decode_token_seconds",
                (time.perf_counter() - t1) / steps,
                backend=self.backend,
            )
        # gen_len-1 decode steps ran, each writing its input token's KV:
        # slots [0, seq+gen_len-1) hold valid entries; the LAST generated
        # token's KV is not yet written (a resumed decode feeds it next).
        self.kv_cache = KVCache(k=k2, v=v2, lengths=cache.lengths + gen_len - 1)
        return out

    # ------------------------------------------------------------- profiling
    def bench_decode(self, bsz: int = 1, prompt_len: int = 64, iters: int = 256,
                     reps: int = 5):
        """Steady-state per-token decode latency (reference perf mode of
        ``test_e2e_inference.py``).

        Times the on-device ``_generate`` loop at TWO long lengths (iters
        and iters//4 steps, one dispatch each) and divides the wall
        difference by the step difference: dispatch and cache-copy overhead
        cancel between two same-shaped long runs (differencing a long run
        against a 1-step wall lets a single contended overhead sample
        swallow the whole signal and once produced a sub-HBM-floor
        \"measurement\"). Median-of-reps rejects host-clock spikes. A naive
        host loop of ``_decode`` calls would measure dispatch, not the
        chip."""
        ids = jnp.zeros((bsz, prompt_len), jnp.int32)
        logits, ks, vs = self._prefill(self.model.params, ids)
        cache = self._make_cache(ks, vs, prompt_len)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key = jax.random.PRNGKey(0)

        def run(n):
            # _generate donates the caches: hand it fresh copies. The int()
            # readback fences device execution.
            out, _, _ = self._generate(
                self.model.params, self._decode_extra, token,
                jnp.copy(cache.k), jnp.copy(cache.v), cache.lengths, n, key
            )
            return int(jnp.sum(out))

        def median_wall(n):
            walls = []
            for _ in range(reps):
                t0 = time.perf_counter()
                run(n)
                walls.append(time.perf_counter() - t0)
            walls.sort()
            return walls[len(walls) // 2]

        if iters < 2:
            raise ValueError("bench_decode needs iters >= 2 (two-length differencing)")
        short_iters = max(1, iters // 4)
        run(1 + short_iters)  # compile short
        run(1 + iters)  # compile long
        short_ = median_wall(1 + short_iters)
        long_ = median_wall(1 + iters)
        if long_ <= short_:
            # Shared-tenancy noise swamped the signal: unusable, never 0
            # (callers would divide by it or report impossible 0 ms).
            return float("inf")
        return (long_ - short_) / (iters - short_iters)


def bench_decode_table(model, backends=_BACKENDS, bsz: int = 1,
                       prompt_len: int = 64, iters: int = 20, max_len: int = 512):
    """Per-backend decode latency comparison (the reference's e2e table,
    ``e2e_dense.md``): {backend: seconds/token}."""
    return {
        b: Engine(model, backend=b, max_len=max_len).bench_decode(
            bsz=bsz, prompt_len=prompt_len, iters=iters
        )
        for b in backends
    }


def modelspecs(model):
    """Parameter PartitionSpec pytree for ``model``. Models with a custom
    layout (the EP MoE model's expert-sharded slabs, ``models/moe.py``)
    override via a ``param_specs`` method; default is the dense/TP layout."""
    fn = getattr(model, "param_specs", None)
    if fn is not None:
        return fn()
    from triton_dist_tpu.models.dense import _specs

    return _specs(model.config)
