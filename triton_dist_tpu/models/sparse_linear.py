"""A decoder of two kinds of mixer (``SparseLinearConfig.mixer_types``;
``layers/sparse_linear.py`` holds the math), in MiniCPM's muP form:

* **lightning** layers: linear attention over rotated, normed q and k with
  a float32 ``[D, D]`` state a head that decays a position, an output norm
  and an output gate;
* **sparse** layers: grouped-query attention without rotation in which a
  K/V head's query group attends ``topk`` blocks of ``block_size``
  positions: the first, the window ending at its own, and the best of the
  rest by a score read from POOLED keys (a mean of ``kernel_size`` keys
  every ``kernel_stride``) alone; an output gate.

``h0 = scale_emb E[token]``; every layer is ``h += a Mix(RMS(h)); h += a
SwiGLU(RMS(h))`` with ``a = scale_depth / sqrt(published_layers)``; the
logits are ``W_head RMS(h) / (hidden / dim_model_base)``.

It is served by the same ``Engine`` programs, scheduler, ledger and block
tables as the other models; what differs is declared:

* ``cache_rows()``: the sparse layers' K and V rows, a layer's K/V heads
  side by side (``kv_rows(n_sparse, 1, Hkv * D)``). **The pool's page must
  be the selection's block** (``InferenceServer(block_size=config.
  block_size)``): a selected block is then a page, and the selection
  through a slot's table row is the list of pages a decode step reads.
* ``slot_state(num_slots)``: the lightning layers' float32 state, and the
  sparse layers' pooled keys at the fixed extent ``max_len //
  kernel_stride`` a slot (the selection's own cache: a sixteenth of the
  K rows' rate, so a fixed extent a slot is 1.6 MB at the published sizes,
  and the engine's pair of row kinds stays a pair). The engine allocates it
  for the slots, donates it through the decode chunk, carries one slot's
  worth from chunk to chunk of a prompt starting from zeros, and writes it
  into the slot when the prompt is done. Nothing of it is in the pool, so
  the server shares no prefix and rewinds no draft for such a model
  (``docs/serving.md``).
* ``step_stats``: blocks selected, visible and learned (selected less
  forced), pages the attends fetched, rows the lightning layers took, [in
  prefill chunks, in decode steps].

One mesh rank; paged serving programs only.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import sparse_linear as sl
from triton_dist_tpu.models.config import SparseLinearConfig
from triton_dist_tpu.models.kv_cache import kv_rows
from triton_dist_tpu.models.latent_sparse import _drawer
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import DistContext

F32 = jnp.float32
STATS = ("bsa_selected", "bsa_visible", "bsa_learned", "bsa_pages_read", "linear_rows")


# ----------------------------------------------------------------- weights


def layer_tensors(c: SparseLinearConfig, layer: int) -> list:
    """(name, shape) of one layer's drawn tensors, in draw order (normal /
    sqrt(shape[0])). ``w_in`` is ``[q | k | v | g]``, ``w1`` ``[gate | up]``."""
    d, ff = c.hidden_size, c.intermediate_size
    if c.mixer_types[layer] == "sparse":
        qw, kvw = c.num_q_heads * c.head_dim, c.num_kv_heads * c.head_dim
        mix = [("w_in", (d, 2 * qw + 2 * kvw)), ("w_o", (qw, d))]
    else:
        w = c.lightning_heads * c.lightning_head_dim
        mix = [("w_in", (d, 4 * w)), ("w_o", (w, d))]
    return mix + [("w1", (d, 2 * ff)), ("w2", (ff, d))]


def layer_ones(c: SparseLinearConfig, layer: int) -> list:
    """(name, length) of one layer's norm weights: ones, not drawn."""
    d = c.hidden_size
    if c.mixer_types[layer] == "sparse":
        return [("ln1", d), ("ln2", d), ("q_norm", c.head_dim), ("k_norm", c.head_dim)]
    D = c.lightning_head_dim
    return [("ln1", d), ("ln2", d), ("q_norm", D), ("k_norm", D), ("o_norm", D)]


def init_params(config: SparseLinearConfig, key, ctx: DistContext) -> dict:
    """Seeded random weights on the mesh: tensor ``i`` of layer ``l`` from
    ``fold_in(fold_in(key, l), i)``; the embedding (x 0.02) and the head are
    tensors 0 and 1 of "layer" ``num_layers``; norm weights 1."""
    c = config
    rep = ctx.replicated()
    key = jnp.asarray(key)
    ones = lambda n: jax.device_put(jnp.ones((n,), jnp.dtype(c.dtype)), rep)
    top = jax.random.fold_in(key, c.num_layers)
    params = {
        "embed": _drawer((c.vocab_size, c.hidden_size), 0.02, c.dtype, rep)(
            jax.random.fold_in(top, 0)),
        "head": _drawer((c.hidden_size, c.vocab_size), None, c.dtype, rep)(
            jax.random.fold_in(top, 1)),
        "final_norm": ones(c.hidden_size),
        "layers": [],
    }
    for layer in range(c.num_layers):
        lk = jax.random.fold_in(key, layer)
        lp = {name: _drawer(shape, None, c.dtype, rep)(jax.random.fold_in(lk, i))
              for i, (name, shape) in enumerate(layer_tensors(c, layer))}
        lp.update({name: ones(n) for name, n in layer_ones(c, layer)})
        params["layers"].append(lp)
    return params


# ------------------------------------------------------------------- model


class SparseLinearLLM:
    """See the module docstring. The engine's hooks are ``param_specs``,
    ``cache_rows``, ``slot_state``, ``step_stats`` / ``publish_step_stats``,
    ``prefill_chunk_shard`` and ``decode_shard_paged``."""

    def __init__(self, config: SparseLinearConfig, ctx: DistContext, params=None, key=None):
        self.config = config
        self.ctx = ctx
        self.axis = "tp"
        self.world = ctx.num_ranks(self.axis)
        if self.world != 1:
            raise NotImplementedError(
                "linear state and pooled keys are not sharded: one mesh rank only")
        if params is None:
            params = init_params(config, key if key is not None else jax.random.PRNGKey(0), ctx)
        self.params = params

    # -- what the engine reads -------------------------------------------
    def param_specs(self):
        return jax.tree.map(lambda _: P(), self.params)

    def cache_rows(self):
        c = self.config
        return kv_rows(len(c.layers_of("sparse")), 1, c.num_kv_heads * c.head_dim)

    def slot_state(self, num_slots: int) -> dict:
        """Zeros of what ``num_slots`` slots keep whatever their length."""
        c = self.config
        H, D = c.lightning_heads, c.lightning_head_dim
        pooled = (num_slots, c.pooled_extent, c.num_kv_heads * c.head_dim)
        return {
            "linear": [jnp.zeros((num_slots, H, D, D), F32) for _ in c.layers_of("lightning")],
            "pooled": [jnp.zeros(pooled, jnp.dtype(c.dtype)) for _ in c.layers_of("sparse")],
        }

    def step_stats(self):
        """Zeros of what every step program returns beside its result, [in
        prefill chunks, in decode steps], over the sparse layers, the K/V
        heads and the rows somebody sent: the blocks a row's group selected,
        those it could see (its own block's index + 1), the selected that
        were not forced, the pages the attend fetched (whole key tiles some
        query of a tile selected in prefill, whole tiles of the selected
        list in decode, the whole extent where the ``jax.numpy`` form
        runs); and the rows the lightning layers took (a row once, not once
        a layer)."""
        return {name: jnp.zeros((2,), jnp.int32) for name in STATS}

    def publish_step_stats(self, stats) -> None:
        """Host side: feed the counters from a finished program's stats."""
        stats = jax.device_get(stats)
        for i, phase in enumerate(("prefill", "decode")):
            count = lambda name: float(stats[name][i])
            telemetry.inc("tdt_bsa_blocks_selected_total", count("bsa_selected"), phase=phase)
            telemetry.inc("tdt_bsa_blocks_visible_total", count("bsa_visible"), phase=phase)
            telemetry.inc("tdt_bsa_blocks_learned_total", count("bsa_learned"), phase=phase)
            telemetry.inc("tdt_bsa_pages_read_total", count("bsa_pages_read"), phase=phase)
            telemetry.inc("tdt_linear_attn_rows_total", count("linear_rows"), phase=phase)

    # -- layers ------------------------------------------------------------
    def _select(self, q, pooled, q_pos, rows, nb: int, off=None):
        """The blocks each query's group takes: q (T, Hkv, G, D), ``pooled``
        (NP, Hkv * D) -> (sel (Hkv, T, nb), [selected, visible, learned]
        over the rows in ``rows``). ``off``: a chunk's first position, where
        ``q_pos`` is a chunk's."""
        c = self.config
        with jax.named_scope("bsa_select"):
            pooled = pooled.reshape(pooled.shape[0], c.num_kv_heads, c.head_dim)
            r = sl.group_scores(q, pooled, q_pos, c.kernel_size, c.kernel_stride, off)
            score = sl.block_scores(r, nb, c.kernel_size, c.kernel_stride, c.block_size)
            own = q_pos // c.block_size
            sel, forced = sl.select_blocks(score, own, c.topk, c.init_blocks,
                                           c.window_size // c.block_size)
            count = lambda m: jnp.sum(m & rows[:, None], dtype=jnp.int32)
            selected = count(sel)
            visible = c.num_kv_heads * jnp.sum(jnp.where(rows, own + 1, 0), dtype=jnp.int32)
            return sel, jnp.stack([selected, visible, selected - c.num_kv_heads * count(forced)])

    @staticmethod
    def _counted(stats, phase: int, counts, pages):
        """``stats`` with a sparse layer's counts added under ``phase``."""
        stats = dict(stats)
        for name, n in zip(STATS[:4], (*counts, pages)):
            stats[name] = stats[name].at[phase].add(n)
        return stats

    def _split_sparse(self, lp, u):
        """(q (..., Hkv, G, D) normed, a K row normed and a V row (..., Hkv *
        D), the gate (..., Hq * D))."""
        c = self.config
        D, hkv = c.head_dim, c.num_kv_heads
        qw, kvw = c.num_q_heads * D, hkv * D
        z = sl.mm(u, lp["w_in"])
        lead = z.shape[:-1]
        q = sl.rms(z[..., :qw].reshape(lead + (hkv, c.num_q_heads // hkv, D)),
                   lp["q_norm"], c.rms_eps)
        k = sl.rms(z[..., qw:qw + kvw].reshape(lead + (hkv, D)), lp["k_norm"], c.rms_eps)
        return (q, k.reshape(lead + (kvw,)), z[..., qw + kvw:qw + 2 * kvw],
                z[..., qw + 2 * kvw:])

    def _split_lightning(self, lp, u, pos):
        """(q, k rotated and normed, v (..., H, D), the gate (..., H * D))."""
        c = self.config
        H, D = c.lightning_heads, c.lightning_head_dim
        z = sl.mm(u, lp["w_in"])
        z4 = z.reshape(z.shape[:-1] + (4, H, D))
        q = sl.rope_half(sl.rms(z4[..., 0, :, :], lp["q_norm"], c.rms_eps), pos, c.rope_theta)
        k = sl.rope_half(sl.rms(z4[..., 1, :, :], lp["k_norm"], c.rms_eps), pos, c.rope_theta)
        return q, k, z4[..., 2, :, :], z[..., 3 * H * D:]

    def _lightning_out(self, lp, o, gate):
        """o (..., H, D) float32 unscaled -> W_o (RMS(o * scale) * sigmoid(g))."""
        c = self.config
        y = sl.rms(o / math.sqrt(c.lightning_head_dim), lp["o_norm"], c.rms_eps)
        y = y.reshape(gate.shape) * jax.nn.sigmoid(gate.astype(F32)).astype(y.dtype)
        return sl.mm(y, lp["w_o"])

    def _sparse_out(self, lp, o, gate):
        o = o.reshape(gate.shape).astype(gate.dtype)
        return sl.mm(o * jax.nn.sigmoid(gate.astype(F32)).astype(o.dtype), lp["w_o"])

    def _mlp(self, lp, x):
        c = self.config
        a = jnp.asarray(c.residual_scale, x.dtype)
        return x + a * sl.swiglu(sl.rms(x, lp["ln2"], c.rms_eps), lp["w1"], lp["w2"])

    def _embed(self, p, tokens):
        return p["embed"][tokens] * jnp.asarray(self.config.scale_emb, p["embed"].dtype)

    def _logits(self, p, x):
        c = self.config
        x = sl.rms(x, p["final_norm"], c.rms_eps)
        return jnp.dot(x, p["head"], preferred_element_type=F32) * c.logit_scale

    def prefill_chunk_shard(self, p, tokens, kbufs, vbufs, off, last_idx, mode: str, state):
        """One chunk of an incremental prefill. tokens (1, C); ``kbufs``,
        ``vbufs`` (n_sparse, 1, 1, P, Hkv * D) the prompt's running K and V
        rows; ``state`` one slot's state, as the chunk before left it (zeros
        before the first); ``off`` the chunk's first position (a multiple of
        ``kernel_stride``), ``last_idx`` the row whose logits matter. Rows
        past P (a padded final chunk) change nothing. Returns (logits (1,
        V) of that row, (kbufs, vbufs), state, stats)."""
        del mode  # one rank: nothing to reduce over
        c = self.config
        bsz, C = tokens.shape
        assert bsz == 1, "a prefill chunk is one request's"
        P_len = kbufs.shape[3]
        nb = -(-P_len // c.block_size)
        n_pooled = min(-(-P_len // c.kernel_stride), c.pooled_extent)
        off = off.astype(jnp.int32)
        last_idx = jnp.clip(last_idx.astype(jnp.int32), 0, C - 1)
        pos = off + jnp.arange(C, dtype=jnp.int32)
        sent = pos < P_len
        n_real = jnp.clip(P_len - off, 0, C)
        a = jnp.asarray(c.residual_scale, jnp.dtype(c.dtype))
        state = {k: list(v) for k, v in state.items()}
        stats = self.step_stats()
        stats["linear_rows"] = stats["linear_rows"].at[0].add(n_real)
        x = self._embed(p, tokens[0])
        i_lin = i_sp = 0
        for layer, lp in enumerate(p["layers"]):
            u = sl.rms(x, lp["ln1"], c.rms_eps)
            if c.mixer_types[layer] == "lightning":
                q, k, v, gate = self._split_lightning(lp, u, pos)
                o, s1 = sl.lightning_chunk(q, k, v, state["linear"][i_lin][0], n_real)
                state["linear"][i_lin] = s1[None]
                i_lin += 1
                mix = self._lightning_out(lp, o, gate)
            else:
                q, k_row, v_row, gate = self._split_sparse(lp, u)
                kbufs = kbufs.at[i_sp, 0, 0, pos].set(k_row, mode="drop")
                vbufs = vbufs.at[i_sp, 0, 0, pos].set(v_row, mode="drop")
                k_all, v_all = kbufs[i_sp, 0, 0], vbufs[i_sp, 0, 0]
                j, keys = sl.pool_chunk(k_all, off, C, c.kernel_size, c.kernel_stride)
                pooled = state["pooled"][i_sp].at[0, j].set(keys, mode="drop")
                state["pooled"][i_sp] = pooled
                sel, counts = self._select(q, pooled[0, :n_pooled], pos, sent, nb, off)
                o, pages = sl.attend_chunk(q, k_all, v_all, sel, off, c.block_size)
                stats = self._counted(stats, 0, counts, pages)
                i_sp += 1
                mix = self._sparse_out(lp, o, gate)
            x = self._mlp(lp, x + a * mix)
        row = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=0)
        return self._logits(p, row), (kbufs, vbufs), state, stats

    def decode_shard_paged(self, p, token, pk, pv, tables, lengths, active, mode: str, state):
        """One decode step. ``pk``, ``pv`` (n_sparse, pages, 1, bs, Hkv * D)
        the sparse layers' K and V pools under the block table, ``bs`` the
        selection's block; ``state`` the slots' state. An active slot's
        lightning layers advance their state; its sparse layers write their
        one K/V row through the table (an inactive slot's to the NULL
        block), complete a pooled key where the row ends one, select from
        the slot's pooled keys and attend the selected pages where they lie
        (``kernels/block_sparse_attn.py``; the pool gathered at the table's
        whole extent where the shapes do not let it). Returns (logits (B,
        V), pk, pv, state, stats)."""
        del mode
        c = self.config
        bs = pk.shape[3]
        assert bs == c.block_size, (
            f"the pool's page ({bs}) must be the selection's block ({c.block_size})")
        B, max_blocks = tables.shape
        assert max_blocks <= -(-c.max_len // bs), (
            f"a table of {max_blocks} pages is longer than the config's max_len {c.max_len}")
        pos = lengths.astype(jnp.int32)
        slots = jnp.arange(B)
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        phys = jnp.where(active, blk, 0)
        sub = pos % bs
        seen = jnp.where(active, pos + 1, 0)
        a = jnp.asarray(c.residual_scale, jnp.dtype(c.dtype))
        state = {k: list(v) for k, v in state.items()}
        stats = self.step_stats()
        stats["linear_rows"] = stats["linear_rows"].at[1].add(active.sum(dtype=jnp.int32))
        x = self._embed(p, token)
        i_lin = i_sp = 0
        for layer, lp in enumerate(p["layers"]):
            u = sl.rms(x, lp["ln1"], c.rms_eps)
            if c.mixer_types[layer] == "lightning":
                q, k, v, gate = self._split_lightning(lp, u, pos)
                o, state["linear"][i_lin] = sl.lightning_step(
                    q, k, v, state["linear"][i_lin], active)
                i_lin += 1
                mix = self._lightning_out(lp, o, gate)
            else:
                q, k_row, v_row, gate = self._split_sparse(lp, u)
                pk = pk.at[i_sp, phys, 0, sub].set(k_row)
                pv = pv.at[i_sp, phys, 0, sub].set(v_row)
                j, keys = sl.pool_step(pk[i_sp], tables, pos, active,
                                       c.kernel_size, c.kernel_stride)
                pooled = state["pooled"][i_sp].at[slots, j].set(keys, mode="drop")
                state["pooled"][i_sp] = pooled
                # a slot a row: its own pooled keys, its own position
                sel, counts = jax.vmap(lambda q1, c1, n1, on: self._select(
                    q1[None], c1, n1[None], on[None], max_blocks))(q, pooled, pos, active)
                o, pages = sl.attend_step(q, pk, pv, i_sp, tables, sel[:, :, 0], seen, c.topk)
                stats = self._counted(stats, 1, counts.sum(axis=0), pages)
                i_sp += 1
                mix = self._sparse_out(lp, o, gate)
            x = self._mlp(lp, x + a * mix)
        return self._logits(p, x), pk, pv, state, stats

    # -- what this model does not have --------------------------------------
    def _paged_only(self, *_, **__):
        raise NotImplementedError(
            "SparseLinearLLM is served through the paged programs only "
            "(chunked prefill and decode against the pool and the slots' state)")

    prefill_shard = decode_shard = verify_shard = _paged_only
