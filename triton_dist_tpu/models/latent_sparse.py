"""A decoder whose stack is a LIST of layer kinds: latent attention in every
layer, a learned sparse selection that some layers compute (``full``),
the layers above them borrow (``shared``) and others do without (``none``:
every earlier position is attended to), and a feed-forward that is a
dense SwiGLU in the leading layers and a sigmoid-routed expert layer with a
shared expert after them (``layers/latent_sparse.py`` holds the math).

It is served by the same ``Engine`` programs, scheduler, ledger and block
tables as ``DenseLLM``; what differs is declared, not forked:

* ``cache_rows()``: a token's cache is one latent row of ``kv_lora_rank +
  qk_rope_head_dim`` values a layer, and one index key of
  ``index_head_dim`` values on the layers that own an indexer. The engine
  sizes its prompt buffers and the paged pools from this declaration; the
  pool pair of every program is (latent pool, index-key pool) where
  ``DenseLLM``'s is (K pool, V pool). A model none of whose layers owns an
  indexer declares the same pair with NO layers in its second kind: the
  index-key pool and buffers are empty arrays that cost nothing, and the
  pool manager, the ledger and the engine's programs are as they were. A
  model with a ``none`` layer and a latent rank in whole lanes keeps its
  latent row in whole lanes too (``cache_row``: 576 values in 640), which
  is what the chip's tiled memory holds anyway, so that the decode kernel
  can copy a page where it lies.
* ``step_stats``: the per-expert row counts, and what the selecting layers
  saw and selected, leave the device as one more small output of the
  prefill chunk and of the decode chunk (summed over the chunk's steps on
  the device), never through a host callback.
* ``experts_held``: the layer routes over every published expert and
  computes the held ones' part of the result with the shared expert; one
  chip of a wide expert-parallel deployment runs it without its exchange,
  and nothing stands in for the absent chips.

The layers are unrolled: three kinds of layer do not scan as one. One mesh
rank only for now (experts over several chips need the exchange; ROADMAP).
Only the paged serving programs exist (chunked prefill into a prompt
buffer, decode against the pools); the contiguous-cache programs raise.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels import latent_flash
from triton_dist_tpu.layers import latent_sparse as ls
from triton_dist_tpu.models.kv_cache import CacheRow
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import DistContext


@dataclasses.dataclass(frozen=True)
class LatentSparseConfig:
    """Defaults are a toy with the structure of the large models of this
    kind: 1 dense + 4 expert layers, indexers full/shared x3/full."""

    vocab_size: int = 256
    hidden_size: int = 64
    num_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 12
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    index_n_heads: int = 4
    index_head_dim: int = 16
    index_rope_dim: int = 8
    index_topk: int = 16
    index_norm_eps: float = 1e-6
    #: per layer: "dense" | "experts", and "full" | "shared"
    mlp_kinds: tuple = ("dense", "experts", "experts", "experts", "experts")
    index_kinds: tuple = ("full", "shared", "shared", "shared", "full")
    intermediate_size: int = 128
    expert_intermediate_size: int = 32
    num_experts: int = 16  # the router's width: every published expert
    experts_per_token: int = 2
    #: (first, count) of the experts whose weights are here
    experts_held: tuple = (0, 16)
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: whether the router adds a correction bias to the scores it ranks
    router_bias: bool = True
    rope_theta: float = 8e6
    #: ``layers/latent_sparse.py:Yarn`` or None for the plain table
    rope_scaling: object = None
    rms_eps: float = 1e-5
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.mlp_kinds) == len(self.index_kinds)
        assert set(self.mlp_kinds) <= {"dense", "experts"}
        assert set(self.index_kinds) <= {"full", "shared", "none"}
        lent = False
        for kind in self.index_kinds:
            assert kind != "shared" or lent, "a shared layer borrows from a full one below it"
            lent = lent or kind == "full"
        first, count = self.experts_held
        assert 0 <= first and first + count <= self.num_experts

    @property
    def num_layers(self) -> int:
        return len(self.mlp_kinds)

    @property
    def latent_row(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """The latent row's width in the pool and the prompt buffers: the
        row itself, or the whole lanes it lies in where a layer without an
        indexer reads the pool in place (see the module docstring)."""
        if "none" in self.index_kinds and self.kv_lora_rank % 128 == 0:
            return -(-self.latent_row // 128) * 128
        return self.latent_row

    @property
    def softmax_scale(self) -> float:
        """The attention's scale: one over the root of the query/key head,
        times YaRN's ``mscale ** 2`` where the table is scaled."""
        scale = float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        return scale if self.rope_scaling is None else scale * self.rope_scaling.softmax_mscale

    @property
    def index_layers(self) -> tuple:
        """The layers that own an indexer, in order: layer ``index_layers[i]``
        keeps its index keys in layer ``i`` of the index-key pool."""
        return tuple(i for i, k in enumerate(self.index_kinds) if k == "full")


# ----------------------------------------------------------------- weights

def layer_tensors(c: LatentSparseConfig, layer: int) -> list:
    """(name, shape, scale or None for 1/sqrt(shape[0])) of one layer's
    drawn tensors, in draw order; norms are ones and are not drawn."""
    d, H = c.hidden_size, c.num_heads
    out = [
        ("w_dq", (d, c.q_lora_rank), None),
        ("w_uq", (c.q_lora_rank, H * (c.qk_nope_head_dim + c.qk_rope_head_dim)), None),
        ("w_dkv", (d, c.latent_row), None),
        ("w_uk", (c.kv_lora_rank, H, c.qk_nope_head_dim), 1 / math.sqrt(c.kv_lora_rank)),
        ("w_uv", (c.kv_lora_rank, H, c.v_head_dim), 1 / math.sqrt(c.kv_lora_rank)),
        ("w_o", (H * c.v_head_dim, d), None),
    ]
    if c.index_kinds[layer] == "full":
        out += [
            ("w_iq", (c.q_lora_rank, c.index_n_heads * c.index_head_dim), None),
            ("w_ik", (d, c.index_head_dim), None),
            ("w_iw", (d, c.index_n_heads), None),
        ]
    if c.mlp_kinds[layer] == "dense":
        ff = c.intermediate_size
        out += [("w_gate", (d, ff), None), ("w_up", (d, ff), None), ("w_down", (ff, d), None)]
    else:
        f, E = c.expert_intermediate_size, c.experts_held[1]
        out += [
            ("router", (d, c.num_experts), None),
            *([("router_bias", (c.num_experts,), 0.1)] if c.router_bias else []),
            ("e_gate", (E, d, f), 1 / math.sqrt(d)),
            ("e_up", (E, d, f), 1 / math.sqrt(d)),
            ("e_down", (E, f, d), 1 / math.sqrt(f)),
            ("s_gate", (d, f), None), ("s_up", (d, f), None), ("s_down", (f, d), None),
        ]
    return out


def layer_ones(c: LatentSparseConfig, layer: int) -> list:
    out = [("ln1", c.hidden_size), ("ln2", c.hidden_size),
           ("q_norm", c.q_lora_rank), ("kv_norm", c.kv_lora_rank)]
    if c.index_kinds[layer] == "full":
        out.append(("ik_norm_w", c.index_head_dim))
    return out


@functools.lru_cache(maxsize=None)
def _drawer(shape, scale, dtype: str, sharding):
    """One jitted draw a distinct (shape, scale, type): normal times the
    scale (1/sqrt(fan_in) unless given), cast, in one fusion, so that no
    float32 copy of a tensor is ever in memory; layers of one kind share
    the compiled draws."""
    dt = jnp.dtype(dtype)
    scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale

    def draw(key):
        x = jax.random.normal(key, shape, jnp.float32)
        if dt == jnp.float32:
            x = jax.lax.optimization_barrier(x)  # as models/dense.py: the toys' rounding order
        return (x * scale).astype(dt)

    return jax.jit(draw, out_shardings=sharding)


def init_params(config: LatentSparseConfig, key, ctx: DistContext) -> dict:
    """Seeded random weights on the mesh. Tensor ``i`` of layer ``l`` is
    drawn from ``fold_in(fold_in(key, l), i)`` (the embedding and the head
    are tensors 0 and 1 of "layer" ``num_layers``): normal times
    1/sqrt(fan_in) (the embedding 0.02, the router's bias 0.1); norm weights
    1, the index key norm's bias 0. The router and its bias stay float32."""
    c = config
    rep = ctx.replicated()
    key = jnp.asarray(key)
    ones = lambda n: jax.device_put(jnp.ones((n,), jnp.dtype(c.dtype)), rep)
    tk = jax.random.fold_in(key, c.num_layers)
    params = {
        "embed": _drawer((c.vocab_size, c.hidden_size), 0.02, c.dtype, rep)(
            jax.random.fold_in(tk, 0)),
        "final_norm": ones(c.hidden_size),
        "lm_head": _drawer((c.hidden_size, c.vocab_size), None, c.dtype, rep)(
            jax.random.fold_in(tk, 1)),
        "layers": [],
    }
    for layer in range(c.num_layers):
        lk = jax.random.fold_in(key, layer)
        lp = {}
        for i, (name, shape, scale) in enumerate(layer_tensors(c, layer)):
            dtype = "float32" if name in ("router", "router_bias") else c.dtype
            lp[name] = _drawer(shape, scale, dtype, rep)(jax.random.fold_in(lk, i))
        for name, n in layer_ones(c, layer):
            lp[name] = ones(n)
        if c.index_kinds[layer] == "full":
            lp["ik_norm_b"] = jnp.zeros_like(lp["ik_norm_w"])
        params["layers"].append(lp)
    return params


# ------------------------------------------------------------------- model


class LatentSparseLLM:
    """See the module docstring. The engine's hooks are ``param_specs``,
    ``cache_rows``, ``step_stats`` / ``publish_step_stats``,
    ``prefill_chunk_shard`` and ``decode_shard_paged``."""

    def __init__(self, config: LatentSparseConfig, ctx: DistContext, params=None, key=None):
        self.config = config
        self.ctx = ctx
        self.axis = "tp"
        self.world = ctx.num_ranks(self.axis)
        if self.world != 1:
            raise NotImplementedError(
                "the held experts run without their exchange: one mesh rank only")
        if params is None:
            params = init_params(config, key if key is not None else jax.random.PRNGKey(0), ctx)
        self.params = params

    # -- what the engine reads -------------------------------------------
    def param_specs(self):
        return jax.tree.map(lambda _: P(), self.params)

    def cache_rows(self):
        c = self.config
        return (CacheRow("latent", c.num_layers, 1, c.cache_row),
                CacheRow("index_key", len(c.index_layers), 1, c.index_head_dim))

    def step_stats(self):
        """Zeros of what every step program returns beside its result and
        sums over a chunk on the device: rows each published expert was
        chosen by, expert-layer calls, and on the layers that select the
        positions the queries could see and the positions selected for
        them, [in prefill chunks, in decode steps], and the prefill rows
        whose selection had more equal scores at its boundary than places
        left (``layers/latent_sparse.py:select_mask``'s slow path). Rows
        nobody sent (a chunk's padding, an inactive slot) are in none of
        those. ``attend_tiles``: over the layers of the prefill chunks, the
        tiles of the attention's mask that allow anything, [which the flash
        kernel visits, those at or before the chunk's last position, and
        those of a layer with no indexer that the kernel attends with no
        mask] (``layers/latent_sparse.py:attend_tiles``, ``causal_tiles``).
        On the layers with no indexer, [prefill, decode]: ``rows_visible``
        the latent rows the queries could see and ``rows_read`` those the
        attention fetched for them (whole key tiles in prefill, whole tiles
        of pages in decode)."""
        return {"expert_rows": jnp.zeros((self.config.num_experts,), jnp.int32),
                "dispatches": jnp.zeros((), jnp.int32),
                "visible": jnp.zeros((2,), jnp.int32),
                "selected": jnp.zeros((2,), jnp.int32),
                "tie_rows": jnp.zeros((), jnp.int32),
                "attend_tiles": jnp.zeros((3,), jnp.int32),
                "rows_visible": jnp.zeros((2,), jnp.int32),
                "rows_read": jnp.zeros((2,), jnp.int32)}

    def publish_step_stats(self, stats) -> None:
        """Host side: feed the counters from a finished program's stats."""
        stats = jax.device_get(stats)
        rows = stats["expert_rows"]
        for e in np.flatnonzero(rows):
            telemetry.inc("tdt_ep_expert_tokens_total", float(rows[e]), expert=int(e))
        telemetry.inc("tdt_ep_dispatch_total", float(stats["dispatches"]), route="held")
        for i, phase in enumerate(("prefill", "decode")):
            telemetry.inc("tdt_dsa_positions_visible_total",
                          float(stats["visible"][i]), phase=phase)
            telemetry.inc("tdt_dsa_positions_selected_total",
                          float(stats["selected"][i]), phase=phase)
            telemetry.inc("tdt_latent_rows_visible_total",
                          float(stats["rows_visible"][i]), phase=phase)
            telemetry.inc("tdt_latent_rows_read_total",
                          float(stats["rows_read"][i]), phase=phase)
        telemetry.inc("tdt_dsa_select_tie_rows_total", float(stats["tie_rows"]),
                      phase="prefill")
        for i, kind in enumerate(("visited", "under_diagonal", "unmasked")):
            telemetry.inc("tdt_dsa_attend_tiles_total", float(stats["attend_tiles"][i]),
                          kind=kind)

    @staticmethod
    def _selected(stats, phase: int, rows, n_visible, chosen):
        """``stats`` with a selecting layer's counts added: ``rows`` (T,)
        the queries that are real, ``n_visible`` (T,) what each could see,
        ``chosen`` (T, S) bool what was selected for it."""
        seen = jnp.where(rows, n_visible, 0).sum()
        took = (chosen & rows[:, None]).sum(dtype=jnp.int32)
        return {**stats, "visible": stats["visible"].at[phase].add(seen),
                "selected": stats["selected"].at[phase].add(took)}

    @staticmethod
    def _read(stats, phase: int, rows):
        """``stats`` with a layer without an indexer's counts added: ``rows``
        (2,) the latent rows its real queries could see, and those fetched."""
        return {**stats, "rows_visible": stats["rows_visible"].at[phase].add(rows[0]),
                "rows_read": stats["rows_read"].at[phase].add(rows[1])}

    # -- layers ------------------------------------------------------------
    def _cache_row(self, row):
        """A token's latent row (T, latent_row) as the pool keeps it."""
        c = self.config
        return row if c.cache_row == c.latent_row else jnp.pad(
            row, ((0, 0), (0, c.cache_row - c.latent_row)))

    def _mlp(self, lp, layer: int, h, stats, rows):
        """The layer's feed-forward over ``h`` (T, d); ``rows`` (T,) bool
        marks the rows somebody sent: the others reach no routed expert and
        are not counted."""
        c = self.config
        if c.mlp_kinds[layer] == "dense":
            return ls.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), stats
        idx, gates = ls.route_sigmoid(
            h, lp["router"], lp.get("router_bias"), c.experts_per_token,
            c.routed_scaling_factor, c.norm_topk_prob)
        with jax.named_scope("held_experts"):
            y = ls.held_experts(h, idx, gates, lp["e_gate"], lp["e_up"], lp["e_down"],
                                c.experts_held[0], rows=rows)
        y = y + ls.swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"]).astype(jnp.float32)
        stats = {**stats, "dispatches": stats["dispatches"] + 1,
                 "expert_rows": stats["expert_rows"]
                 + ls.expert_counts(idx, c.num_experts, rows)}
        return y.astype(h.dtype), stats

    def prefill_chunk_shard(self, p, tokens, kbufs, vbufs, off, last_idx, mode: str):
        """One chunk of an incremental prefill. tokens (1, C); ``kbufs`` (L,
        1, 1, P, cache_row) and ``vbufs`` (F, 1, 1, P, index_head_dim) the
        prompt's running buffers of the two kinds of cache row; ``off`` the
        chunk's first position, ``last_idx`` the row whose logits matter.
        Rows past P (a padded final chunk) are dropped on insertion. Returns
        (logits (1, V), (kbufs, vbufs), stats)."""
        del mode  # one rank: nothing to reduce over
        c = self.config
        bsz, C = tokens.shape
        assert bsz == 1, "a prefill chunk is one request's"
        P_len = kbufs.shape[3]
        off = off.astype(jnp.int32)
        pos = off + jnp.arange(C, dtype=jnp.int32)
        x = p["embed"][tokens[0]]
        visible = jnp.arange(P_len, dtype=jnp.int32)[None, :] <= pos[:, None]
        sent = pos < P_len  # a padded final chunk's rows past the prompt are nobody's
        stats = self.step_stats()
        if "none" in c.index_kinds:  # causality alone: one table for all of them
            causal_table, causal_tiles = ls.causal_tiles(C, P_len, off)
            causal_rows = jnp.stack([jnp.where(sent, pos + 1, 0).sum(),
                                     ls.tile_rows_read(causal_table, sent, P_len)])
        for layer, lp in enumerate(p["layers"]):
            kind = c.index_kinds[layer]
            h = ls.rms_norm(x, lp["ln1"], c.rms_eps)
            c_q, q_nope, q_rope, row = ls.latent_project(lp, h, pos, c)
            kbufs = kbufs.at[layer, 0, 0, pos].set(self._cache_row(row), mode="drop")
            if kind == "full":
                fi = c.index_layers.index(layer)
                q_i, k_i, w_i = ls.index_project(lp, h, c_q, pos, c)
                vbufs = vbufs.at[fi, 0, 0, pos].set(k_i, mode="drop")
                scores = ls.index_scores(q_i, w_i, vbufs[fi, 0, 0])
                allowed, walked = ls.select_mask(scores, visible, c.index_topk)
                stats = self._selected(stats, 0, sent, pos + 1, allowed)
                stats = {**stats, "tie_rows": stats["tie_rows"]
                         + (walked & sent).sum(dtype=jnp.int32)}
                table, tiles = ls.attend_tiles(allowed, off)  # a shared layer's too
            if kind == "none":
                with jax.named_scope("latent_attend"):
                    a = ls.attend_expanded(q_nope, q_rope, kbufs[layer, 0, 0], None, off,
                                           lp["w_uk"], lp["w_uv"], c, table=causal_table)
                stats = self._read(stats, 0, causal_rows)
                stats = {**stats, "attend_tiles": stats["attend_tiles"] + causal_tiles}
            else:
                a = ls.attend_expanded(q_nope, q_rope, kbufs[layer, 0, 0], allowed, off,
                                       lp["w_uk"], lp["w_uv"], c, table=table)
                stats = {**stats, "attend_tiles": stats["attend_tiles"] + tiles}
            x = x + ls.mm(a, lp["w_o"])
            h = ls.rms_norm(x, lp["ln2"], c.rms_eps)
            m, stats = self._mlp(lp, layer, h, stats, sent)
            x = x + m
        x = ls.rms_norm(x, p["final_norm"], c.rms_eps)
        x_last = jax.lax.dynamic_slice(
            x, (jnp.clip(last_idx.astype(jnp.int32), 0, C - 1), 0), (1, x.shape[-1]))
        logits = jnp.dot(x_last, p["lm_head"], preferred_element_type=jnp.float32)
        return logits, (kbufs, vbufs), stats

    def decode_shard_paged(self, p, token, pk, pv, tables, lengths, active, mode: str):
        """One decode step against the pools where they lie. ``pk`` (L,
        blocks, 1, bs, cache_row) the latent pool, ``pv`` (F, blocks, 1, bs,
        index_head_dim) the index-key pool, both under the one block table.
        Each layer writes its one latent row a slot through the table (an
        inactive slot's to the NULL block), a ``full`` layer its index key
        too; a ``full`` layer reads the index keys of the table's whole
        extent and selects, and it and the layers that borrow from it gather
        the selected latent rows alone; a layer with no indexer attends over
        everything the slot has, read in the pool where it lies (one kernel,
        a slot's live tiles alone; over the gathered extent where the shapes
        do not tile). Returns (logits (B, V), pk, pv, stats)."""
        del mode
        c = self.config
        bs = pk.shape[3]
        B, max_blocks = tables.shape
        pos = lengths.astype(jnp.int32)
        x = p["embed"][token]
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        phys = jnp.where(active, blk, 0)
        sub = pos % bs
        span = jnp.arange(max_blocks * bs, dtype=jnp.int32)
        visible = span[None, :] <= pos[:, None]
        sel = real = None
        stats = self.step_stats()
        if "none" in c.index_kinds:
            in_place = latent_flash.decode_takes(
                c.num_heads, c.kv_lora_rank, pk.shape, max_blocks, pk.dtype.itemsize)
            seen = jnp.where(active, pos + 1, 0)  # an inactive slot reads nothing
            tile = (latent_flash.decode_tile_pages(pk.shape, max_blocks, pk.dtype.itemsize) * bs
                    if in_place else max_blocks * bs)
            dense_rows = jnp.stack([seen.sum(), (-(-seen // tile) * tile).sum()])
        for layer, lp in enumerate(p["layers"]):
            kind = c.index_kinds[layer]
            h = ls.rms_norm(x, lp["ln1"], c.rms_eps)
            c_q, q_nope, q_rope, row = ls.latent_project(lp, h, pos, c)
            pk = pk.at[layer, phys, 0, sub].set(self._cache_row(row))
            if kind == "full":
                fi = c.index_layers.index(layer)
                q_i, k_i, w_i = ls.index_project(lp, h, c_q, pos, c)
                pv = pv.at[fi, phys, 0, sub].set(k_i)
                keys = jnp.take(pv[fi, :, 0], tables, axis=0)  # (B, MB, bs, Di)
                keys = keys.reshape(B, max_blocks * bs, -1)
                scores = ls.index_scores_batched(q_i, w_i, keys)
                sel, real = ls.select_positions(scores, visible, c.index_topk)
                stats = self._selected(stats, 1, active, pos + 1, real)
            if kind == "none":
                with jax.named_scope("latent_attend"):
                    if in_place:
                        a = ls.attend_absorbed_paged(q_nope, q_rope, pk, layer, tables, seen,
                                                     lp["w_uk"], lp["w_uv"], c)
                    else:
                        rows = jnp.take(pk[layer, :, 0], tables, axis=0)  # (B, MB, bs, W)
                        rows = rows.reshape(B, max_blocks * bs, -1)
                        a = ls.attend_absorbed(q_nope, q_rope, rows, visible,
                                               lp["w_uk"], lp["w_uv"], c)
                stats = self._read(stats, 1, dense_rows)
            else:
                rows_blk = jnp.take_along_axis(tables, sel // bs, axis=1)
                rows = pk[layer, rows_blk, 0, sel % bs]  # (B, K, latent_row)
                a = ls.attend_absorbed(q_nope, q_rope, rows, real, lp["w_uk"], lp["w_uv"], c)
            x = x + ls.mm(a, lp["w_o"])
            h = ls.rms_norm(x, lp["ln2"], c.rms_eps)
            m, stats = self._mlp(lp, layer, h, stats, active)
            x = x + m
        x = ls.rms_norm(x, p["final_norm"], c.rms_eps)
        logits = jnp.dot(x, p["lm_head"], preferred_element_type=jnp.float32)
        return logits, pk, pv, stats

    # -- what this model does not have --------------------------------------
    def _paged_only(self, *_, **__):
        raise NotImplementedError(
            "LatentSparseLLM is served through the paged programs only "
            "(chunked prefill and decode against the pools)")

    prefill_shard = decode_shard = verify_shard = _paged_only
