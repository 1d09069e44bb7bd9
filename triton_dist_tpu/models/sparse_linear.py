"""A decoder of four kinds of mixer (``SparseLinearConfig.mixer_types``;
``layers/sparse_linear.py`` holds the math), in muP form. MiniCPM-SALA's two:

* **lightning** layers: linear attention over rotated, normed q and k with
  a float32 ``[D, D]`` state a head that decays a position, an output norm
  and an output gate;
* **sparse** layers: grouped-query attention without rotation in which a
  K/V head's query group attends ``topk`` blocks of ``block_size``
  positions: the first, the window ending at its own, and the best of the
  rest by a score read from POOLED keys (a mean of ``kernel_size`` keys
  every ``kernel_stride``) alone; an output gate.

And Granite-4.0-H's two:

* **mamba2** layers: Mamba-2's state-space mixer, heads with a scalar decay
  that is data, one group of ``B`` and ``C``, a float32 state a head and a
  conv tail a slot;
* **full** layers: grouped-query attention over every position before a
  query, no rotation.

``h0 = scale_emb E[token]``; every layer is ``h += a Mix(RMS(h)); h += a
SwiGLU(RMS(h))`` with ``a`` the configuration's ``residual_scale``
(MiniCPM's ``scale_depth / sqrt(published_layers)``, Granite's 0.22); the
logits are ``W_head RMS(h)`` times ``logit_scale`` (``dim_model_base /
hidden``, or 1/8), the head the embedding's transpose where it is tied.

It is served by the same ``Engine`` programs, scheduler, ledger and block
tables as the other models; what differs is declared:

* ``cache_rows()``: the attention layers' K and V rows (sparse or full), a
  layer's K/V heads side by side (``kv_rows(n_attention, 1, Hkv * D)``).
  **Where layers select, the pool's page must be the selection's block**
  (``InferenceServer(block_size=config.block_size)``): a selected block is
  then a page, and the selection through a slot's table row is the list of
  pages a decode step reads.
* ``slot_state(num_slots)``: the lightning layers' float32 state, the
  Mamba-2 layers' float32 state ``(N, heads * head_dim)`` (``ssm``; head
  ``h`` is the columns ``h P ... h P + P - 1``: ``[H, N, P]`` transposed
  head by head) and conv tail ``(K - 1, inner + 2 N)`` (``conv``), and the
  sparse layers' pooled keys at the fixed extent ``max_len //
  kernel_stride`` a slot (the selection's own cache: a sixteenth of the
  K rows' rate, so a fixed extent a slot is 1.6 MB at the published sizes,
  and the engine's pair of row kinds stays a pair). The engine allocates it
  for the slots, donates it through the decode chunk, carries one slot's
  worth from chunk to chunk of a prompt starting from zeros, and writes it
  into the slot when the prompt is done. Nothing of it is in the pool, so
  the server shares no prefix and rewinds no draft for such a model
  (``docs/serving.md``).
* ``step_stats``: of the kinds the model has, blocks selected, visible and
  learned (selected less forced), pages the attends fetched, rows the
  lightning layers took, rows the Mamba-2 layers advanced and, in decode,
  the slot-layer states the decode kernel moved and those that advanced,
  [in prefill chunks, in decode steps].

One mesh rank; paged serving programs only.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import sparse_linear as sl
from triton_dist_tpu.models.config import SparseLinearConfig
from triton_dist_tpu.models.hybrid_ssm import _dt_bias_drawer
from triton_dist_tpu.models.kv_cache import kv_rows
from triton_dist_tpu.models.latent_sparse import _drawer
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import DistContext

F32 = jnp.float32
BSA = ("bsa_selected", "bsa_visible", "bsa_learned", "bsa_pages_read")
#: A step's stats by the kind of mixer that counts them.
STATS = {"sparse": BSA, "lightning": ("linear_rows",),
         "mamba2": ("ssd_rows", "ssd_moved", "ssd_advanced")}
#: Tensors kept in float32 whatever the model's type, and how the drawn
#: ones that are not normal / sqrt(fan_in) are drawn.
SSD_F32 = ("a_log", "dt_bias", "d")
DRAWN_AS = {"conv_b": 0.02, "a_log": "a_log", "dt_bias": "dt"}


# ----------------------------------------------------------------- weights


def layer_tensors(c: SparseLinearConfig, layer: int) -> list:
    """(name, shape) of one layer's drawn tensors, in draw order (normal /
    sqrt(shape[0]) unless ``DRAWN_AS`` says otherwise). ``w_in`` is ``[q |
    k | v | g]`` (sparse, lightning), ``[q | k | v]`` (full) or ``[z | x | B
    | C | dt]`` (mamba2), ``w1`` ``[gate | up]``."""
    d, ff = c.hidden_size, c.intermediate_size
    kind = c.mixer_types[layer]
    qw, kvw = c.num_q_heads * c.head_dim, c.num_kv_heads * c.head_dim
    if kind == "sparse":
        mix = [("w_in", (d, 2 * qw + 2 * kvw)), ("w_o", (qw, d))]
    elif kind == "full":
        mix = [("w_in", (d, qw + 2 * kvw)), ("w_o", (qw, d))]
    elif kind == "mamba2":
        din, conv = c.mamba_inner, c.mamba_inner + 2 * c.mamba_state
        mix = [("w_in", (d, din + conv + c.mamba_heads)), ("conv_w", (c.mamba_conv, conv)),
               ("conv_b", (conv,)), ("a_log", (c.mamba_heads,)),
               ("dt_bias", (c.mamba_heads,)), ("w_out", (din, d))]
    else:
        w = c.lightning_heads * c.lightning_head_dim
        mix = [("w_in", (d, 4 * w)), ("w_o", (w, d))]
    return mix + [("w1", (d, 2 * ff)), ("w2", (ff, d))]


def layer_ones(c: SparseLinearConfig, layer: int) -> list:
    """(name, length) of one layer's norm weights: ones, not drawn."""
    d = c.hidden_size
    kind = c.mixer_types[layer]
    if kind == "sparse":
        return [("ln1", d), ("ln2", d), ("q_norm", c.head_dim), ("k_norm", c.head_dim)]
    if kind == "full":
        return [("ln1", d), ("ln2", d)]
    if kind == "mamba2":
        return [("ln1", d), ("ln2", d), ("norm", c.mamba_inner), ("d", c.mamba_heads)]
    D = c.lightning_head_dim
    return [("ln1", d), ("ln2", d), ("q_norm", D), ("k_norm", D), ("o_norm", D)]


def init_params(config: SparseLinearConfig, key, ctx: DistContext) -> dict:
    """Seeded random weights on the mesh: tensor ``i`` of layer ``l`` from
    ``fold_in(fold_in(key, l), i)``; the embedding (x ``embed_std``) and the
    head (where it is not tied) are tensors 0 and 1 of "layer" ``num_layers``;
    norm weights and Mamba-2's ``D`` 1. ``a_log`` is ``log A`` with ``A``
    uniform in [1, 16] and ``dt_bias`` the bias whose softplus is
    log-uniform in [1e-3, 1e-1] (Mamba-2's initialisation); those two and
    ``D`` stay float32."""
    c = config
    rep = ctx.replicated()
    key = jnp.asarray(key)
    ones = lambda name, n: jax.device_put(
        jnp.ones((n,), F32 if name in SSD_F32 else jnp.dtype(c.dtype)), rep)
    top = jax.random.fold_in(key, c.num_layers)
    params = {
        "embed": _drawer((c.vocab_size, c.hidden_size), c.embed_std, c.dtype, rep)(
            jax.random.fold_in(top, 0)),
        "final_norm": ones("final_norm", c.hidden_size),
        "layers": [],
    }
    if not c.tie_embeddings:
        params["head"] = _drawer((c.hidden_size, c.vocab_size), None, c.dtype, rep)(
            jax.random.fold_in(top, 1))

    def drawer(name, shape):
        how = DRAWN_AS.get(name)
        if how == "dt":
            return _dt_bias_drawer(shape, rep)
        if how == "a_log":
            return _a_log_drawer(shape, rep)
        return _drawer(shape, how, c.dtype, rep)

    for layer in range(c.num_layers):
        lk = jax.random.fold_in(key, layer)
        lp = {name: drawer(name, shape)(jax.random.fold_in(lk, i))
              for i, (name, shape) in enumerate(layer_tensors(c, layer))}
        lp.update({name: ones(name, n) for name, n in layer_ones(c, layer)})
        params["layers"].append(lp)
    return params


@functools.lru_cache(maxsize=None)
def _a_log_drawer(shape, sharding):
    """``log A`` with ``A`` uniform in [1, 16], float32."""
    return jax.jit(lambda key: jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)),
                   out_shardings=sharding)


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
        n_attention = len(c.layers_of("sparse")) + len(c.layers_of("full"))
        return kv_rows(n_attention, 1, c.num_kv_heads * c.head_dim)

    def slot_state(self, num_slots: int) -> dict:
        """Zeros of what ``num_slots`` slots keep whatever their length."""
        c = self.config
        H, D = c.lightning_heads, c.lightning_head_dim
        pooled = (num_slots, c.pooled_extent, c.num_kv_heads * c.head_dim)
        dt = jnp.dtype(c.dtype)
        n_ssd = len(c.layers_of("mamba2"))
        tail = (num_slots, c.mamba_conv - 1, c.mamba_inner + 2 * c.mamba_state)
        return {
            "linear": [jnp.zeros((num_slots, H, D, D), F32) for _ in c.layers_of("lightning")],
            "pooled": [jnp.zeros(pooled, dt) for _ in c.layers_of("sparse")],
            "ssm": [jnp.zeros((num_slots, c.mamba_state, c.mamba_inner), F32)
                    for _ in range(n_ssd)],
            "conv": [jnp.zeros(tail, dt) for _ in range(n_ssd)],
        }

    def step_stats(self):
        """Zeros of what every step program returns beside its result, [in
        prefill chunks, in decode steps], for the kinds of mixer the model
        has. Over the sparse layers, the K/V heads and the rows somebody
        sent: the blocks a row's group selected, those it could see (its own
        block's index + 1), the selected that were not forced, the pages the
        attend fetched (whole key tiles some query of a tile selected in
        prefill, whole tiles of the selected list in decode, the whole
        extent where the ``jax.numpy`` form runs). The rows the lightning
        layers took and the rows the Mamba-2 layers advanced (a row once,
        not once a layer). Over the Mamba-2 layers and a decode step's
        slots, the slot-layer states the decode kernel read and wrote
        (``ssd_moved``) and those that advanced (``ssd_advanced``): equal
        where idle slots are skipped."""
        return {name: jnp.zeros((2,), jnp.int32)
                for kind in STATS if kind in self.config.mixer_types for name in STATS[kind]}

    def publish_step_stats(self, stats) -> None:
        """Host side: feed the counters from a finished program's stats."""
        stats = jax.device_get(stats)
        for i, phase in enumerate(("prefill", "decode")):
            count = lambda name: float(stats[name][i])
            if "bsa_selected" in stats:
                telemetry.inc("tdt_bsa_blocks_selected_total", count("bsa_selected"), phase=phase)
                telemetry.inc("tdt_bsa_blocks_visible_total", count("bsa_visible"), phase=phase)
                telemetry.inc("tdt_bsa_blocks_learned_total", count("bsa_learned"), phase=phase)
                telemetry.inc("tdt_bsa_pages_read_total", count("bsa_pages_read"), phase=phase)
            if "linear_rows" in stats:
                telemetry.inc("tdt_linear_attn_rows_total", count("linear_rows"), phase=phase)
            if "ssd_rows" in stats:
                telemetry.inc("tdt_ssd_rows_total", count("ssd_rows"), phase=phase)
                telemetry.inc("tdt_ssd_state_slots_total", count("ssd_moved"), phase=phase,
                              kind="moved")
                telemetry.inc("tdt_ssd_state_slots_total", count("ssd_advanced"), phase=phase,
                              kind="advanced")

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
        for name, n in zip(BSA, (*counts, pages)):
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
        if c.tie_embeddings:
            return jax.lax.dot_general(x, p["embed"], (((1,), (1,)), ((), ())),
                                       preferred_element_type=F32) * c.logit_scale
        return jnp.dot(x, p["head"], preferred_element_type=F32) * c.logit_scale

    def _split_full(self, lp, u):
        """(q (..., Hq, D), a K row and a V row (..., Hkv * D))."""
        c = self.config
        qw, kvw = c.num_q_heads * c.head_dim, c.num_kv_heads * c.head_dim
        z = sl.mm(u, lp["w_in"])
        q = z[..., :qw].reshape(z.shape[:-1] + (c.num_q_heads, c.head_dim))
        return q, z[..., qw:qw + kvw], z[..., qw + kvw:]

    def _full_out(self, lp, o):
        return sl.mm(o.reshape(o.shape[:-2] + (-1,)).astype(lp["w_o"].dtype), lp["w_o"])

    def prefill_chunk_shard(self, p, tokens, kbufs, vbufs, off, last_idx, mode: str, state):
        """One chunk of an incremental prefill. tokens (1, C); ``kbufs``,
        ``vbufs`` (n_attention, 1, 1, P, Hkv * D) the prompt's running K and V
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
        for name in ("linear_rows", "ssd_rows"):
            if name in stats:
                stats[name] = stats[name].at[0].add(n_real)
        x = self._embed(p, tokens[0])
        i_lin = i_sp = i_ssd = 0
        for layer, lp in enumerate(p["layers"]):
            u = sl.rms(x, lp["ln1"], c.rms_eps)
            kind = c.mixer_types[layer]
            if kind == "lightning":
                q, k, v, gate = self._split_lightning(lp, u, pos)
                o, s1 = sl.lightning_chunk(q, k, v, state["linear"][i_lin][0], n_real)
                state["linear"][i_lin] = s1[None]
                i_lin += 1
                mix = self._lightning_out(lp, o, gate)
            elif kind == "mamba2":
                mix, tail, s1 = sl.mamba2_chunk(
                    lp, u, state["conv"][i_ssd][0], state["ssm"][i_ssd][0], n_real,
                    c.mamba_heads, c.mamba_state, c.mamba_chunk, c.rms_eps)
                state["conv"][i_ssd], state["ssm"][i_ssd] = tail[None], s1[None]
                i_ssd += 1
            elif kind == "full":
                q, k_row, v_row = self._split_full(lp, u)
                kbufs = kbufs.at[i_sp, 0, 0, pos].set(k_row, mode="drop")
                vbufs = vbufs.at[i_sp, 0, 0, pos].set(v_row, mode="drop")
                o = sl.attend_full_chunk(q, kbufs[i_sp, 0, 0], vbufs[i_sp, 0, 0], off,
                                         c.attention_scale)
                i_sp += 1
                mix = self._full_out(lp, o)
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
        """One decode step. ``pk``, ``pv`` (n_attention, pages, 1, bs, Hkv *
        D) the attention layers' K and V pools under the block table, ``bs``
        the selection's block where layers select; ``state`` the slots'
        state. An active slot's lightning and Mamba-2 layers advance their
        state (the Mamba-2 kernel reads and writes no other slot's); its
        full layers write their one K/V row through the table and attend
        every position up to their own where the rows lie
        (``kernels/shared_kv_decode.py``; gathered at the table's extent
        where the row is not whole lanes); its sparse layers write their
        one K/V row through the table (an inactive slot's to the NULL
        block), complete a pooled key where the row ends one, select from
        the slot's pooled keys and attend the selected pages where they lie
        (``kernels/block_sparse_attn.py``; the pool gathered at the table's
        whole extent where the shapes do not let it). Returns (logits (B,
        V), pk, pv, state, stats)."""
        del mode
        c = self.config
        bs = pk.shape[3]
        assert bs == c.block_size or not c.layers_of("sparse"), (
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
        n_active = active.sum(dtype=jnp.int32)
        for name in ("linear_rows", "ssd_rows"):
            if name in stats:
                stats[name] = stats[name].at[1].add(n_active)
        x = self._embed(p, token)
        i_lin = i_sp = i_ssd = 0
        for layer, lp in enumerate(p["layers"]):
            u = sl.rms(x, lp["ln1"], c.rms_eps)
            kind = c.mixer_types[layer]
            if kind == "lightning":
                q, k, v, gate = self._split_lightning(lp, u, pos)
                o, state["linear"][i_lin] = sl.lightning_step(
                    q, k, v, state["linear"][i_lin], active)
                i_lin += 1
                mix = self._lightning_out(lp, o, gate)
            elif kind == "mamba2":
                mix, state["conv"][i_ssd], state["ssm"][i_ssd], moved = sl.mamba2_step(
                    lp, u, state["conv"][i_ssd], state["ssm"][i_ssd], active, c.mamba_heads,
                    c.mamba_state, c.rms_eps)
                i_ssd += 1
                stats["ssd_moved"] = stats["ssd_moved"].at[1].add(moved)
                stats["ssd_advanced"] = stats["ssd_advanced"].at[1].add(n_active)
            elif kind == "full":
                q, k_row, v_row = self._split_full(lp, u)
                pk = pk.at[i_sp, phys, 0, sub].set(k_row)
                pv = pv.at[i_sp, phys, 0, sub].set(v_row)
                o = sl.attend_full_step(q, pk, pv, i_sp, tables, seen, c.attention_scale)
                i_sp += 1
                mix = self._full_out(lp, o)
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
