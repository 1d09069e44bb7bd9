"""A decoder of five kinds of layer, three kinds of state
(``HybridSSMConfig.layer_kind``; ``layers/hybrid_ssm.py`` holds the math):

* the first half alternates **Mamba-1** layers and **sliding-window**
  differential attention;
* the second half is a cross-decoder: one more Mamba layer, which also hands
  on its scan's output ``m``; one **full** differential-attention layer,
  whose K/V rows are the only ones the paged pool holds; then **gated memory
  units**, which gate ``m``, alternating with **cross-attention** layers,
  which bring a query and read the full layer's K/V.

Every layer is ``h += Mix(LN(h)); h += SwiGLU(LN'(h))``, LayerNorm with
weight and bias, no positional encoding anywhere, the head is the embedding.

It is served by the same ``Engine`` programs, scheduler, ledger and block
tables as the other models; what differs is declared:

* ``cache_rows()``: one layer's K and V rows a token, all heads side by side
  (``kv_rows(1, 1, Hkv * D)``: a head of 64 would fill half a lane tile),
  whatever the depth: eight layers read them.
* ``slot_state(num_slots)``: what a slot keeps whatever its length, a list
  an array a layer, the slot's axis first: the Mamba layers' conv tail and
  float32 state, the window layers' K and V rings (position ``i`` lives at
  ``i % window``). The engine allocates it for the slots, donates it through
  the decode chunk, carries one slot's worth from chunk to chunk of a
  prompt starting from zeros, and writes it into the slot when the prompt
  is done. Nothing of it is in the pool, so the server shares no prefix and
  rewinds no draft for such a model (``docs/serving.md``).
* ``step_stats``: the window layers' attended and visible positions, the
  rows scanned, and the positions the layers that read the pool fetched
  from it and could see, [in prefill chunks, in decode steps].

A prompt row that is not its last needs only the first half, the last Mamba
layer and the full layer's K/V: the layers above run for a prompt's last
row alone, on its final chunk (the program sees it from ``off + last_idx``
and the buffer's length). One mesh rank; paged serving programs only.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels import shared_kv_decode
from triton_dist_tpu.layers import hybrid_ssm as hs
from triton_dist_tpu.models.config import HybridSSMConfig
from triton_dist_tpu.models.kv_cache import kv_rows
from triton_dist_tpu.models.latent_sparse import _drawer
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import DistContext

F32 = jnp.float32
#: Tensors kept in float32 whatever the model's type: the scan's own.
SCAN_F32 = ("a_log", "d", "b_dt")


# ----------------------------------------------------------------- weights


def layer_tensors(c: HybridSSMConfig, layer: int) -> list:
    """(name, shape, how) of one layer's drawn tensors, in draw order.
    ``how``: None for normal / sqrt(shape[0]), a number for normal times
    it, ``"dt"`` for the bias whose softplus is log-uniform in [1e-3,
    1e-1]. Norm weights (1) and biases (0), ``a_log`` (log 1..N) and ``d``
    (1) are not drawn."""
    d, din, n, k, r = c.hidden_size, c.d_inner, c.d_state, c.d_conv, c.dt_rank
    hd = c.head_dim
    qw, kvw = c.num_q_heads * hd, c.num_kv_heads * hd
    kind = c.layer_kind(layer)
    lam = [(f"lam_{x}", (hd,), 0.1) for x in ("q1", "k1", "q2", "k2")]
    mix = {
        "mamba": [("w_in", (d, 2 * din), None), ("conv_w", (k, din), None),
                  ("conv_b", (din,), 0.02), ("w_x", (din, r + 2 * n), None),
                  ("w_dt", (r, din), None), ("b_dt", (din,), "dt"),
                  ("w_out", (din, d), None)],
        "gmu": [("w_in", (d, din), None), ("w_out", (din, d), None)],
        "cross": [("w_q", (d, qw), None), ("b_q", (qw,), 0.02),
                  ("w_o", (qw, d), None), ("b_o", (d,), 0.02)] + lam,
    }
    mix["window"] = mix["full"] = [
        ("w_qkv", (d, qw + 2 * kvw), None), ("b_qkv", (qw + 2 * kvw,), 0.02),
        ("w_o", (qw, d), None), ("b_o", (d,), 0.02)] + lam
    ff = c.intermediate_size
    return mix[kind] + [("w1", (d, 2 * ff), None), ("w2", (ff, d), None)]


def layer_fixed(c: HybridSSMConfig, layer: int) -> dict:
    """The tensors that are not drawn, as numpy-free constants: name ->
    (shape, value or "log_n")."""
    d = c.hidden_size
    out = {"ln1_w": ((d,), 1.0), "ln1_b": ((d,), 0.0),
           "ln2_w": ((d,), 1.0), "ln2_b": ((d,), 0.0)}
    kind = c.layer_kind(layer)
    if kind == "mamba":
        out["a_log"] = ((c.d_state, c.d_inner), "log_n")
        out["d"] = ((c.d_inner,), 1.0)
    elif kind != "gmu":
        out["subln"] = ((2 * c.head_dim,), 1.0)
    return out


@functools.lru_cache(maxsize=None)
def _dt_bias_drawer(shape, sharding):
    """The bias ``b`` with ``softplus(b)`` log-uniform in [1e-3, 1e-1], float32."""

    def draw(key):
        u = jax.random.uniform(key, shape, F32)
        dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus's inverse

    return jax.jit(draw, out_shardings=sharding)


def init_params(config: HybridSSMConfig, key, ctx: DistContext) -> dict:
    """Seeded random weights on the mesh: tensor ``i`` of layer ``l`` from
    ``fold_in(fold_in(key, l), i)``, the embedding (x 0.02) tensor 0 of
    "layer" ``num_layers``. ``a_log``, ``d`` and ``b_dt`` stay float32."""
    c = config
    rep = ctx.replicated()
    key = jnp.asarray(key)
    dt = jnp.dtype(c.dtype)

    def fixed(name, shape, value):
        if value == "log_n":
            x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))[:, None], shape)
        else:
            x = jnp.full(shape, value, F32)
        return jax.device_put(x.astype(F32 if name in SCAN_F32 else dt), rep)

    params = {
        "embed": _drawer((c.vocab_size, c.hidden_size), 0.02, c.dtype, rep)(
            jax.random.fold_in(jax.random.fold_in(key, c.num_layers), 0)),
        "final_w": fixed("final_w", (c.hidden_size,), 1.0),
        "final_b": fixed("final_b", (c.hidden_size,), 0.0),
        "layers": [],
    }
    for layer in range(c.num_layers):
        lk = jax.random.fold_in(key, layer)
        lp = {name: fixed(name, shape, value)
              for name, (shape, value) in layer_fixed(c, layer).items()}
        for i, (name, shape, how) in enumerate(layer_tensors(c, layer)):
            draw = _dt_bias_drawer(shape, rep) if how == "dt" else _drawer(shape, how, c.dtype, rep)
            lp[name] = draw(jax.random.fold_in(lk, i))
        params["layers"].append(lp)
    return params


# ------------------------------------------------------------------- model


class HybridSSMLLM:
    """See the module docstring. The engine's hooks are ``param_specs``,
    ``cache_rows``, ``slot_state``, ``step_stats`` / ``publish_step_stats``,
    ``prefill_chunk_shard`` and ``decode_shard_paged``."""

    def __init__(self, config: HybridSSMConfig, ctx: DistContext, params=None, key=None):
        self.config = config
        self.ctx = ctx
        self.axis = "tp"
        self.world = ctx.num_ranks(self.axis)
        if self.world != 1:
            raise NotImplementedError(
                "window rings and recurrent state are not sharded: one mesh rank only")
        if params is None:
            params = init_params(config, key if key is not None else jax.random.PRNGKey(0), ctx)
        self.params = params

    # -- what the engine reads -------------------------------------------
    def param_specs(self):
        return jax.tree.map(lambda _: P(), self.params)

    def cache_rows(self):
        c = self.config
        return kv_rows(1, 1, c.num_kv_heads * c.head_dim)

    def slot_state(self, num_slots: int) -> dict:
        """Zeros of what ``num_slots`` slots keep whatever their length."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        n_mamba, n_win = len(c.layers_of("mamba")), len(c.layers_of("window"))
        ring = (num_slots, c.sliding_window, c.num_kv_heads * c.head_dim)
        return {
            "conv": [jnp.zeros((num_slots, c.d_conv - 1, c.d_inner), dt) for _ in range(n_mamba)],
            "ssm": [jnp.zeros((num_slots, c.d_state, c.d_inner), F32) for _ in range(n_mamba)],
            "ring_k": [jnp.zeros(ring, dt) for _ in range(n_win)],
            "ring_v": [jnp.zeros(ring, dt) for _ in range(n_win)],
        }

    def step_stats(self):
        """Zeros of what every step program returns beside its result, [in
        prefill chunks, in decode steps]: over the window layers and the
        rows somebody sent, the positions a row attended to and those it
        could have seen (its position + 1); the rows the Mamba layers
        scanned (a row once, not once a layer); over the layers that read
        the pool and the decode rows somebody sent, the positions a step
        fetched from the pool (whole tiles up to the length in place, the
        table's extent gathered) and those its rows could see."""
        return {"swa_attended": jnp.zeros((2,), jnp.int32),
                "swa_visible": jnp.zeros((2,), jnp.int32),
                "ssm_tokens": jnp.zeros((2,), jnp.int32),
                "shared_kv_read": jnp.zeros((2,), jnp.int32),
                "shared_kv_visible": jnp.zeros((2,), jnp.int32)}

    def publish_step_stats(self, stats) -> None:
        """Host side: feed the counters from a finished program's stats."""
        stats = jax.device_get(stats)
        for i, phase in enumerate(("prefill", "decode")):
            telemetry.inc("tdt_swa_positions_attended_total",
                          float(stats["swa_attended"][i]), phase=phase)
            telemetry.inc("tdt_swa_positions_visible_total",
                          float(stats["swa_visible"][i]), phase=phase)
            telemetry.inc("tdt_ssm_tokens_total", float(stats["ssm_tokens"][i]), phase=phase)
            telemetry.inc("tdt_shared_kv_positions_read_total",
                          float(stats["shared_kv_read"][i]), phase=phase)
            telemetry.inc("tdt_shared_kv_positions_visible_total",
                          float(stats["shared_kv_visible"][i]), phase=phase)

    @staticmethod
    def _windowed(stats, phase: int, rows, mask, pos):
        seen = (mask & rows[:, None]).sum(dtype=jnp.int32)
        return {**stats, "swa_attended": stats["swa_attended"].at[phase].add(seen),
                "swa_visible": stats["swa_visible"].at[phase].add(
                    jnp.where(rows, pos + 1, 0).sum(dtype=jnp.int32))}

    # -- layers ------------------------------------------------------------
    def _heads(self, x, n: int):
        return x.reshape(x.shape[:-1] + (n, self.config.head_dim))

    def _qkv(self, lp, u):
        """(q (..., Hq, D), a K row and a V row (..., Hkv * D))."""
        c = self.config
        qw = c.num_q_heads * c.head_dim
        kvw = c.num_kv_heads * c.head_dim
        qkv = hs.mm(u, lp["w_qkv"]) + lp["b_qkv"]
        return (self._heads(qkv[..., :qw], c.num_q_heads),
                qkv[..., qw:qw + kvw], qkv[..., qw + kvw:])

    def _out(self, lp, a):
        return hs.mm(a, lp["w_o"]) + lp["b_o"]

    def _attend(self, lp, layer: int, q, k_rows, v_rows, mask):
        """A chunk's queries q (T, Hq, D), or one a slot (B, Hq, D) over the
        slot's own rows (B, S, Hkv * D)."""
        c = self.config
        args = (mask, hs.diff_lambda(lp, layer), layer, lp["subln"], c.layer_norm_eps)
        if k_rows.ndim == 3:
            a = hs.diff_attend_rows(q, k_rows, v_rows, *args)
        else:
            a = hs.diff_attend(q, self._heads(k_rows, c.num_kv_heads),
                               self._heads(v_rows, c.num_kv_heads), *args)
        return self._out(lp, a)

    def _mlp(self, lp, x):
        c = self.config
        return x + hs.swiglu(hs.layer_norm(x, lp["ln2_w"], lp["ln2_b"], c.layer_norm_eps),
                             lp["w1"], lp["w2"])

    def _logits(self, p, x):
        c = self.config
        x = hs.layer_norm(x, p["final_w"], p["final_b"], c.layer_norm_eps)
        return jax.lax.dot_general(x, p["embed"], (((1,), (1,)), ((), ())),
                                   preferred_element_type=F32)

    def prefill_chunk_shard(self, p, tokens, kbufs, vbufs, off, last_idx, mode: str, state):
        """One chunk of an incremental prefill. tokens (1, C); ``kbufs``,
        ``vbufs`` (1, 1, 1, P, Hkv * D) the prompt's running K and V rows of
        the full layer; ``state`` one slot's state, as the chunk before
        left it (zeros before the first); ``off`` the chunk's first
        position, ``last_idx`` the row whose logits matter. Rows past P (a
        padded final chunk) change nothing. Returns (logits (1, V): the
        last row's on a prompt's final chunk, else zeros; (kbufs, vbufs),
        state, stats)."""
        del mode  # one rank: nothing to reduce over
        c = self.config
        bsz, C = tokens.shape
        assert bsz == 1, "a prefill chunk is one request's"
        P_len = kbufs.shape[3]
        w = c.sliding_window
        half = c.num_layers // 2
        off = off.astype(jnp.int32)
        last_idx = jnp.clip(last_idx.astype(jnp.int32), 0, C - 1)
        pos = off + jnp.arange(C, dtype=jnp.int32)
        sent = pos < P_len
        n_real = jnp.clip(P_len - off, 0, C)
        last = jnp.minimum(off + C, P_len) - 1  # the newest position there is
        # the ring's entry r holds the newest position under ``off`` that is r mod w
        ring_at = (off - 1) - jnp.mod(off - 1 - jnp.arange(w, dtype=jnp.int32), w)
        key_pos = jnp.concatenate([ring_at, pos])
        in_window = ((key_pos[None, :] >= 0) & (key_pos[None, :] <= pos[:, None])
                     & (key_pos[None, :] > pos[:, None] - w))
        # after the chunk, entry r holds the newest position up to ``last``
        ring_next = last - jnp.mod(last - jnp.arange(w, dtype=jnp.int32), w)
        from_chunk = (ring_next >= off)[:, None]
        chunk_row = jnp.clip(ring_next - off, 0, C - 1)
        state = {k: list(v) for k, v in state.items()}
        stats = self.step_stats()
        stats["ssm_tokens"] = stats["ssm_tokens"].at[0].add(n_real)
        x = p["embed"][tokens[0]]
        i_mamba = i_win = 0
        m = None
        for layer in range(half + 2):
            lp = p["layers"][layer]
            kind = c.layer_kind(layer)
            u = hs.layer_norm(x, lp["ln1_w"], lp["ln1_b"], c.layer_norm_eps)
            if kind == "mamba":
                mix, m, tail, s = hs.mamba_chunk(
                    lp, u, state["conv"][i_mamba][0], state["ssm"][i_mamba][0], n_real)
                state["conv"][i_mamba], state["ssm"][i_mamba] = tail[None], s[None]
                i_mamba += 1
                x = self._mlp(lp, x + mix)
            elif kind == "window":
                q, k, v = self._qkv(lp, u)
                rk, rv = state["ring_k"][i_win][0], state["ring_v"][i_win][0]
                mix = self._attend(lp, layer, q, jnp.concatenate([rk, k]),
                                   jnp.concatenate([rv, v]), in_window)
                stats = self._windowed(stats, 0, sent, in_window, pos)
                state["ring_k"][i_win] = jnp.where(from_chunk, k[chunk_row], rk)[None]
                state["ring_v"][i_win] = jnp.where(from_chunk, v[chunk_row], rv)[None]
                i_win += 1
                x = self._mlp(lp, x + mix)
            else:  # the full layer: its K/V for every row, the rest for the last
                _, k, v = self._qkv(lp, u)
                kbufs = kbufs.at[0, 0, 0, pos].set(k, mode="drop")
                vbufs = vbufs.at[0, 0, 0, pos].set(v, mode="drop")

        def above(x_last, m_last, kb, vb):
            """Layers ``half + 1`` and up, for the row at ``off + last_idx``."""
            qp = off + last_idx
            k_all, v_all = kb[0, 0, 0], vb[0, 0, 0]
            visible = (jnp.arange(P_len, dtype=jnp.int32) <= qp)[None, :]
            x1 = x_last
            for layer in range(half + 1, c.num_layers):
                lp = p["layers"][layer]
                kind = c.layer_kind(layer)
                u = hs.layer_norm(x1, lp["ln1_w"], lp["ln1_b"], c.layer_norm_eps)
                if kind == "gmu":
                    mix = hs.gmu(lp, u, m_last)
                else:
                    q = (self._qkv(lp, u)[0] if kind == "full" else
                         self._heads(hs.mm(u, lp["w_q"]) + lp["b_q"], c.num_q_heads))
                    mix = self._attend(lp, layer, q, k_all, v_all, visible)
                x1 = self._mlp(lp, x1 + mix)
            return self._logits(p, x1)

        row = lambda z: jax.lax.dynamic_slice_in_dim(z, last_idx, 1, axis=0)
        logits = jax.lax.cond(
            off + last_idx == P_len - 1, above,
            lambda *_: jnp.zeros((1, c.vocab_size), F32), row(x), row(m), kbufs, vbufs)
        return logits, (kbufs, vbufs), state, stats

    def decode_shard_paged(self, p, token, pk, pv, tables, lengths, active, mode: str, state):
        """One decode step. ``pk``, ``pv`` (1, blocks, 1, bs, Hkv * D) the full
        layer's K and V pools under the block table; ``state`` the slots'
        state. An active slot's Mamba layers advance their tail and state,
        its window layers write position ``i`` at ``i % window`` of their
        rings, the full layer writes its one K/V row through the table (an
        inactive slot's to the NULL block, its state left as it was); the
        full layer and every cross layer read the pool's rows through the
        table: in place, a slot's live tiles only, where the shapes let
        ``kernels/shared_kv_decode.py`` take them, else gathered once at
        the table's whole extent. Returns (logits (B, V), pk, pv, state,
        stats)."""
        del mode
        c = self.config
        w = c.sliding_window
        bs = pk.shape[3]
        B, max_blocks = tables.shape
        pos = lengths.astype(jnp.int32)
        slots = jnp.arange(B)
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        phys = jnp.where(active, blk, 0)
        sub = pos % bs
        ring_row = jnp.where(active, pos % w, w)  # an inactive slot's write is dropped
        ring_at = pos[:, None] - jnp.mod(pos[:, None] - jnp.arange(w, dtype=jnp.int32)[None], w)
        in_window = ring_at >= 0  # (B, w)
        seen = jnp.where(active, pos + 1, 0)  # the positions a row somebody sent may see
        # by shape alone: the kernel that reads the pool where it lies, or
        # the pool gathered through the table at its whole extent
        in_place = shared_kv_decode.takes(c.num_q_heads, pk.shape, max_blocks, pk.dtype.itemsize)
        if in_place:
            tile = bs * shared_kv_decode.tile_pages(pk.shape, max_blocks, pk.dtype.itemsize)
            fetched = -(-seen // tile) * tile
        else:
            visible = jnp.arange(max_blocks * bs, dtype=jnp.int32)[None] <= pos[:, None]
            fetched = jnp.where(active, max_blocks * bs, 0)
        state = {k: list(v) for k, v in state.items()}
        stats = self.step_stats()
        stats["ssm_tokens"] = stats["ssm_tokens"].at[1].add(active.sum(dtype=jnp.int32))
        readers = 1 + len(c.layers_of("cross"))
        stats["shared_kv_read"] = stats["shared_kv_read"].at[1].add(
            readers * fetched.sum(dtype=jnp.int32))
        stats["shared_kv_visible"] = stats["shared_kv_visible"].at[1].add(
            readers * seen.sum(dtype=jnp.int32))
        x = p["embed"][token]
        i_mamba = i_win = 0
        m = k_all = v_all = None
        for layer, lp in enumerate(p["layers"]):
            kind = c.layer_kind(layer)
            u = hs.layer_norm(x, lp["ln1_w"], lp["ln1_b"], c.layer_norm_eps)
            if kind == "mamba":
                mix, m, state["conv"][i_mamba], state["ssm"][i_mamba] = hs.mamba_step(
                    lp, u, state["conv"][i_mamba], state["ssm"][i_mamba], active)
                i_mamba += 1
            elif kind == "gmu":
                mix = hs.gmu(lp, u, m)
            elif kind == "window":
                q, k, v = self._qkv(lp, u)
                rk = state["ring_k"][i_win].at[slots, ring_row].set(k, mode="drop")
                rv = state["ring_v"][i_win].at[slots, ring_row].set(v, mode="drop")
                state["ring_k"][i_win], state["ring_v"][i_win] = rk, rv
                i_win += 1
                mix = self._attend(lp, layer, q, rk, rv, in_window)
                stats = self._windowed(stats, 1, active, in_window, pos)
            else:
                if kind == "full":
                    q, k, v = self._qkv(lp, u)
                    pk = pk.at[0, phys, 0, sub].set(k)
                    pv = pv.at[0, phys, 0, sub].set(v)
                    if not in_place:
                        # a table holds block numbers of the pool: nothing to fill in
                        through = lambda pool: jnp.take(
                            pool[0, :, 0], tables, axis=0, mode="clip").reshape(
                                B, max_blocks * bs, -1)
                        k_all, v_all = through(pk), through(pv)
                else:
                    q = self._heads(hs.mm(u, lp["w_q"]) + lp["b_q"], c.num_q_heads)
                if in_place:
                    mix = self._out(lp, hs.diff_attend_pool(
                        q, pk, pv, tables, seen, hs.diff_lambda(lp, layer), layer, lp["subln"],
                        c.layer_norm_eps))
                else:
                    mix = self._attend(lp, layer, q, k_all, v_all, visible)
            x = self._mlp(lp, x + mix)
        return self._logits(p, x), pk, pv, state, stats

    # -- what this model does not have --------------------------------------
    def _paged_only(self, *_, **__):
        raise NotImplementedError(
            "HybridSSMLLM is served through the paged programs only "
            "(chunked prefill and decode against the pool and the slots' state)")

    prefill_shard = decode_shard = verify_shard = _paged_only
