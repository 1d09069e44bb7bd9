"""A prefill chunk's masked latent attention as one flash kernel
(``kernels/latent_flash.py``) against ``attend_expanded``'s XLA body
(``layers/latent_sparse.py:attend_expanded_xla``), on the CPU through the
Pallas interpreter at toy shapes with tiles of 8 queries by 16 keys (the
module's tile sizes are set for the test: the kernel has one way to size a
tile); then the kernel as the chunk program reaches it, through
``attend_expanded`` with ``attend_tiles``' table, at head dims that tile.

float32 at 2e-5 (``test_expanded_equals_absorbed``'s; the issue asks 2e-4):
no rounding differs, only the order of the float32 sums. bfloat16 at 2e-2:
the operands are rounded alike on both sides, and a score that differs in
its last float32 bit can round ``p`` to the next bfloat16.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import latent_flash as lf
from triton_dist_tpu.layers import latent_sparse as ls
from triton_dist_tpu.models.latent_sparse import LatentSparseConfig, LatentSparseLLM
from triton_dist_tpu.runtime.mesh import initialize_distributed

CFG = LatentSparseConfig()
TQ, TK = 8, 16
TOL = {"float32": dict(atol=2e-5, rtol=1e-5), "bfloat16": dict(atol=2e-2, rtol=2e-2)}


def _causal(C, P, off):
    return np.arange(P)[None, :] <= (off + np.arange(C))[:, None]


@functools.lru_cache(maxsize=None)
def _selected(C, P, off, k=6):
    """``select_mask``'s exact top-k of random index scores under causality."""
    scores = jax.random.normal(jax.random.PRNGKey(7), (C, P))
    return np.asarray(ls.select_mask(scores, jnp.asarray(_causal(C, P, off)), k)[0])


def _empty_key_tile(C, P, off):
    """No row allows a key of the second key tile, which lies under the
    diagonal: the table has a hole and the kernel steps over it."""
    a = _causal(C, P, off)
    a[:, TK:2 * TK] = False
    return a


def _blind_row(C, P, off):
    """Row 3 allows nothing in the first key tile (its tile is visited for
    its neighbours) and row 5 allows nothing anywhere: all zeros out."""
    a = _causal(C, P, off)
    a[3, :TK] = False
    a[5, :] = False
    return a


#: name -> (C, P, off, heads a grid step, mask maker). Two shapes in all, so
#: that the cases share their compiled programs.
CASES = {
    "causal_at_0": (16, 64, 0, 4, _causal),
    "off_32_three_key_tiles": (16, 64, 32, 4, _causal),
    "selection_mask": (16, 64, 48, 4, _selected),
    "empty_key_tile": (16, 64, 48, 4, _empty_key_tile),
    "blind_row": (16, 64, 16, 4, _blind_row),
    "ragged_p": (16, 40, 24, 2, _selected),          # P is no multiple of the key tile
    "padded_final_chunk": (16, 40, 32, 2, _causal),  # rows 8.. lie past the prompt
}



@functools.lru_cache(maxsize=None)
def _kernel(g):
    """The kernel jitted, one program a (head group, shape): the tile sizes
    are read when it is traced."""
    del g
    return jax.jit(lf.dsa_flash_prefill, static_argnames=("scale",))


def _tiles(monkeypatch, tq, tk, g):
    monkeypatch.setattr(lf, "QUERY_TILE", tq)
    monkeypatch.setattr(lf, "KEY_TILE", tk)
    monkeypatch.setattr(lf, "HEAD_GROUP", g)


_xla_body = jax.jit(lambda *a: ls.attend_expanded_xla(*a, CFG, head_group=2, key_block=16))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_xla_body(case, dtype, monkeypatch):
    c = CFG
    C, P, off, g, make = CASES[case]
    _tiles(monkeypatch, TQ, TK, g)
    dt = jnp.dtype(dtype)
    k = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    H = c.num_heads
    rows = jax.random.normal(k[0], (P, c.latent_row)).astype(dt)
    q_nope = jax.random.normal(k[1], (C, H, c.qk_nope_head_dim)).astype(dt)
    q_rope = jax.random.normal(k[2], (C, H, c.qk_rope_head_dim)).astype(dt)
    w_uk = (jax.random.normal(k[3], (c.kv_lora_rank, H, c.qk_nope_head_dim)) / 4).astype(dt)
    w_uv = (jax.random.normal(k[4], (c.kv_lora_rank, H, c.v_head_dim)) / 4).astype(dt)
    allowed = jnp.asarray(make(C, P, off))
    scale = float(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5

    got = _kernel(g)(q_nope, q_rope, rows, allowed, w_uk, w_uv, scale=scale)
    want = _xla_body(q_nope, q_rope, rows, allowed, jnp.int32(off), w_uk, w_uv)
    assert got.shape == want.shape == (C, H * c.v_head_dim) and got.dtype == dt
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, **TOL[dtype])

    # the case is the case its name says
    table = np.asarray(lf.tile_table(allowed, TQ, TK))
    assert table.shape == (C // TQ, -(-P // TK))
    if case == "causal_at_0":
        assert table.tolist() == [[True, False, False, False]] * 2
    if case == "off_32_three_key_tiles":
        assert table.tolist() == [[True, True, True, False]] * 2
    if case == "empty_key_tile":
        assert not table[:, 1].any() and table[:, [0, 2, 3]].all()
    if case == "blind_row":
        assert table[0, 0] and not np.asarray(allowed)[3, :TK].any()
        assert not got[5].any() and np.abs(got[3]).max() > 0
    if case == "ragged_p":
        assert P % TK
    if case == "padded_final_chunk":
        assert off + C > P and np.asarray(allowed)[P - off:].all()


def test_shape_rule_and_tile_sizes():
    """The published shapes take the kernel at tiles of whole lanes; the toy
    model's head dims do not tile, so its programs keep the XLA body; nor
    does a chunk whose accumulator, queries and mask would not fit the VMEM
    asked for (Mosaic refuses 3072 rows of float32 at 102 MiB and compiles
    4096 of bfloat16: the rule errs to the XLA body's side)."""
    assert lf.takes(2048, 64, 512, 192 + 64, 256, 2)
    assert lf.takes(2048, 64, 512, 192 + 64, 256, 4)
    assert lf.tile_sizes(2048, 16384) == (lf.QUERY_TILE, lf.KEY_TILE)
    assert lf.tile_sizes(2048, 4096 + 1)[1] == lf.KEY_TILE
    c = CFG
    assert not lf.takes(32, c.num_heads, c.kv_lora_rank,
                        c.qk_nope_head_dim + c.qk_rope_head_dim, c.v_head_dim, 4)
    assert not lf.takes(2048 + 8, 64, 512, 256, 256, 2)  # a ragged last query tile
    assert lf.takes(4096, 64, 512, 256, 256, 2)
    assert not lf.takes(3072, 64, 512, 256, 256, 4) and not lf.takes(8192, 64, 512, 256, 256, 2)
    assert lf.vmem_bytes(8192, 4, 512, 256, 256, 2) > lf.VMEM_LIMIT_BYTES
    assert lf.tile_sizes(16, 40) == (16, 128)


# ------------------------------------------------- as the chunk program runs it

#: The toy at head dims and a latent rank in whole lanes: ``attend_expanded``
#: takes the kernel.
TILED = LatentSparseConfig(num_heads=2, kv_lora_rank=128, qk_nope_head_dim=64,
                           qk_rope_head_dim=64, v_head_dim=128, experts_held=(0, 4))


def _spy(monkeypatch):
    """Every call of the kernel: (allowed, table) as values."""
    calls, inner = [], lf.dsa_flash_prefill
    monkeypatch.setattr(lf, "dsa_flash_prefill", lambda *a, **k: (
        calls.append((np.asarray(a[3]), np.asarray(k["table"]))), inner(*a, **k))[1])
    return calls


def test_attend_expanded_takes_the_kernel_with_attend_tiles_table(monkeypatch):
    """64 rows at offset 192 over a buffer of 320, tiles of 32 by 128: the
    third key tile lies past the diagonal and the table leaves it out."""
    _tiles(monkeypatch, 32, 128, 4)
    c, C, P, off = TILED, 64, 320, 192
    k = jax.random.split(jax.random.PRNGKey(31), 5)
    H = c.num_heads
    rows = jax.random.normal(k[0], (P, c.latent_row))
    q_nope = jax.random.normal(k[1], (C, H, c.qk_nope_head_dim))
    q_rope = jax.random.normal(k[2], (C, H, c.qk_rope_head_dim))
    w_uk = jax.random.normal(k[3], (c.kv_lora_rank, H, c.qk_nope_head_dim)) / 11
    w_uv = jax.random.normal(k[4], (c.kv_lora_rank, H, c.v_head_dim)) / 11
    allowed = jnp.asarray(_selected(C, P, off, k=40))
    table, counts = ls.attend_tiles(allowed, jnp.int32(off))
    assert np.asarray(table).tolist() == [[True, True, False]] * 2
    assert np.asarray(counts).tolist() == [4, 4, 0]
    calls = _spy(monkeypatch)
    got = ls.attend_expanded(q_nope, q_rope, rows, allowed, jnp.int32(off), w_uk, w_uv, c,
                             table=table)
    assert len(calls) == 1
    want = ls.attend_expanded_xla(q_nope, q_rope, rows, allowed, jnp.int32(off), w_uk, w_uv, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL["float32"])
    # a table that leaves out a tile somebody needs is not the same attention:
    # the comparison would see a wrong table reach the kernel
    holed = ls.attend_expanded(q_nope, q_rope, rows, allowed, jnp.int32(off), w_uk, w_uv, c,
                               table=table.at[1, 0].set(False))
    assert np.abs(np.asarray(holed) - np.asarray(want)).max() > 1e-2


def test_chunk_program_hands_each_layer_the_table_of_its_mask(monkeypatch):
    """The second chunk of a prompt through ``prefill_chunk_shard`` at dims
    that tile, over buffers that hold a first chunk's rows: every layer calls
    the kernel, the ``shared`` layer with the mask AND the table of the
    ``full`` layer below it, and logits and buffers are the XLA body's."""
    _tiles(monkeypatch, 32, 128, 4)
    c = dataclasses.replace(TILED, mlp_kinds=("dense", "experts", "experts"),
                            index_kinds=("full", "shared", "full"))
    C, P, off = 64, 160, 64
    ctx = initialize_distributed(devices=jax.devices()[:1], axis_names=("tp",),
                                 set_default=False)
    model = LatentSparseLLM(c, ctx, key=jax.random.PRNGKey(5))
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, c.vocab_size, size=(1, C)), jnp.int32)
    held = np.arange(P) < off  # what a first chunk left: any rows will do
    bufs = [jnp.where(held[:, None], jax.random.normal(jax.random.PRNGKey(i), (
        r.layers, 1, r.heads, P, r.width)), 0.0) for i, r in enumerate(model.cache_rows())]

    def chunk():  # op by op: the spy reads values
        logits, (kbufs, _), stats = model.prefill_chunk_shard(
            model.params, tokens, bufs[0], bufs[1], jnp.int32(off), jnp.int32(C - 1), "dist_ar")
        return np.asarray(logits), np.asarray(kbufs), np.asarray(stats["attend_tiles"]).tolist()

    calls = _spy(monkeypatch)
    got = chunk()
    assert len(calls) == c.num_layers
    for allowed, table in calls:
        assert (table == np.asarray(lf.tile_table(jnp.asarray(allowed), 32, 128))).all()
        assert (allowed.sum(axis=1) == c.index_topk).all()  # the selection binds
    assert (calls[1][0] == calls[0][0]).all()      # the shared layer's mask is the full one's
    assert not (calls[2][0] == calls[0][0]).all()  # and the two full layers select apart
    monkeypatch.setattr(lf, "takes", lambda *a: False)
    want = chunk()
    assert len(calls) == c.num_layers  # the XLA body alone
    np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got[1], want[1], atol=2e-4, rtol=2e-4)
    # positions 64..127 lie in the first key tile of 128: 2 query tiles x 1 a layer
    assert got[2] == want[2] == [2 * c.num_layers] * 2 + [0]  # a selection's: none unmasked
