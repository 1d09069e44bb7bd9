"""``kernels/block_sparse_attn.py`` interpreted on the CPU at heads of 128
lanes against the ``jax.numpy`` form (``layers/sparse_linear.py:attend_xla``),
and the selection that feeds both against a brute-force form: forced
blocks, ties and the dense special case included.

The tiles are the module's constants (the kernels take no tile argument);
the tests set them with ``monkeypatch`` so that a few hundred positions
cross tiles. Tolerance: float32 on both sides, the sums in another order
(an online softmax a tile at a time): 2e-5 on outputs of size ~1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import block_sparse_attn as bsa
from triton_dist_tpu.layers import sparse_linear as sl

HKV, G, D, BLOCK = 2, 8, 128, 16
TOL = 2e-5


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _selection(rng, own, nb, extra):
    """(Hkv, T, nb) bool: each row's own block, block 0 and ``extra`` random
    earlier ones."""
    sel = np.zeros((HKV, len(own), nb), bool)
    for g in range(HKV):
        for t, b in enumerate(own):
            sel[g, t, [0, b]] = True
            if b > 1 and extra:
                sel[g, t, rng.integers(0, b, size=extra)] = True
    return sel


@pytest.mark.parametrize("off,C,P,extra", [(256, 64, 400, 0), (0, 40, 40, 1)],
                         ids=["deep_chunk_ragged_buffer", "first_chunk_padded"])
def test_prefill_kernel_matches_the_plain_form(monkeypatch, off, C, P, extra):
    monkeypatch.setattr(bsa, "QUERY_TILE", 32)
    monkeypatch.setattr(bsa, "KEY_TILE", 128)
    rng = np.random.default_rng(off + C)
    q = _rand(rng, C, HKV, G, D)
    k, v = _rand(rng, P, HKV * D), _rand(rng, P, HKV * D)
    nb = -(-P // BLOCK)
    pos = off + np.arange(C)
    sel = jnp.asarray(_selection(rng, pos // BLOCK, nb, extra))
    assert bsa.takes(G, D, BLOCK, 4)
    tq, tk = bsa.prefill_tiles(C, P, BLOCK)
    table = np.asarray(bsa.tile_table(sel, tq, tk // BLOCK))
    if not extra:  # the first block and the own: the key tile between them is nobody's
        assert table.any(axis=(0, 1)).tolist() == [True, False, True, False]
    got = jax.jit(lambda *a: bsa.bsa_prefill(*a, block=BLOCK, scale=D ** -0.5))(
        q, k, v, sel, jnp.int32(off))
    heads = lambda z: z.reshape(P, HKV, D)
    want = sl.attend_xla(q, heads(k), heads(v), sel, jnp.asarray(pos), BLOCK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
    out, pages = sl.attend_chunk(q, k, v, sel, jnp.int32(off), BLOCK)
    assert int(pages) == table.sum() * (tk // BLOCK)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(got))


def test_decode_kernel_walks_the_selected_pages_alone(monkeypatch):
    """Four slots: a long one with a full list, one with fewer blocks than
    ``topk`` (the dense special case), one at position 0, one nobody sent."""
    monkeypatch.setattr(bsa, "DECODE_TILE", 64)  # 4 pages a tile
    topk, mb, layers = 8, 12, 2
    rng = np.random.default_rng(3)
    seen = np.asarray([mb * BLOCK - 5, 3 * BLOCK + 1, 1, 0])
    b = len(seen)
    pk, pv = _rand(rng, layers, b * mb + 1, 1, BLOCK, HKV * D), _rand(
        rng, layers, b * mb + 1, 1, BLOCK, HKV * D)
    tables = jnp.asarray(1 + rng.permutation(b * mb).reshape(b, mb), jnp.int32)
    q = _rand(rng, b, HKV, G, D)
    own = np.maximum(seen - 1, 0) // BLOCK
    sel = np.stack([_selection(rng, own[i:i + 1], mb, extra=topk - 2)[:, 0] for i in range(b)])
    assert sel.sum(-1).max() <= topk and sel[1].sum(-1).min() >= 3
    sel = jnp.asarray(sel)
    assert bsa.decode_pages(topk, BLOCK) == 4
    got, pages = jax.jit(lambda *a: sl.attend_step(*a, topk), static_argnums=(3,))(
        q, pk, pv, 1, tables, sel, jnp.asarray(seen, jnp.int32))
    counts = np.where(seen[:, None] > 0, np.asarray(sel).sum(-1), 0)
    assert int(pages) == (-(-counts // 4) * 4).sum()
    through = lambda pool: np.asarray(pool)[1, :, 0][np.asarray(tables)].reshape(
        b, mb * BLOCK, HKV, D)
    for i in range(b):
        want = sl.attend_xla(q[i][None], jnp.asarray(through(pk)[i]), jnp.asarray(through(pv)[i]),
                             sel[i][:, None], jnp.asarray([seen[i] - 1]), BLOCK)[0]
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want), atol=TOL)
    assert not np.asarray(got[3]).any()


def _brute_force(score, own, topk, init, window):
    """The selection a row at a time, by a stable sort."""
    out = np.zeros(score.shape, bool)
    for idx in np.ndindex(score.shape[:-1]):
        b = own[idx[-1]]
        key = []
        for j in range(score.shape[-1]):
            forced = j <= b and (j < init or j > b - window)
            key.append((0 if forced else 1, -score[idx][j], j) if j <= b else None)
        best = sorted(k for k in key if k is not None)[:topk]
        out[idx][[k[2] for k in best]] = True
    return out


@pytest.mark.parametrize("quantum", [None, 4], ids=["distinct_scores", "ties"])
def test_selection_matches_a_brute_force_form(quantum):
    rng = np.random.default_rng(11)
    nb, topk, init, window = 20, 6, 1, 3
    own = np.asarray([0, 2, 4, 5, 9, 19, 19])  # fewer visible than topk: every one taken
    score = rng.random((HKV, len(own), nb)).astype(np.float32)
    if quantum:
        score = np.round(score * quantum) / quantum  # many equal scores: the lower index wins
    sel, forced = sl.select_blocks(jnp.asarray(score), jnp.asarray(own), topk, init, window)
    sel, forced = np.asarray(sel), np.asarray(forced)
    np.testing.assert_array_equal(sel, _brute_force(score, own, topk, init, window))
    assert (sel.sum(-1) == np.minimum(own + 1, topk)[None]).all()
    assert (sel | ~forced[None]).all()  # every forced block is taken
    assert forced[5].tolist() == [j == 0 or j >= 17 for j in range(nb)]
    blocks, counts = sl.selected_lists(jnp.asarray(sel), topk)
    for idx in np.ndindex(sel.shape[:-1]):
        assert np.asarray(blocks)[idx][: np.asarray(counts)[idx]].tolist() == np.flatnonzero(
            sel[idx]).tolist()


def test_block_scores_and_pooled_keys_follow_their_spans():
    """A block's score is the largest ``r`` over the pooled keys whose span
    meets it; a chunk's pooled keys are the means of their spans whatever
    chunk completes them, a decode step's the same from the pool."""
    kernel, stride, block = 4, 2, 8
    rng = np.random.default_rng(5)
    r = rng.random((3, 21)).astype(np.float32)
    nb = 6
    got = np.asarray(sl.block_scores(jnp.asarray(r), nb, kernel, stride, block))
    for b in range(nb):
        meets = [j for j in range(r.shape[1])
                 if stride * j <= block * b + block - 1 and stride * j + kernel - 1 >= block * b]
        want = r[:, meets].max(axis=1) if meets else np.full(3, -1.0)
        np.testing.assert_array_equal(got[:, b], want)
    P, W, C = 27, 8, 8
    k = rng.normal(size=(P, W)).astype(np.float32)
    pooled = np.zeros((P // stride, W), np.float32)
    for off in range(0, P, C):
        j, keys = sl.pool_chunk(jnp.asarray(k), jnp.int32(off), C, kernel, stride)
        j, keys = np.asarray(j), np.asarray(keys)
        real = j < len(pooled)
        pooled[j[real]] = keys[real]
    n = (P - kernel) // stride + 1
    want = np.stack([k[stride * j:stride * j + kernel].mean(0) for j in range(n)])
    np.testing.assert_allclose(pooled[:n], want, atol=1e-6)
    assert not pooled[n:].any()
    bs = 8
    pool = np.zeros((5, 1, bs, W), np.float32)
    tables = np.asarray([[3, 1, 4, 2], [0, 0, 0, 0]], np.int32)
    for p in range(P):
        pool[tables[0, p // bs], 0, p % bs] = k[p]
    for p, done in ((9, True), (10, False), (2, False), (3, True), (26, False)):
        j, keys = sl.pool_step(jnp.asarray(pool), jnp.asarray(tables), jnp.asarray([p, p]),
                               jnp.asarray([True, False]), kernel, stride)
        assert (int(j[0]) == (p - kernel + 1) // stride) == done and int(j[1]) > 10**6
        if done:
            np.testing.assert_allclose(np.asarray(keys[0]), want[int(j[0])], atol=1e-6)


def test_selection_scores_kernel_matches_the_plain_form(monkeypatch):
    """``bsa_select``: a chunk at 96 over pooled keys of which its first
    row sees 47 and its last 63; a padded tail of keys nobody sees yet."""
    monkeypatch.setattr(bsa, "QUERY_TILE", 16)
    kernel, stride, off, C, n_keys = 4, 2, 96, 32, 70
    rng = np.random.default_rng(9)
    q, pooled = _rand(rng, C, HKV, G, D), _rand(rng, n_keys, HKV, D)
    pos = jnp.asarray(off + np.arange(C))
    want = sl.group_scores(q, pooled, pos, kernel, stride)
    got = jax.jit(lambda q, c: sl.group_scores(q, c, pos, kernel, stride, off=jnp.int32(off)))(
        q, pooled)
    assert got.shape == (HKV, C, n_keys)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
    seen = (stride * np.arange(n_keys) + kernel - 1)[None, :] <= np.asarray(pos)[:, None]
    assert not np.asarray(got)[:, ~seen].any() and seen.sum(1).tolist()[::31] == [47, 63]
    np.testing.assert_allclose(np.asarray(got).sum(-1), G, atol=1e-4)  # G softmaxes a row
