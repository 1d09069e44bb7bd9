"""``kernels/shared_kv_decode.py``: the hybrid model's shared K/V read where
it lies in the block pool, against the form it replaces on the decode path
(the pool gathered through the table at its whole extent, then
``layers/hybrid_ssm.py:diff_attend_rows``), interpreted on the CPU at rows
of 128 lanes; then served, where the block size alone decides which of the
two a decode step runs.

The tile is the module's ``TILE_BYTES`` (the kernel takes no tile argument);
the tests set it with ``monkeypatch`` so that a table of a few pages crosses
tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import shared_kv_decode as skd
from triton_dist_tpu.layers import hybrid_ssm as hs
from triton_dist_tpu.models import Engine, HybridSSMConfig, HybridSSMLLM
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import initialize_distributed
from triton_dist_tpu.serving import InferenceServer

HQ, HKV, DH, BS, MB = 8, 4, 32, 8, 6  # a row of 4 x 32 = 128 lanes, a table of 48 positions
PAGES = 2  # a tile of 16 positions
LAYER, EPS = 5, 1e-5
#: slot by slot; 0 is a slot nobody reads for (a table row of NULL blocks)
LENGTHS = {"inactive_first": 0, "one": 1, "ends_a_tile": 32, "inactive": 0,
           "inside_a_page": 21, "full_extent": MB * BS, "inactive_last": 0}


def _tile_bytes(pages: int, block_size: int, width: int) -> int:
    return pages * 2 * block_size * width * 4


@pytest.fixture(scope="module")
def both_forms():
    """(the kernel's rows, the gathered form's rows), a slot a case."""
    rng = np.random.default_rng(0)
    b, w = len(LENGTHS), HKV * DH
    lengths = jnp.asarray(list(LENGTHS.values()), jnp.int32)
    pk, pv = (jnp.asarray(rng.normal(size=(1, b * MB + 1, 1, BS, w)), jnp.float32)
              for _ in range(2))
    tables = 1 + rng.permutation(b * MB).reshape(b, MB)  # scrambled: no slot's blocks in a row
    tables = jnp.asarray(np.where(np.asarray(lengths)[:, None] > 0, tables, 0), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, HQ, DH)), jnp.float32)
    lam, subln = jnp.float32(0.35), jnp.asarray(rng.normal(size=(2 * DH,)), jnp.float32)
    assert skd.takes(HQ, pk.shape, MB, 4)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(skd, "TILE_BYTES", _tile_bytes(PAGES, BS, w))
        assert skd.tile_pages(pk.shape, MB, 4) == PAGES
        got = jax.jit(hs.diff_attend_pool, static_argnums=(6,))(
            q, pk, pv, tables, lengths, lam, LAYER, subln, EPS)
    through = lambda pool: jnp.take(pool[0, :, 0], tables, axis=0).reshape(b, MB * BS, w)
    mask = jnp.arange(MB * BS)[None] < jnp.maximum(lengths, 1)[:, None]
    want = jax.jit(hs.diff_attend_rows, static_argnums=(5,))(
        q, through(pk), through(pv), mask, lam, LAYER, subln, EPS)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("case", list(LENGTHS))
def test_a_slot_reads_its_live_tiles_through_the_table(both_forms, case):
    """Float32 on both sides: the two differ in the order of their sums (a
    tile at a time under a running max; the pair's difference taken after
    the value product and not before it)."""
    got, want = both_forms
    slot = list(LENGTHS).index(case)
    if LENGTHS[case] == 0:
        assert not got[slot].any()  # nothing fetched, nothing computed: zeros, and finite
    else:
        np.testing.assert_allclose(got[slot], want[slot], atol=2e-5)


def test_the_shape_rule():
    """Whole lanes, whole sublane tiles of the pool's type, one "head" of
    whole rows (of any layer of the pool: the layer is the kernel's static
    argument), the buffers inside the VMEM rule; the tile is the most pages
    that divide the table under ``TILE_BYTES``."""
    pool = lambda bs, w: (1, 33, 1, bs, w)
    assert skd.takes(40, (1, 5377, 1, 16, 1280), 168, 2)  # phi-4-mini-flash, bfloat16
    assert skd.tile_pages((1, 5377, 1, 16, 1280), 168, 2) == 14
    assert skd.takes(8, pool(8, 128), 8, 4) and skd.takes(8, pool(16, 128), 8, 2)
    assert not skd.takes(8, pool(8, 32), 8, 4)  # the CPU tests' default: 32 lanes
    assert not skd.takes(8, pool(4, 128), 8, 4)  # half a float32 sublane tile
    assert not skd.takes(8, pool(8, 128), 8, 2)  # half a bfloat16 one
    assert skd.takes(32, (4, 17665, 1, 16, 512), 552, 2)  # granite-4.0-h-micro's four layers
    assert not skd.takes(8, (1, 33, 4, 8, 128), 8, 4)  # heads apart: the dense models'
    assert not skd.takes(4096, (1, 33, 1, 16, 1280), 7, 2)  # 7 pages are 0.3 MB; 4096 query rows are not


# ------------------------------------------------------------------ served

WIDE = HybridSSMConfig(hidden_size=256, num_q_heads=8, num_kv_heads=4)  # a K/V row of 128
SIZES = [(30, 6), (17, 11), (5, 9)]


@pytest.fixture(scope="module")
def wide_engine():
    ctx = initialize_distributed(devices=jax.devices()[:1], axis_names=("tp",), set_default=False)
    return Engine(HybridSSMLLM(WIDE, ctx, key=jax.random.PRNGKey(3)), backend="dist", max_len=64)


def _serve(eng):
    """Three requests on two slots (the third joins the slot the first
    left). Returns (tokens a request, the logits before each decode chunk
    by (request, position))."""
    srv = InferenceServer(eng, num_slots=2, chunk=4, prefill_chunk=12)
    rng = np.random.default_rng(5)
    reqs = [srv.submit(rng.integers(0, WIDE.vocab_size, size=n).tolist(), new)
            for n, new in SIZES]
    seen = {}
    for _ in range(80):
        srv.step()
        srv._land_in_flight()  # the cache and the last tokens of the same chunk
        decoding = srv.scheduler.decoding_slots()
        if decoding:
            logits = np.asarray(eng.decode_logits_paged(srv.cache, jnp.asarray(srv._last)))
            for slot in decoding:
                r = slot.request
                seen[reqs.index(r), len(r.prompt) + len(r.tokens) - 1] = logits[slot.idx]
        if all(r.finish_reason is not None for r in reqs):
            break
    srv.shutdown(drain=False)
    return [list(r.tokens) for r in reqs], seen


def test_served_in_place_as_through_the_gather_and_counted(wide_engine, monkeypatch):
    """One engine, two servers: blocks of 16 float32 rows are whole sublane
    tiles, so the decode chunk reads the pool in place, a tile of one page;
    blocks of 4 are not, so it gathers. The same tokens, the logits before
    every decode chunk within the float32 tolerance, and on each path the
    positions fetched and visible are the host's arithmetic from the
    lengths: whole tiles up to the length in place, the table's extent
    gathered, over the two reading layers and the rows somebody sent."""
    monkeypatch.setattr(skd, "TILE_BYTES", _tile_bytes(1, 16, 128))
    spans = np.concatenate([np.arange(p + 1, p + new) for p, new in SIZES])  # a step's pos + 1
    readers = 1 + len(WIDE.layers_of("cross"))
    served = {}
    for block_size, fetched in ((16, -(-spans // 16) * 16), (4, np.full_like(spans, 64))):
        monkeypatch.setenv("TDT_KV_BLOCK_SIZE", str(block_size))
        telemetry.reset()
        served[block_size] = _serve(wide_engine)
        value = lambda name: telemetry.counter_value(name, phase="decode")
        assert value("tdt_shared_kv_positions_visible_total") == readers * spans.sum()
        assert value("tdt_shared_kv_positions_read_total") == readers * fetched.sum()
        assert telemetry.counter_value("tdt_shared_kv_positions_read_total", phase="prefill") == 0
    (tokens, logits), (tokens_gathered, logits_gathered) = served[16], served[4]
    assert tokens == tokens_gathered and [len(t) for t in tokens] == [new for _, new in SIZES]
    assert logits.keys() == logits_gathered.keys() and len(logits) >= 4
    assert max(float(np.abs(logits[k] - logits_gathered[k]).max()) for k in logits) <= 2e-4
