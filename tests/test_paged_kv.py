"""Paged-KV tests: block allocator/ledger lifecycle (share -> CoW ->
evict with no double-free), paged-vs-contiguous decode parity, and the
serving-level acceptance for prefix reuse and chunked prefill.

Host tier for the pure bookkeeping (``BlockAllocator``, ``PrefixIndex``,
``KVLedger``, scheduler admission); world=1 xla-backend serving (same
harness as ``tests/test_serving.py``) for the end-to-end bars:

* the server must produce byte-identical tokens to one-shot
  ``Engine.serve`` — including when requests share a >=block_size prompt
  prefix (borrowed donor blocks) and when ``TDT_PREFILL_CHUNK`` splits
  prefills into several chunks (token-identical: multi-chunk GEMM
  accumulation is not bitwise on logits, argmax is stable).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models.kv_cache import NULL_BLOCK, BlockAllocator
from triton_dist_tpu.runtime import resilience, telemetry
from triton_dist_tpu.serving import InferenceServer, RequestState, Scheduler
from triton_dist_tpu.serving.scheduler import KVLedger, Request

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    resilience.reset_degradation()
    yield
    telemetry.reset()
    resilience.reset_degradation()


@pytest.fixture(scope="module")
def model1():
    from triton_dist_tpu.models import PRESETS, DenseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False
    )
    return DenseLLM(PRESETS["test-dense"], ctx, key=jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def engine(model1):
    from triton_dist_tpu.models import Engine

    return Engine(model1, backend="xla", max_len=MAX_LEN)


# ========================================================= allocator/ledger


def test_block_allocator_guards():
    a = BlockAllocator(4)                    # blocks 1..3; 0 is NULL
    blocks = a.alloc(3)
    assert sorted(blocks) == [1, 2, 3]
    assert a.alloc(1) is None                # all-or-nothing when dry
    assert a.alloc(0) == []
    with pytest.raises(ValueError):
        a.incref([NULL_BLOCK])               # null is never allocated
    a.incref([blocks[0]])
    a.free(blocks)
    assert a.num_free == 2                   # blocks[0] still referenced
    a.free([blocks[0]])
    assert a.num_free == 3 and a.num_used == 0
    with pytest.raises(ValueError):
        a.free([blocks[1]])                  # double free is loud
    a.free([NULL_BLOCK])                     # freeing null is a no-op


def test_ledger_share_cow_release_evict_no_double_free():
    """The full chain lifecycle: reserve -> register -> shared reserve ->
    CoW divergence -> release (idempotent) -> index eviction, with the
    refcounts balancing to an empty pool and no block freed twice."""
    led = KVLedger(9, 4)                     # 8 usable blocks of 4 rows
    r1 = Request(req_id=1, prompt=list(range(10)), max_new=2)  # 3 blocks
    assert led.reserve(r1)
    assert len(r1.kv_blocks) == 3 and r1.kv_shared == 0
    assert led.stats()["blocks_used"] == 3
    assert led.register_prefix(r1) == 2      # 10 // 4 full prompt blocks

    # Identical prompt: borrows the indexed chain, capped at (10-1)//4 = 2
    # so prefill still computes the last prompt row.
    r2 = Request(req_id=2, prompt=list(range(10)), max_new=2)
    assert led.reserve(r2)
    assert r2.kv_shared == 2
    assert r2.kv_blocks[:2] == r1.kv_blocks[:2]
    assert r2.kv_blocks[2] != r1.kv_blocks[2]    # fresh tail, not shared
    assert telemetry.counter_value("tdt_kv_prefix_hits_total") == 1.0
    assert telemetry.counter_value("tdt_kv_prefix_blocks_reused_total") == 2.0
    assert led.stats()["blocks_shared"] == 2

    # CoW on a shared position diverges the chain in place; an exclusive
    # position is untouched.
    shared_blk = r2.kv_blocks[0]
    blk, copied = led.make_writable(r2, 0)
    assert copied and blk != shared_blk and r2.kv_blocks[0] == blk
    assert telemetry.counter_value("tdt_kv_cow_copies_total") == 1.0
    assert led.make_writable(r2, 2) == (r2.kv_blocks[2], False)

    # Releases drop exactly one ref per chain position; the second release
    # is a no-op, and the indexed blocks survive under the index's refs.
    led.release(r1)
    led.release(r1)
    led.release(r2)
    st = led.stats()
    assert st["blocks_used"] == st["blocks_indexed"] == 2
    # Evicting the whole index drains the pool back to empty.
    assert led.prefix.evict(st["blocks_total"]) == 2
    assert led.stats()["blocks_used"] == 0
    with pytest.raises(ValueError):
        led.allocator.free([2])              # everything is already free


def test_ledger_eviction_makes_room():
    led = KVLedger(5, 4)                     # 4 usable blocks
    r1 = Request(req_id=1, prompt=list(range(8)), max_new=4)   # 3 blocks
    assert led.reserve(r1)
    led.register_prefix(r1)
    led.release(r1)
    assert led.stats()["blocks_used"] == 2   # only the index holds blocks
    # A disjoint prompt needing 3 blocks: 2 free < 3, so the LRU index
    # leaves are evicted until the fresh tail fits.
    r2 = Request(req_id=2, prompt=list(range(100, 108)), max_new=4)
    assert led.reserve(r2)
    assert r2.kv_shared == 0 and len(r2.kv_blocks) == 3
    assert telemetry.counter_value("tdt_kv_evictions_total") >= 1.0


def test_eviction_walks_once_a_chain_and_keeps_the_lru_order():
    """Evicting a long prompt's chain takes its blocks in one walk of the
    trie (leaf, then each ancestor the removal leaves bare under the same
    stamp), and drops exactly the entries that one walk a block dropped."""
    import random

    from triton_dist_tpu.serving.scheduler import PrefixIndex

    def build():
        alloc = BlockAllocator(400)
        index = PrefixIndex(alloc, 4)
        rng = random.Random(1)
        base = [rng.randrange(50) for _ in range(40)]
        for i in range(12):  # chains that share prefixes of ``base``
            prompt = base[: rng.randrange(0, 40, 4)] + [
                rng.randrange(50) for _ in range(rng.randrange(8, 60))]
            blocks = alloc.alloc(len(prompt) // 4)
            index.register(prompt, blocks)
            alloc.free(blocks)
            if i % 3 == 0:
                index.lookup(base[: rng.randrange(4, 40)])  # restamp a prefix
        return alloc, index

    def indexed(index):
        out, stack = [], list(index._roots.values())
        while stack:
            node = stack.pop()
            out.append(node.block)
            stack += node.children.values()
        return sorted(out)

    a1, one_a_block = build()
    a2, one_a_chain = build()
    need = a1.num_free + 57
    walks = 0
    while a1.num_free < need and one_a_block._drop_leaf(None, "pressure"):
        walks += 1
    calls = []
    real = one_a_chain._lru_leaf
    one_a_chain._lru_leaf = lambda *a, **k: calls.append(1) or real(*a, **k)
    assert one_a_chain.evict(need) == walks == 57
    assert indexed(one_a_block) == indexed(one_a_chain) and a1.num_free == a2.num_free
    assert len(calls) < walks / 2


def test_scheduler_kv_budget_hard_and_kv_wait():
    led = KVLedger(5, 4)                     # 4 usable blocks = 16 rows
    sched = Scheduler(num_slots=2, max_len=MAX_LEN, kv_ledger=led)
    # A chain the EMPTY pool can't hold rejects at submit: 5 blocks > 4.
    r = sched.submit([1] * 18, max_new=2)
    assert r.state is RequestState.REJECTED
    assert r.reject_reason == "kv_budget_hard"
    # max_len overflow also hard-rejects in ledger mode.
    assert sched.submit([1] * 30, max_new=4).reject_reason == "kv_budget_hard"

    a = sched.submit([1] * 10, max_new=2, now_s=0.0)   # 3 blocks
    b = sched.submit([2] * 10, max_new=2, now_s=0.0,   # 3 blocks: the pool
                     ttft_deadline_s=10.0)             # can't hold both
    (s,) = sched.join_free_slots(now_s=0.0)
    assert s.request is a and a.kv_blocks
    # b fits the pool but not the free set: parked, not rejected.
    assert b.state is RequestState.QUEUED and b.kv_wait
    assert telemetry.counter_value("tdt_serving_kv_budget_wait_total") == 1.0
    # Parked requests are exempt from queue-time deadline expiry (the same
    # wait WOULD expire an unparked request)...
    assert not sched._queue_expired(b, now_s=1e9)
    b.kv_wait = False
    assert sched._queue_expired(b, now_s=1e9)
    b.kv_wait = True
    # ... and the park is counted once per episode, not once per sweep.
    assert sched.join_free_slots(now_s=0.0) == []
    assert telemetry.counter_value("tdt_serving_kv_budget_wait_total") == 1.0
    # A finishing tenant frees its chain; the parked request then admits.
    sched.start_decode(s)
    sched.finish(s)
    led.release(a)
    sched.release(s)
    (s2,) = sched.join_free_slots(now_s=0.0)
    assert s2.request is b and not b.kv_wait and b.kv_blocks


# =============================================== paged decode (kernel tier)


@pytest.mark.parametrize("block_k", [16, 32, 64, 128])
def test_paged_decode_matches_contiguous(block_k):
    """The several-pages walk is bitwise-identical to the contiguous kernel
    at the same ``block_k``: scatter a contiguous cache into one layer of a
    STACKED pool whose physical pages are out of order, decode through the
    table walk (pallas, the pages of a tile DMA'd by the table) and the
    gather oracle, and compare outputs and log-sum-exps against
    ``flash_decode`` — lengths 0, 1, on and round a page boundary, round a
    tile boundary, and full."""
    from triton_dist_tpu.kernels.flash_decode import (
        flash_decode,
        paged_flash_decode,
    )

    bs, mb, hkv, hq, d, layers, layer = 16, 8, 2, 4, 64, 3, 1
    s = mb * bs
    lens = [1, 15, 16, 17, 64, 65, s, 0]
    b = len(lens)
    rng = np.random.RandomState(0)
    kc = rng.randn(b, hkv, s, d).astype(np.float32)
    vc = rng.randn(b, hkv, s, d).astype(np.float32)
    q = rng.randn(b, hq, d).astype(np.float32)
    lengths = np.asarray(lens, np.int32)

    # Shuffled physical placement: a distinct pool block per (seq, logical)
    # position, with the chain truncated at the null block past lengths.
    # The other layers of the stack hold noise the walk must never read.
    nb = 1 + b * mb
    tables = rng.permutation(np.arange(1, nb)).reshape(b, mb).astype(np.int32)
    k_pool = rng.randn(layers, nb, hkv, bs, d).astype(np.float32)
    v_pool = rng.randn(layers, nb, hkv, bs, d).astype(np.float32)
    k_pool[:, NULL_BLOCK] = v_pool[:, NULL_BLOCK] = 0.0
    for i in range(b):
        used = -(-lens[i] // bs)
        for j in range(mb):
            if j >= used:
                tables[i, j] = NULL_BLOCK
                continue
            k_pool[layer, tables[i, j]] = kc[i][:, j * bs:(j + 1) * bs]
            v_pool[layer, tables[i, j]] = vc[i][:, j * bs:(j + 1) * bs]
        # Rows past lengths live in the null block on the paged side: zero
        # the contiguous reference's tail so both kernels mask the same bytes.
        kc[i][:, used * bs:] = 0.0
        vc[i][:, used * bs:] = 0.0

    ref = flash_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(lengths), block_k=block_k, return_lse=True,
    )
    for impl in ("gather", "pallas"):
        got = paged_flash_decode(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(lengths), layer=jnp.int32(layer),
            block_k=block_k, impl=impl, return_lse=True,
        )
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r), impl)


# ============================ a decode chunk against the pool where it lies


def _bounce_chunk(eng, paged, tokens, remaining, chunk, key):
    """What ``decode_steps_paged`` was before the pool was decoded in
    place, kept as the oracle: gather the pool into the contiguous layout,
    run the contiguous chunk program, scatter the written rows back."""
    import dataclasses

    kc, vc = eng._paged_gather(
        paged.k, paged.v, paged.k_scale, paged.v_scale, paged.tables)
    out, tok, k2, v2, lengths, rem = eng._decode_chunk(
        eng.model.params, eng._decode_extra, tokens, kc, vc, paged.lengths,
        remaining, chunk, key)
    pk, pv, ks, vs = eng._paged_scatter_rows(
        paged.k, paged.v, paged.k_scale, paged.v_scale, k2, v2, paged.tables,
        paged.lengths, jnp.clip(remaining, 0, chunk), chunk, paged.quant)
    return out, tok, dataclasses.replace(
        paged, k=pk, v=pv, k_scale=ks, v_scale=vs, lengths=lengths), rem


@pytest.mark.parametrize("quant", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("backend", ["xla", "dist_ar"])
def test_decode_chunk_in_place_matches_the_bounce(backend, quant):
    """A decode chunk through the pool — one K/V row written a layer, K/V
    read through the table — gives the tokens, lengths and live pool rows
    that the gather, the contiguous chunk and the scatter-back gave. Slot 1
    is inactive throughout; slot 3 was freed and its pages handed to slot
    2 while its own table still names them (so only the NULL-block redirect
    keeps it off its new tenant's rows); lengths start on, before and after
    a page boundary, and the second chunk crosses one.

    A bfloat16 pool agrees bit for bit. A quantized pool agrees in tokens
    and lengths, and in rows to a quantization step: the bounce attended a
    chunk's own new rows unquantized and quantized them at the scatter,
    while in place a row is quantized once, at the append, as ``mega``
    does it."""
    import dataclasses

    from triton_dist_tpu.models import PRESETS, DenseLLM, Engine
    from triton_dist_tpu.models.quant import dequantize_kv
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import cpu_mesh

    m = cpu_mesh((1,), ("tp",))
    ctx = initialize_distributed(
        devices=list(m.devices.flat), axis_names=("tp",), set_default=False)
    cfg = dataclasses.replace(PRESETS["test-dense"], dtype="bfloat16")
    eng = Engine(DenseLLM(cfg, ctx, key=jax.random.PRNGKey(1)),
                 backend=backend, max_len=MAX_LEN)
    bs, chunk = 8, 3
    paged = eng.alloc_paged(4, block_size=bs, num_blocks=17, quant=quant)
    lens = [8, 5, 7, 9]  # on a page boundary, inactive, before one, after one
    tables = np.zeros((4, MAX_LEN // bs), np.int32)
    tables[0, :3] = [9, 2, 12]  # physical pages out of order
    tables[1, :2] = [5, 6]
    tables[2, :3] = [7, 3, 11]
    tables[3, :2] = [7, 3]  # freed: its pages are slot 2's now
    prompts = np.random.RandomState(3).randint(1, cfg.vocab_size, (4, 12))
    toks = []
    for slot, n in enumerate(lens):
        kbuf, vbuf = eng.paged_kbuf_zeros(n)
        logits, kbuf, vbuf = eng.prefill_chunk(
            kbuf, vbuf, jnp.asarray(prompts[slot:slot + 1, :n], jnp.int32), 0, n - 1)
        toks.append(int(jnp.argmax(logits[0])))
        if slot != 3:  # the freed slot's rows are long gone
            paged = eng.complete_paged_prefill(paged, kbuf, vbuf, tables[slot], 0)
    paged = dataclasses.replace(
        paged, tables=jnp.asarray(tables), lengths=jnp.asarray(lens, jnp.int32))
    tokens = jnp.asarray(toks, jnp.int32)
    remaining = jnp.asarray([5, 0, 6, 0], jnp.int32)
    key = jax.random.PRNGKey(0)
    assert eng._decode_shard_paged is not None

    copy = lambda c: jax.tree.map(jnp.copy, c)
    ref_p, got_p = copy(paged), copy(paged)
    ref_t = got_t = tokens
    ref_r = got_r = remaining
    for _ in range(2):
        ref_out, ref_t, ref_p, ref_r = _bounce_chunk(
            eng, ref_p, ref_t, ref_r, chunk, key)
        got_out, got_t, got_p, got_r = eng.decode_steps_paged(
            got_p, got_t, got_r, chunk, key)
        np.testing.assert_array_equal(np.asarray(got_out), np.asarray(ref_out))
        np.testing.assert_array_equal(np.asarray(got_t), np.asarray(ref_t))
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(ref_r))
        np.testing.assert_array_equal(
            np.asarray(got_p.lengths), np.asarray(ref_p.lengths))
    assert list(np.asarray(got_p.lengths)) == [13, 5, 13, 9]
    assert telemetry.counter_value(
        "tdt_engine_decode_chunks_total", path="pool") == 2.0
    assert telemetry.counter_value(
        "tdt_engine_decode_chunks_total", path="bounce") == 0.0

    def rows(c, pool, scale):
        x = pool if scale is None else dequantize_kv(pool, scale)
        return np.asarray(x.astype(jnp.float32))[:, 1:]  # NULL block: junk

    for name in ("k", "v"):
        got = rows(got_p, getattr(got_p, name), getattr(got_p, name + "_scale"))
        ref = rows(ref_p, getattr(ref_p, name), getattr(ref_p, name + "_scale"))
        if quant is None:
            np.testing.assert_array_equal(got, ref)
        else:
            scale = np.asarray(getattr(ref_p, name + "_scale"))[:, 1:]
            assert (np.abs(got - ref) <= 2 * scale + 1e-6).all()
    # The freed slot's stale table named pages 7 and 3; slot 2 wrote there.
    assert np.asarray(got_p.k.astype(jnp.float32))[:, 3].any()


# ======================================== acceptance: server over paged KV

REQUESTS = [
    ([3, 17, 42, 7, 99], 6),
    ([8, 1, 13], 4),
    ([5, 5, 5, 5, 5, 5, 5, 5], 3),
    ([100, 200, 30], 5),
    ([7, 7, 7, 7], 1),
    ([91, 12, 55, 2, 8, 41], 4),
    ([3, 3], 6),
    ([111, 4, 9, 16, 25, 36, 49], 3),
]

#: 16-token shared head == one full default-size KV block, so every
#: request after the donor borrows its first block from the prefix index.
PREFIX = [(3 * j + 5) % 256 for j in range(16)]
SHARED_REQUESTS = [(PREFIX + [10 + i], 4) for i in range(4)] + [
    (PREFIX + [50 + i, 60 + i], 3) for i in range(2)
]


def _references(eng, requests):
    return [
        list(np.asarray(eng.serve(jnp.asarray([p], jnp.int32), gen_len=g))[0])
        for p, g in requests
    ]


def test_server_prefix_reuse_hits_and_parity(engine):
    """Requests sharing a full-block prompt prefix borrow the donor's
    block and still match one-shot serve token for token; after the drain
    only the prefix index holds pool blocks."""
    refs = _references(engine, SHARED_REQUESTS)
    srv = InferenceServer(engine, num_slots=1, chunk=2)  # serialize joins
    assert srv.cache.block_size == srv.block_size == 16
    handles = [srv.submit(p, g) for p, g in SHARED_REQUESTS]
    srv.run()
    for h, ref in zip(handles, refs):
        assert h.done
        assert list(h.tokens) == ref
    # Every request after the donor hit the index.
    assert telemetry.counter_value("tdt_kv_prefix_hits_total") >= float(
        len(SHARED_REQUESTS) - 1
    )
    assert telemetry.counter_value("tdt_kv_prefix_blocks_reused_total") > 0
    st = srv.kv_ledger.stats()
    assert st["blocks_used"] == st["blocks_indexed"] >= 1
    # The pool gauges track the ledger.
    snap = telemetry.snapshot()["gauges"]
    (free_gauge,) = snap["tdt_kv_blocks_free"]
    assert free_gauge["value"] == float(st["blocks_free"])


def test_chunked_prefill_staggered_parity(engine, monkeypatch):
    """A small TDT_PREFILL_CHUNK splits every prefill into several chunks
    interleaved with decode; the streams stay token-identical to one-shot
    serve across 8 staggered requests."""
    monkeypatch.setenv("TDT_PREFILL_CHUNK", "3")
    refs = _references(engine, REQUESTS)
    srv = InferenceServer(engine, num_slots=3, chunk=2)
    assert srv.prefill_chunk == 3
    handles = [srv.submit(p, g) for p, g in REQUESTS[:4]]
    srv.step()
    handles += [srv.submit(p, g) for p, g in REQUESTS[4:]]
    srv.run()
    for h, ref in zip(handles, refs):
        assert h.done
        assert list(h.tokens) == ref
    # Every prefill recorded its chunk count; the per-prompt counts are
    # ceil(len/3), summing to 15 over the 8 prompts — strictly more than
    # one chunk per prefill, so the chunked path genuinely ran.
    (entry,) = telemetry.snapshot()["histograms"]["tdt_serving_prefill_chunks"]
    assert entry["count"] == len(REQUESTS)
    assert entry["sum"] == float(sum(-(-len(p) // 3) for p, _ in REQUESTS))
