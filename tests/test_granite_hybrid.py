"""Granite-4.0-H's decoder (``SparseLinearLLM`` with ``"mamba2"`` and
``"full"`` mixers) and its two state-space kernels on the CPU, float32:

* ``ssd_chunk`` (interpreted) and ``ssd_chunk_xla`` against ``ssd_scan``, the
  recurrence a row at a time, and ``ssd_step`` against one step of it;
* the served toy model (``tests/benchmark/toy/configs/toy-granite.json``: 4
  layers, mamba / attention / mamba / mamba) against the benchmark's plain
  reference (``benchmark/reference/granite_hybrid.py``: the full forward
  pass, no kernels, cache or batching), prefill in chunks then decode
  through the cache; the counters the decode kernel feeds;
* ``minicpm-sala``'s toy reading as before under the new muP fields.

Tolerance: program and reference both compute in float32 here and differ in
the order of their sums (a sub-chunk's products from a carried state, an
online softmax over tiles), which moves a logit of size ~0.5 by some
1e-7; ``TOL`` leaves two orders of room. The planted
faults show what it holds: each misses it by over ten times.
"""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import paged_drive  # noqa: E402
import test_hybrid_ssm as hybrid_drive  # noqa: E402  (its join and its decode step, by hand)
from benchmark import harness  # noqa: E402
from triton_dist_tpu.kernels import lightning_attn as la  # noqa: E402
from triton_dist_tpu.layers import sparse_linear as sl  # noqa: E402
from triton_dist_tpu.models import Engine, SparseLinearLLM  # noqa: E402
from triton_dist_tpu.runtime import telemetry  # noqa: E402
from triton_dist_tpu.runtime.mesh import initialize_distributed  # noqa: E402
from triton_dist_tpu.serving import InferenceServer  # noqa: E402

TOL = 2e-5
KERNEL_TOL = 2e-4
CFG = json.loads((REPO / "tests/benchmark/toy/configs/toy-granite.json").read_text())
REF = harness._module(REPO / "benchmark/reference/granite_hybrid.py")
BUILD = harness._module(REPO / "benchmark/build/granite_hybrid.py")
KEY = harness.seed_key(2**31 + 42)
BLOCK, CHUNK, MAX_LEN = 8, 32, 256


# ------------------------------------------------------------------ kernels


def _inputs(T, H, P, N, seed=0, dt_scale=None):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=(T, H)))
    if dt_scale is not None:
        dt = np.full((T, H), dt_scale)
    A = -jnp.asarray(r.uniform(1, 16, size=H), jnp.float32)
    return f(T, H, P), jnp.asarray(dt, jnp.float32), A, f(T, N), f(T, N), f(H), f(N, H * P)


#: (rows, heads, head, state, real rows, sub-chunk, step): rows past
#: ``n_real`` (a prompt's padded last chunk); a chunk of 2.5 sub-chunks; a
#: carried state with every row real; a step of 2 a row, so that ``a`` falls
#: by up to 512 over a sub-chunk and ``exp(a_t)`` alone would underflow
#: (``exp(-a_j)`` overflow).
CASES = {"rows_past_n_real": (40, 4, 32, 16, 29, 16, None),
         "ragged_sub_chunks": (40, 4, 32, 16, 40, 16, None),
         "carried_state_whole": (24, 8, 16, 8, 24, 256, None),
         "strong_decay": (48, 4, 32, 16, 48, 16, 2.0)}


@pytest.mark.parametrize("case", list(CASES))
def test_ssd_chunk_and_its_xla_form_are_the_recurrence(case):
    T, H, P, N, n, sub, step = CASES[case]
    args = _inputs(T, H, P, N, seed=T + n, dt_scale=step)
    y0, s0 = la.ssd_scan(*args, n)
    real = np.arange(T) < n
    for fn in (la.ssd_chunk_xla, la.ssd_chunk):
        y, s = fn(*args, jnp.int32(n), sub=sub)
        assert np.isfinite(np.asarray(y)).all()
        assert np.abs(np.asarray(y - y0))[real].max() <= KERNEL_TOL, fn.__name__
        assert np.abs(np.asarray(s - s0)).max() <= KERNEL_TOL, fn.__name__


def test_ssd_step_advances_the_active_slots_and_leaves_the_rest_bit_for_bit():
    b, H, P, N = 5, 4, 32, 16
    r = np.random.default_rng(1)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    x, B, C, D, S = f(b, H, P), f(b, N), f(b, N), f(H), f(b, N, H * P)
    dt = jnp.asarray(r.uniform(1e-3, 1e-1, size=(b, H)), jnp.float32)
    A = -jnp.asarray(r.uniform(1, 16, size=H), jnp.float32)
    active = jnp.asarray([False, True, False, True, True])
    before = np.asarray(S)
    y, S1, moved = la.ssd_step(x, dt, A, B, C, D, S, active)
    assert int(moved) == 3
    for i in range(b):
        if active[i]:
            yr, sr = la.ssd_scan(x[i:i + 1], dt[i:i + 1], A, B[i:i + 1], C[i:i + 1], D,
                                 jnp.asarray(before[i]))
            assert np.abs(np.asarray(y[i] - yr[0])).max() <= KERNEL_TOL
            assert np.abs(np.asarray(S1[i] - sr)).max() <= KERNEL_TOL
        else:
            np.testing.assert_array_equal(np.asarray(S1[i]), before[i])
            assert not np.asarray(y[i]).any()


# ------------------------------------------------------------------ served


@pytest.fixture(scope="module")
def ctx():
    return initialize_distributed(devices=jax.devices()[:1], axis_names=("tp",), set_default=False)


def _model(ctx, cfg=CFG):
    return SparseLinearLLM(BUILD.model_config(cfg), ctx, key=jnp.asarray(KEY))


@pytest.fixture(scope="module")
def engine(ctx):
    return Engine(_model(ctx), backend="dist", max_len=MAX_LEN)


@pytest.fixture(scope="module")
def weights():
    return REF.make_weights(CFG, KEY, jax.devices()[:1])


@pytest.fixture(scope="module")
def ref_logits(weights):
    def run(tokens, w=weights):
        seq = np.zeros((1, 160), np.int32)
        seq[0, : len(tokens)] = tokens  # padding sits in every row's future
        return np.asarray(REF.logits_at(CFG, w, seq, np.arange(len(tokens))[None], block=32))[0]

    return run


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], size=n).tolist()


def _join(eng, paged, slot, ids):
    """(every chunk's logits, paged'): the prompt in chunks of ``CHUNK``."""
    _, every, paged = hybrid_drive._join(eng, paged, slot, ids, CHUNK)
    return every, paged


_decode = hybrid_drive._decode


def test_weights_are_the_references(engine, weights):
    for mine, theirs in zip(weights["layers"], engine.model.params["layers"]):
        for name, w in mine.items():
            np.testing.assert_array_equal(np.asarray(w), np.asarray(theirs[name]))
    np.testing.assert_array_equal(np.asarray(weights["embed"]),
                                  np.asarray(engine.model.params["embed"]))
    assert "head" not in engine.model.params  # tied: the head is the embedding's transpose


def test_chunked_prefill_then_decode_matches_the_forward_pass(engine, ref_logits):
    """A prompt of 110 (three chunks of 32 and one of 14 padded to 32) with
    the conv tails and the state carried from chunk to chunk: every chunk's
    last row is the full forward's. Then 30 decode steps through the pool
    and the state, beside an idle slot."""
    ids = _ids(140, seed=1)
    want = ref_logits(ids)
    every, paged = _join(engine, paged_drive.alloc_chains(engine, 2, BLOCK), 1, ids[:110])
    for i, got in enumerate(every):
        assert np.abs(got - want[min(CHUNK * (i + 1), 110) - 1]).max() <= TOL, i
    for t in range(110, 140):
        logits, paged = _decode(engine, paged, [0, ids[t]], [0, 1])
        assert np.abs(logits[1] - want[t]).max() <= TOL, t
    assert np.asarray(paged.lengths).tolist() == [0, 140]


def test_two_lengths_an_idle_slot_and_a_slot_reused_after_a_longer_tenant(engine, ref_logits):
    """Slots 0 and 2 decode at lengths 40 and 120 in one chunk of 4 steps in
    which slot 2 runs out after 2, while slot 1 (a finished tenant) sits
    idle: its state and length do not move, bit for bit. Then slot 2 is
    given a shorter prompt: nothing of its 122-token tenant (state, conv
    tail, pages) is left in the second's logits."""
    a, b, idle = _ids(50, seed=5), _ids(130, seed=6), _ids(40, seed=7)
    paged = paged_drive.alloc_chains(engine, 3, BLOCK)
    _, paged = _join(engine, paged, 0, a[:40])
    _, paged = _join(engine, paged, 1, idle)
    _, paged = _join(engine, paged, 2, b[:120])
    before = jax.tree.map(lambda x: np.asarray(x[1]), paged.state)
    out, tok, paged, _ = engine.decode_steps_paged(
        paged, jnp.asarray([a[40], 3, b[120]], jnp.int32), jnp.asarray([4, 0, 2], jnp.int32), 4)
    out = np.asarray(out)
    assert out[1].tolist() == [-1] * 4 and out[2, 2:].tolist() == [-1, -1]
    assert np.asarray(paged.lengths).tolist() == [44, 40, 122]
    for x, y in zip(jax.tree.leaves(before),
                    jax.tree.leaves(jax.tree.map(lambda x: np.asarray(x[1]), paged.state))):
        np.testing.assert_array_equal(x, y)
    logits = np.asarray(engine.decode_logits_paged(paged, tok))
    for slot, seq, n in ((0, a[:41], 4), (2, b[:121], 2)):
        seq = seq + out[slot, :n].tolist()
        want = ref_logits(seq)
        for i in range(n):  # each emitted token is the reference's first choice
            row = want[len(seq) - n - 1 + i]
            assert row.max() - row[seq[len(seq) - n + i]] <= TOL
        assert np.abs(logits[slot] - want[-1]).max() <= TOL
    second = _ids(50, seed=8)
    want = ref_logits(second)
    every, paged = _join(engine, paged, 2, second[:40])
    assert np.abs(every[-1] - want[39]).max() <= TOL
    for t in range(40, 50):
        logits, paged = _decode(engine, paged, [0, 0, second[t]], [0, 0, 1])
        assert np.abs(logits[2] - want[t]).max() <= TOL, t


def _no_carry(lp, u, tail, s, n_real, *a):
    return MAMBA2_CHUNK(lp, u, jnp.zeros_like(tail), jnp.zeros_like(s), n_real, *a)


def _dt_zero(x, dt, *a, **k):
    return SSD_CHUNK(x, jnp.zeros_like(dt), *a, **k)


def _no_skip(x, dt, A, B, C, D, S, active):
    return SSD_STEP(x, dt, A, B, C, jnp.zeros_like(D), S, active)


def _no_gate(lp, y, z, eps):
    return MAMBA2_OUT(lp, y, jnp.full_like(z, 30.0), eps)  # silu(30) is 30: a constant gain


MAMBA2_CHUNK, MAMBA2_OUT, SSD_CHUNK, SSD_STEP = (sl.mamba2_chunk, sl._mamba2_out, la.ssd_chunk,
                                                 la.ssd_step)
FAULTS = {"state_not_carried": (sl, "mamba2_chunk", _no_carry),
          "dt_zero": (la, "ssd_chunk", _dt_zero),
          "decode_drops_the_d_skip": (la, "ssd_step", _no_skip),
          "gate_dropped": (sl, "_mamba2_out", _no_gate)}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_tolerance_holds_the_carry_the_decay_the_skip_and_the_gate(
        ctx, ref_logits, monkeypatch, fault):
    """What ``TOL`` is for: with the state and tail not carried from chunk
    to chunk, ``dt`` forced to 0 in the chunk, the ``D`` skip dropped from
    the decode steps or the gate ``silu(z)`` dropped (the norm then sees
    ``y`` alone), the same drive misses the reference by over ten times the
    tolerance."""
    monkeypatch.setattr(*FAULTS[fault])
    eng = Engine(_model(ctx), backend="dist", max_len=MAX_LEN)
    ids = _ids(104, seed=1)
    want = ref_logits(ids)
    every, paged = _join(eng, paged_drive.alloc_chains(eng, 2, BLOCK), 1, ids[:96])
    worst = np.abs(every[-1] - want[95]).max()
    for t in range(96, 104):
        logits, paged = _decode(eng, paged, [0, ids[t]], [0, 1])
        worst = max(worst, np.abs(logits[1] - want[t]).max())
    assert worst > 10 * TOL, worst


def test_served_counters_are_the_decode_kernels_own(engine):
    """Through ``InferenceServer``: the rows the Mamba-2 layers advanced are
    counted once a row, not once a layer, in both phases; in decode the
    slot-layer states the kernel moved equal those that advanced (idle
    slots are skipped), the layers times the rows; nothing falls back."""
    names = [("tdt_ssd_rows_total", {}),
             ("tdt_ssd_state_slots_total", {"kind": "moved"}),
             ("tdt_ssd_state_slots_total", {"kind": "advanced"})]
    read = lambda: {(n, tuple(l.items()), ph): telemetry.counter_value(n, phase=ph, **l)
                    for n, l in names for ph in ("prefill", "decode")}
    srv = InferenceServer(engine, num_slots=3, chunk=4, prefill_chunk=CHUNK, block_size=BLOCK)
    before = read()
    sizes = [(70, 7), (33, 3)]
    reqs = [srv.submit(_ids(p, seed=p), new) for p, new in sizes]
    while not all(r.done for r in reqs):
        srv.step()
    assert all(r.finish_reason == "ok" for r in reqs)
    got = {k: v - before[k] for k, v in read().items()}
    layers = len(engine.model.config.layers_of("mamba2"))
    rows = sum(new - 1 for _, new in sizes)  # the first token is prefill's
    assert got[("tdt_ssd_rows_total", (), "prefill")] == sum(p for p, _ in sizes)
    assert got[("tdt_ssd_rows_total", (), "decode")] == rows
    moved = got[("tdt_ssd_state_slots_total", (("kind", "moved"),), "decode")]
    advanced = got[("tdt_ssd_state_slots_total", (("kind", "advanced"),), "decode")]
    assert moved == advanced == layers * rows
    assert telemetry.counter_total("tdt_engine_fallbacks_total") == 0


def test_the_references_ssd_sum_is_the_row_at_a_time_recurrence(weights):
    """The reference sums the recurrence a sub-chunk at a time; a row at a
    time, in numpy, it is the same to float32's rounding: over 40 rows (two
    sub-chunks of 16 and a ragged third)."""
    s = REF.sizes(CFG)
    H, P, N, T = s["H"], s["P"], s["N"], 40
    r = np.random.default_rng(3)
    x, B, C = r.normal(size=(T, H, P)), r.normal(size=(T, N)), r.normal(size=(T, N))
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=(T, H)))
    A = -r.uniform(1, 16, size=H)
    got = np.asarray(REF._ssd(s, *(jnp.asarray(z, jnp.float32) for z in (x, dt, A, B, C))))
    S, want = np.zeros((H, N, P)), np.zeros((T, H, P))
    for t in range(T):
        S = np.exp(dt[t] * A)[:, None, None] * S + B[t][None, :, None] * (dt[t][:, None] * x[t])[:, None]
        want[t] = np.einsum("n,hnp->hp", C[t], S) + x[t]
    np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=1e-5)


def test_minicpm_reads_as_before_under_the_new_mup_fields(ctx):
    """MiniCPM-SALA's toy with the three multipliers left to their defaults
    and with them given as MiniCPM's own numbers: the same scalings and,
    bit for bit, the same logits of a chunk."""
    sala = json.loads((REPO / "tests/benchmark/toy/configs/toy-sala.json").read_text())
    c = harness._module(REPO / "benchmark/build/minicpm_sala.py").model_config(sala)
    assert (c.residual_multiplier, c.logit_multiplier, c.attention_multiplier) == (None,) * 3
    assert c.residual_scale == c.scale_depth / c.published_layers ** 0.5
    assert c.logit_scale == c.dim_model_base / c.hidden_size and not c.tie_embeddings
    given = dataclasses.replace(c, residual_multiplier=c.residual_scale,
                                logit_multiplier=c.logit_scale,
                                attention_multiplier=c.head_dim ** -0.5)
    ids = _ids(40, seed=11)
    logits = []
    for config in (c, given):
        eng = Engine(SparseLinearLLM(config, ctx, key=jnp.asarray(KEY)), backend="dist",
                     max_len=MAX_LEN)
        logits.append(_join(eng, paged_drive.alloc_chains(eng, 1, BLOCK), 0, ids)[0][-1])
    np.testing.assert_array_equal(logits[0], logits[1])
