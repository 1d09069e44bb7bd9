"""Pipeline-parallel tests: GPipe schedule parity, VJP through the
pipeline, and the TP×PP engine's bitwise contract.

Two tiers on the 8-device CPU mesh:

* **Schedule** (pure-``pp`` 4-stage mesh, toy stages): ``gpipe_forward``
  must equal the sequential layer sweep bitwise for any microbatch count
  (masked ticks compute on zeros and are discarded — M=1 is almost all
  masked ticks), the ``jax.lax.scan`` body (``TDT_PP_UNROLL=0``) must be
  bitwise the unrolled body, and ``jax.grad`` through the unrolled
  schedule must match the sequential gradient (the custom-VJP /
  ppermute-transpose backward pass).
* **Engine** (world 4 = 2 pp × 2 tp vs the single-mesh 2-way TP engine,
  same ``PRNGKey`` so the weights are identical): prefill logits and the
  reassembled KV slabs byte-identical, and full greedy ``serve`` streams
  byte-identical — the contract ``docs/disagg.md`` states.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers.pp import PPCommLayer
from triton_dist_tpu.layers.pp_schedule import gpipe_forward, gpipe_stage_params
from triton_dist_tpu.runtime import telemetry
from triton_dist_tpu.runtime.mesh import initialize_distributed
from triton_dist_tpu.runtime.platform import cpu_mesh

L = 4       # toy layers (one per stage on the 4-stage mesh)
D = 8       # toy feature width
MB = 2      # rows per microbatch


@pytest.fixture(scope="module")
def ctx_pp4():
    m = cpu_mesh((4,), ("pp",))
    return initialize_distributed(
        devices=list(m.devices.flat), axis_names=("pp",), set_default=False
    )


def _pipeline(ctx, Ws, x, unroll):
    """Run the toy stage stack through gpipe_forward on the 4-stage mesh;
    broadcast the last stage's output (all-gather pick, bitwise)."""
    S = int(ctx.mesh.shape["pp"])
    comm = PPCommLayer(axis="pp", backend="xla", mesh_axes=("pp",))

    def fn(W, xb):
        def stage(h):
            stack = gpipe_stage_params(W, L, axis="pp")

            def layer(h, w):
                return jnp.tanh(h @ w), None

            h, _ = jax.lax.scan(layer, h, stack)
            return h

        out = gpipe_forward(stage, xb, axis="pp", comm=comm, unroll=unroll)
        return jax.lax.all_gather(out, "pp", axis=0)[S - 1]

    return jax.shard_map(fn, mesh=ctx.mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(Ws, x)


def _sequential(Ws, x):
    """Per-microbatch sequential sweep with the same (mb, d) @ (d, d)
    shapes the pipeline stages use — the bitwise reference."""
    def fold(h):
        for w in Ws:
            h = jnp.tanh(h @ w)
        return h

    return jnp.stack([fold(x[m]) for m in range(x.shape[0])])


@pytest.mark.parametrize("m_total", [1, 3, 6])
def test_gpipe_matches_sequential_bitwise(ctx_pp4, m_total):
    """The 4-stage GPipe sweep equals the sequential layer sweep bitwise
    for short (masked-tick-dominated) and long microbatch streams."""
    rng = np.random.default_rng(m_total)
    Ws = jnp.asarray(rng.standard_normal((L, D, D)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((m_total, MB, D)), jnp.float32)
    out = jax.jit(lambda W, xb: _pipeline(ctx_pp4, W, xb, True))(Ws, x)
    ref = jax.jit(_sequential)(Ws, x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_gpipe_scan_matches_unrolled_bitwise(ctx_pp4):
    """TDT_PP_UNROLL=0's lax.scan schedule body shares _tick with the
    unrolled body — their outputs must be bitwise identical."""
    rng = np.random.default_rng(7)
    Ws = jnp.asarray(rng.standard_normal((L, D, D)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, MB, D)), jnp.float32)
    unrolled = jax.jit(lambda W, xb: _pipeline(ctx_pp4, W, xb, True))(Ws, x)
    scanned = jax.jit(lambda W, xb: _pipeline(ctx_pp4, W, xb, False))(Ws, x)
    np.testing.assert_array_equal(np.asarray(unrolled), np.asarray(scanned))


def test_gpipe_vjp_matches_sequential(ctx_pp4):
    """jax.grad through the unrolled schedule (ring-shift transpose =
    reversed pipeline) matches the sequential gradient."""
    rng = np.random.default_rng(11)
    Ws = jnp.asarray(rng.standard_normal((L, D, D)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, MB, D)), jnp.float32)

    g_pipe = jax.jit(jax.grad(
        lambda W: jnp.sum(_pipeline(ctx_pp4, W, x, True) ** 2)
    ))(Ws)
    g_ref = jax.jit(jax.grad(
        lambda W: jnp.sum(_sequential(W, x) ** 2)
    ))(Ws)
    np.testing.assert_allclose(
        np.asarray(g_pipe), np.asarray(g_ref), rtol=1e-5, atol=1e-5
    )


# ------------------------------------------------------------- TP×PP engine


@pytest.fixture(scope="module")
def engines():
    """(single-mesh tp-2 engine, 2×2 tp×pp engine) over IDENTICAL weights
    (same PRNGKey; DenseLLM init is mesh-independent)."""
    from triton_dist_tpu.models import PRESETS, DenseLLM, Engine

    cfg = PRESETS["test-dense"]
    devs = jax.devices("cpu")
    ctx_tp = initialize_distributed(
        axis_names=("tp",), devices=devs[:2], set_default=False
    )
    ctx_pp = initialize_distributed(
        axis_names=("pp", "tp"), axis_sizes=(2, 2), devices=devs[:4],
        set_default=False,
    )
    m_ref = DenseLLM(cfg, ctx_tp, key=jax.random.PRNGKey(1))
    m_pp = DenseLLM(cfg, ctx_pp, key=jax.random.PRNGKey(1))
    return (Engine(m_ref, backend="xla", max_len=32),
            Engine(m_pp, backend="xla", max_len=32), m_pp)


@pytest.mark.timeout(600)
def test_pp_engine_prefill_bitwise(engines):
    """2×2 prefill — microbatches through the pipeline, KV via the aux
    channel, tiled stage gather — is byte-identical to the tp-2 engine:
    logits, ks, and vs."""
    e_ref, e_pp, _ = engines
    assert e_pp.pp_world == 2
    tok = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 8)), jnp.int32
    )
    l0, k0, v0 = jax.tree.map(
        np.asarray, e_ref._prefill(e_ref.model.params, tok)
    )
    l1, k1, v1 = jax.tree.map(
        np.asarray, e_pp._prefill(e_pp.model.params, tok)
    )
    np.testing.assert_array_equal(l0, l1)
    np.testing.assert_array_equal(k0, k1)
    np.testing.assert_array_equal(v0, v1)
    snap = telemetry.snapshot()
    assert telemetry.counter_value("tdt_pp_prefill_microbatches_total") >= 4.0
    assert telemetry.counter_value("tdt_pp_ticks_total") >= 5.0
    (stages,) = snap["gauges"]["tdt_pp_stages"]
    assert stages["value"] == 2.0


@pytest.mark.timeout(600)
def test_pp_engine_serve_bitwise(engines):
    """Full serve (prefill + round-robin decode across stages) streams
    byte-identical tokens on the 2×2 mesh."""
    e_ref, e_pp, _ = engines
    tok = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 8)), jnp.int32
    )
    out_ref = np.asarray(e_ref.serve(tok, 6, key=jax.random.PRNGKey(7)))
    out_pp = np.asarray(e_pp.serve(tok, 6, key=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(out_ref, out_pp)


@pytest.mark.timeout(600)
def test_pp_engine_scan_schedule_bitwise(engines, monkeypatch):
    """TDT_PP_UNROLL=0 swaps the prefill schedule body for lax.scan; the
    serve stream must not move a bit."""
    from triton_dist_tpu.models import Engine

    e_ref, _, m_pp = engines
    monkeypatch.setenv("TDT_PP_UNROLL", "0")
    e_scan = Engine(m_pp, backend="xla", max_len=32)
    tok = jnp.asarray(
        np.random.default_rng(3).integers(0, 256, (2, 7)), jnp.int32
    )
    out_ref = np.asarray(e_ref.serve(tok, 5, key=jax.random.PRNGKey(9)))
    out_pp = np.asarray(e_scan.serve(tok, 5, key=jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(out_ref, out_pp)
