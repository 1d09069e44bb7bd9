"""Ask the chip's compiler, without the chip.

The CPU-sim suite proves the *protocols* (interpret mode executes the DMA /
semaphore semantics); it does NOT prove Mosaic can lower the kernels for a
real TPU, that a program fits the device, or that it partitions over a mesh.
The TPU's compiler is installed here and compiles for a chip that is
described and not attached, so this file does — no execution, no hardware:

* the multi-chip kernels, one by one, against a described **v5e 2x4**;
* the programs ``chip_smoke.py`` runs on ONE chip — chunked prefill, the
  paged decode on ``dist``, the mega paged step — at Qwen3-8B widths and the
  smoke's depths, from ``jax.eval_shape``'d parameters, held to 16 GB;
* the TP=4 prefill / chunk / decode programs of the full 36-layer preset on a
  described **v5e 2x2**, with the collective kernels and their bounded-wait
  ``semaphore_read`` polls, held to per-device bytes.

The topology is described inside the fixtures, in the test's own process:
only one process at a time may load the TPU library, the worker that runs
this file keeps it until it exits, and no other test file may load it (a
child started from here could not either).

These shapes are real-TPU-sized (lane-aligned, bf16) — unlike the CPU-sim
tests they exercise the exact tiling Mosaic must schedule on hardware.
"""

import dataclasses
import functools
import re
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

WORLD = 8
GIB = 1 << 30
#: One v5e chip's HBM (Google Cloud documentation, "TPU v5e").
HBM_BYTES = 16 * 10**9

# Each compile is a full XLA TPU pipeline (seconds; a cold first one more).
pytestmark = pytest.mark.timeout(420)


def _describe(topology: str):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name=topology
        )
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no {topology} topology can be described here: "
                    f"{type(e).__name__}: {e}")


@pytest.fixture(scope="module")
def tpu_mesh():
    devs = np.array(_describe("v5e:2x4").devices)
    assert devs.size == WORLD
    return Mesh(devs.reshape(WORLD), ("tp",))


@pytest.fixture(scope="module")
def topo_2x2():
    return _describe("v5e:2x2")


def compile_sharded(mesh, fn, arg_shapes, in_specs, out_specs):
    """jit(shard_map(fn)) → .lower(abstract args) → .compile() on the
    topology-only client. Raises (test fails) iff Mosaic/XLA reject it.

    ``force_mosaic()`` is LOAD-BEARING (r5): tracing happens on the CPU
    default backend, where ``interpret_mode_default`` would hand every
    pallas_call InterpretParams — the topology compile then exercises the
    pure-HLO interpret emulation and proves nothing about Mosaic. The
    tpu_custom_call assertion keeps that from regressing silently."""
    from triton_dist_tpu.runtime.platform import force_mosaic

    f = jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        ),
        in_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), tuple(in_specs),
            is_leaf=lambda x: isinstance(x, P),
        ),
    )
    with force_mosaic():
        lowered = f.lower(*arg_shapes)
        assert "tpu_custom_call" in lowered.as_text(), (
            "no Mosaic custom-call in the lowered module — the kernel "
            "traced through the interpret path, not Mosaic")
        compiled = lowered.compile()
    assert compiled is not None
    return compiled


def test_lowering_fused_ag_gemm(tpu_mesh):
    """One-sided ring AG + tiled GEMM consumer (allgather_gemm.py
    PALLAS_FUSED) compiles for the 8-chip topology."""
    from triton_dist_tpu.kernels import AGGemmMethod, ag_gemm_shard

    m_shard, k, n_shard = 256, 512, 256
    a = jax.ShapeDtypeStruct((WORLD * m_shard, k), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((k, WORLD * n_shard), jnp.bfloat16)
    compile_sharded(
        tpu_mesh,
        lambda a_s, b_s: ag_gemm_shard(
            a_s, b_s, axis="tp", method=AGGemmMethod.PALLAS_FUSED
        ),
        (a, b),
        (P("tp"), P(None, "tp")),
        P(None, "tp"),
    )


def test_lowering_fused_gemm_rs(tpu_mesh):
    """Tiled GEMM producer + fused-add-on-receive ring RS
    (gemm_reduce_scatter.py PALLAS_FUSED) compiles for the 8-chip topology."""
    from triton_dist_tpu.kernels import GemmRSMethod, gemm_rs_shard

    m, k, n = 512, WORLD * 256, 256
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
    compile_sharded(
        tpu_mesh,
        lambda a_s, b_s: gemm_rs_shard(
            a_s, b_s, axis="tp", method=GemmRSMethod.PALLAS_FUSED
        ),
        (a, b),
        (P(None, "tp"), P("tp")),
        P("tp"),
    )


def test_lowering_one_sided_a2a(tpu_mesh):
    """The one-sided all-to-all push kernel (ep_a2a.py use_pallas=True)
    compiles for the 8-chip topology."""
    from triton_dist_tpu.kernels import all_to_all_single_shard

    x = jax.ShapeDtypeStruct((WORLD, WORLD, 64, 256), jnp.bfloat16)
    compile_sharded(
        tpu_mesh,
        lambda xs: all_to_all_single_shard(xs[0], axis="tp", use_pallas=True)[None],
        (x,),
        (P("tp"),),
        P("tp"),
    )


def test_lowering_ep_fused_dispatch_mlp(tpu_mesh):
    """The mega-EP one-kernel a2a-dispatch + grouped expert MLP
    (ep_fused.py) compiles for the 8-chip topology."""
    from triton_dist_tpu.kernels.ep_fused import fused_dispatch_mlp_shard

    e_local, cap, d, ff = 2, 64, 256, 512
    send = jax.ShapeDtypeStruct((WORLD, WORLD, e_local * cap, d), jnp.bfloat16)
    wg = jax.ShapeDtypeStruct((WORLD * e_local, d, ff), jnp.bfloat16)
    wu = jax.ShapeDtypeStruct((WORLD * e_local, d, ff), jnp.bfloat16)
    wd = jax.ShapeDtypeStruct((WORLD * e_local, ff, d), jnp.bfloat16)
    compile_sharded(
        tpu_mesh,
        lambda s, g, u, dn: fused_dispatch_mlp_shard(
            s[0], g, u, dn, capacity=cap, axis="tp", mesh_axes=("tp",),
            block_f=256,
        )[None],
        (send, wg, wu, wd),
        (P("tp"), P("tp"), P("tp"), P("tp")),
        P("tp"),
    )


def test_lowering_ep_fused_combine(tpu_mesh):
    """The one-kernel dispatch+MLP+combine (in-kernel return a2a, VMEM-
    sourced remote puts) compiles for the 8-chip topology — both wire
    dtypes."""
    from triton_dist_tpu.kernels.ep_fused import fused_dispatch_mlp_combine_shard

    e_local, cap, d, ff = 2, 64, 256, 512
    send = jax.ShapeDtypeStruct((WORLD, WORLD, e_local * cap, d), jnp.bfloat16)
    wg = jax.ShapeDtypeStruct((WORLD * e_local, d, ff), jnp.bfloat16)
    wu = jax.ShapeDtypeStruct((WORLD * e_local, d, ff), jnp.bfloat16)
    wd = jax.ShapeDtypeStruct((WORLD * e_local, ff, d), jnp.bfloat16)
    for fp8 in (False, True):
        compile_sharded(
            tpu_mesh,
            lambda s, g, u, dn, fp8=fp8: fused_dispatch_mlp_combine_shard(
                s[0], g, u, dn, capacity=cap, axis="tp", mesh_axes=("tp",),
                block_f=256, wire_fp8=fp8,
            )[None],
            (send, wg, wu, wd),
            (P("tp"), P("tp"), P("tp"), P("tp")),
            P("tp"),
        )


def test_lowering_mega_decode_layer(tpu_mesh):
    """A full megakernel decode layer (fused LN+QKV+RoPE, cache update,
    flash decode, o-proj AR, fused MLP block, one-shot AR) compiles for the
    8-chip topology at TP8 Qwen3-8B-width shapes — the whole mega backend's
    per-layer program through Mosaic."""
    from triton_dist_tpu.megakernel.builder import ModelBuilder
    from triton_dist_tpu.models import ModelConfig

    cfg = ModelConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=12288,
        num_layers=1, num_q_heads=32, num_kv_heads=8, head_dim=128,
        dtype="bfloat16",
    )
    layer_fn = ModelBuilder(
        cfg, axis="tp", world=WORLD, mesh_axes=("tp",)
    ).build_layer_fn()
    bsz, S = 8, 512
    hkv_l = cfg.num_kv_heads // WORLD
    d = cfg.hidden_size
    # GLOBAL shapes; the tp shardings below hand each rank its shard.
    lp = {
        "ln1": jax.ShapeDtypeStruct((d,), jnp.bfloat16),
        "wqkv": jax.ShapeDtypeStruct(
            (d, (cfg.num_q_heads + 2 * cfg.num_kv_heads) * cfg.head_dim),
            jnp.bfloat16),
        "q_norm": jax.ShapeDtypeStruct((cfg.head_dim,), jnp.bfloat16),
        "k_norm": jax.ShapeDtypeStruct((cfg.head_dim,), jnp.bfloat16),
        "wo": jax.ShapeDtypeStruct(
            (cfg.num_q_heads * cfg.head_dim, d), jnp.bfloat16),
        "ln2": jax.ShapeDtypeStruct((d,), jnp.bfloat16),
        "mlp_gate": jax.ShapeDtypeStruct(
            (d, cfg.intermediate_size), jnp.bfloat16),
        "mlp_up": jax.ShapeDtypeStruct(
            (d, cfg.intermediate_size), jnp.bfloat16),
        "mlp_down": jax.ShapeDtypeStruct(
            (cfg.intermediate_size, d), jnp.bfloat16),
    }
    x = jax.ShapeDtypeStruct((bsz, d), jnp.bfloat16)
    ks = jax.ShapeDtypeStruct((1, bsz, WORLD * hkv_l, S, cfg.head_dim), jnp.bfloat16)
    lengths = jax.ShapeDtypeStruct((bsz,), jnp.int32)

    compile_sharded(
        tpu_mesh,
        lambda lp_, x_, ks_, vs_, len_: layer_fn(lp_, x_, ks_, vs_, 0, len_)[0],
        (lp, x, ks, ks, lengths),
        ({k: (P(None, "tp") if k in ("wqkv", "mlp_gate", "mlp_up")
              else P("tp", None) if k in ("wo", "mlp_down") else P())
          for k in lp}, P(), P(None, None, "tp"), P(None, None, "tp"), P()),
        P(),
    )


def test_lowering_ring_attention(tpu_mesh):
    """SP ring attention (sp.py) — per-step remote KV rotation + flash
    consumer — compiles for the 8-chip topology."""
    from triton_dist_tpu.kernels.sp import ring_attention_shard

    b, hq, hkv, s_loc, d = 1, 8, 2, 512, 128
    s = WORLD * s_loc
    q = jax.ShapeDtypeStruct((b, hq, s, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    compile_sharded(
        tpu_mesh,
        lambda q_, k_, v_: ring_attention_shard(
            q_, k_, v_, axis="tp", causal=True, block_q=256, block_k=256
        ),
        (q, k, v),
        (P(None, None, "tp"), P(None, None, "tp"), P(None, None, "tp")),
        P(None, None, "tp"),
    )


def _entry_schedule(compiled):
    """Linearized (kind, idx) event order of the compiled module's entry
    computation: collective-permute START/DONE ops and Mosaic (FLASH)
    custom-calls, in the TPU scheduler's emitted order."""
    txt = compiled.as_text()
    entry = txt[txt.index("ENTRY "):]
    order = []
    for i, line in enumerate(entry.splitlines()):
        if "collective-permute-start" in line:
            order.append(("START", i))
        elif "collective-permute-done" in line:
            order.append(("DONE", i))
        elif "tpu_custom_call" in line:
            order.append(("FLASH", i))
    return order


def _assert_hops_ride_under_flash(order, min_flash):
    """THE scheduled-module overlap assertion (r4 verdict item 4): during
    every flash call except the FIRST (nothing has been issued before it
    on some ranks' view) and the LAST (no hop remains to hide under it),
    at least one collective-permute must be IN FLIGHT (a start issued with
    its done not yet consumed). A serialized schedule (start, done, flash,
    start, done, flash, ...) has zero in-flight transfers during every
    mid-ring flash and fails."""
    kinds = [k for k, _ in order]
    n_flash = kinds.count("FLASH")
    assert n_flash >= min_flash, (n_flash, order)
    assert n_flash >= 3, "need at least one mid-ring flash to assert on"
    in_flight = 0
    flash_seen = 0
    for k in kinds:
        if k == "START":
            in_flight += 1
        elif k == "DONE":
            in_flight -= 1
        else:
            flash_seen += 1
            if 1 < flash_seen < n_flash:
                assert in_flight > 0, (
                    "no collective-permute in flight during flash call "
                    f"#{flash_seen} — the ring serialized", kinds)


def test_ring_schedule_hops_under_flash(tpu_mesh):
    """The REAL TPU scheduled module brackets every mid-ring flash call
    with in-flight collective-permutes — XLA's latency-hiding scheduler
    hoisting the hop under the in-flight flash step, asserted from the
    compiled text (the scheduled-module half of the overlap claim; the
    dataflow half lives in tests/test_ring_overlap.py)."""
    from triton_dist_tpu.kernels.sp import ring_attention_shard

    b, hq, hkv, s_loc, d = 1, 8, 2, 512, 128
    s = WORLD * s_loc
    q = jax.ShapeDtypeStruct((b, hq, s, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    compiled = compile_sharded(
        tpu_mesh,
        lambda q_, k_, v_: ring_attention_shard(
            q_, k_, v_, axis="tp", causal=True, block_q=256, block_k=256
        ),
        (q, k, v),
        (P(None, None, "tp"),) * 3,
        P(None, None, "tp"),
    )
    _assert_hops_ride_under_flash(_entry_schedule(compiled), min_flash=WORLD)


def test_ring_2d_schedule_hops_under_flash(tpu_mesh):
    """Same scheduled-module assertion for the two-level (DCN x ICI) ring
    on a (2,4) partition of the topology: the early-issued outer hops and
    the inner hops are all in flight under mid-ring flash calls."""
    from triton_dist_tpu.kernels.sp import ring_attention_2d_shard

    mesh2 = Mesh(tpu_mesh.devices.reshape(2, 4), ("dp", "tp"))
    b, hq, hkv, s_loc, d = 1, 8, 2, 512, 128
    s = WORLD * s_loc
    q = jax.ShapeDtypeStruct((b, hq, s, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    compiled = compile_sharded(
        mesh2,
        lambda q_, k_, v_: ring_attention_2d_shard(
            q_, k_, v_, axes=("dp", "tp"), causal=True,
            block_q=256, block_k=256
        ),
        (q, k, v),
        (P(None, None, ("dp", "tp")),) * 3,
        P(None, None, ("dp", "tp")),
    )
    _assert_hops_ride_under_flash(_entry_schedule(compiled), min_flash=WORLD)


def test_lowering_ag_attention(tpu_mesh):
    """The fused AG-SP attention kernel (one-sided KV gather + per-source
    waits + streaming online softmax in ONE kernel) compiles via Mosaic
    for the 8-chip topology — both the inference variant and the training
    forward (LSE + gathered-KV residuals for ``ag_attention_fn``)."""
    from triton_dist_tpu.kernels.ag_attention import ag_flash_attention_shard

    b, hq, hkv, s_loc, d = 1, 8, 2, 512, 128
    s = WORLD * s_loc
    q = jax.ShapeDtypeStruct((b, hq, s, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16)
    compile_sharded(
        tpu_mesh,
        lambda q_, k_, v_: ag_flash_attention_shard(
            q_, k_, v_, axis="tp", mesh_axes=("tp",), causal=True
        ),
        (q, k, v),
        (P(None, None, "tp"),) * 3,
        P(None, None, "tp"),
    )
    compile_sharded(
        tpu_mesh,
        lambda q_, k_, v_: ag_flash_attention_shard(
            q_, k_, v_, axis="tp", mesh_axes=("tp",), causal=True,
            return_residuals=True,
        )[0],
        (q, k, v),
        (P(None, None, "tp"),) * 3,
        P(None, None, "tp"),
    )


# ===================================================== chip_smoke's programs
#
# Whole engine programs at Qwen3-8B widths. Nothing can be placed on a
# described device, so the model is built over ``jax.eval_shape``'d
# parameters and every operand is a shape with its sharding. The op-by-op
# programs scan over layers, so the smoke's real depth compiles in seconds.


def _smoke_sizes():
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import chip_smoke

    return chip_smoke.QWEN3_8B, chip_smoke.QWEN3_8B_TP4


def _abstract_model(devices, depth):
    """(model, params) at Qwen3-8B widths over ``devices`` (one TP mesh):
    parameters are ShapeDtypeStructs carrying the init's own shardings."""
    from triton_dist_tpu.models.config import PRESETS
    from triton_dist_tpu.models.dense import DenseLLM, _build_params, _specs
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(
        devices=list(devices), axis_names=("tp",), set_default=False
    )
    cfg = dataclasses.replace(PRESETS["qwen3-8b"], num_layers=depth)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=ctx.replicated())
    shapes = jax.eval_shape(functools.partial(_build_params, cfg), key)
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=ctx.sharding(*s)),
        shapes, _specs(cfg), is_leaf=lambda x: isinstance(x, P),
    )
    return DenseLLM(cfg, ctx, params=params), params


def _operands(engine, sizes, slots):
    """The serving operands of ``engine`` as shapes, by name."""
    cfg = engine.model.config
    rep = engine.model.ctx.replicated()
    max_blocks = sizes.max_len // 16

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def kv(batch, seq):
        return sds((cfg.num_layers, batch, cfg.num_kv_heads, seq, cfg.head_dim),
                   jnp.bfloat16, engine._kv_sharding)

    return {
        "sds": sds, "kv": kv,
        "key": sds((2,), jnp.uint32),
        "slots_i32": sds((slots,), jnp.int32),
        "tables": sds((slots, max_blocks), jnp.int32),
        "pool": sds((cfg.num_layers, slots * max_blocks + 1, cfg.num_kv_heads,
                     16, cfg.head_dim), jnp.bfloat16, engine._pool_sharding),
    }


def _compile(lowered, *, kernels=(), mosaic=True):
    """Compile a lowered program; assert it reaches Mosaic (unless it is one
    that holds no kernel) and, by name, the kernels it is expected to hold.
    Returns (compiled, bytes one device holds while it runs)."""
    txt = lowered.as_text()
    assert not mosaic or "tpu_custom_call" in txt, "no Mosaic kernel in the lowered module"
    for name in kernels:
        assert name in txt, f"kernel {name} not in the lowered module"
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return compiled, held


def _nbytes(sds):
    """Bytes of ``sds`` one device holds."""
    shard = sds.sharding.shard_shape(sds.shape)
    return int(np.prod(shard)) * np.dtype(sds.dtype).itemsize


def _pool_sized_copies(hlo: str, pool) -> list[str]:
    """The ``copy`` instructions of a compiled module whose result has the
    shape of the stacked pool ``pool`` or of one layer's slice of it."""
    dt = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}[np.dtype(pool.dtype).name]
    shapes = [",".join(map(str, pool.shape[i:])) for i in (0, 1)]
    pat = re.compile(
        r"= \(?%s\[(%s)\][^ ]* copy\(" % (dt, "|".join(map(re.escape, shapes))))
    return [line.strip()[:160] for line in hlo.splitlines() if pat.search(line)]


def test_smoke_one_chip_dist_programs_fit(topo_2x2):
    """The smoke's ``dist`` serving programs on one chip: chunked prefill at
    a ragged and an aligned prompt length (flash attention pads what Mosaic
    cannot tile), and the paged decode chunk against the pool where it
    lies. Of that program the compiled module itself is held to what the
    in-place decode is for: the pool operands are aliased to the outputs,
    and no ``copy`` of the pool's shape, or of one layer's slice of it, is
    left (the scan over ``xs`` / ``ys`` made two of the first a step, the
    ``pool[layer]`` operand of the kernel two of the second a layer)."""
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime.platform import force_mosaic

    sizes, _ = _smoke_sizes()
    model, params = _abstract_model(topo_2x2.devices[:1], sizes.depth)
    with force_mosaic():
        eng = Engine(model, backend="dist", max_len=sizes.max_len)
        ops = _operands(eng, sizes, sizes.num_slots)
        sds, kv = ops["sds"], ops["kv"]
        for p_len in (1500, 1024):
            _, held = _compile(eng._prefill_chunk_prog.lower(
                params, sds((1, p_len), jnp.int32), kv(1, p_len), kv(1, p_len),
                sds((), jnp.int32), sds((), jnp.int32)))
            # Beside the chunk program: the serving pool.
            assert held + 2 * _nbytes(ops["pool"]) < HBM_BYTES, (p_len, held)
        chunk, held = _compile(eng._decode_chunk_paged.lower(
            params, (), ops["slots_i32"], ops["pool"], ops["pool"],
            ops["tables"], ops["slots_i32"], ops["slots_i32"], sizes.chunk,
            ops["key"]), kernels=("paged_flash_decode",))
    # The pool is an operand and nothing of its size is held beside it.
    assert held < HBM_BYTES, held
    m = chunk.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * _nbytes(ops["pool"]), m.alias_size_in_bytes
    hlo = chunk.as_text()
    header = hlo.split("\n", 1)[0]
    # (params..., token, pk, pv, ...) -> (out, token, pk, pv, ...)
    first = len(jax.tree.leaves(params)) + 1
    for out, param in ((2, first), (3, first + 1)):
        assert re.search(r"\{%d\}: \(%d, \{\}, may-alias\)" % (out, param), header), header
    assert _pool_sized_copies(hlo, ops["pool"]) == []
    # ... and the search does find the two this PR removed, by their lines.
    layer = ",".join(map(str, ops["pool"].shape[1:]))
    was = ("  %copy.46 = bf16[" + str(ops["pool"].shape[0]) + "," + layer + "]{4,3,2,1,0:T(8,128)(2,1)} copy(%x)\n"
           "  %copy.10 = bf16[" + layer + "]{3,2,1,0:T(8,128)(2,1)} copy(%y)")
    assert len(_pool_sized_copies(was, ops["pool"])) == 2


def test_smoke_one_chip_mega_step_fits(topo_2x2):
    """The mega paged step (every layer unrolled into one task graph of
    fused Pallas kernels, tables and active mask as data) at the depth the
    smoke runs it, beside the second copy of the layer weights it keeps."""
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime.platform import force_mosaic

    sizes, _ = _smoke_sizes()
    model, params = _abstract_model(topo_2x2.devices[:1], sizes.mega_depth)
    ctx = model.ctx
    per_layer = [
        jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape[1:], a.dtype,
                sharding=NamedSharding(ctx.mesh, P(*a.sharding.spec[1:]))),
            model._layer_stack(params))
        for _ in range(sizes.mega_depth)
    ]
    # Engine pre-splits REAL weights layer by layer; shapes cannot be sliced.
    model.split_layer_params = lambda: per_layer
    with force_mosaic():
        eng = Engine(model, backend="mega", max_len=sizes.max_len)
        ops = _operands(eng, sizes, sizes.num_slots)
        _, held = _compile(eng._decode_chunk_paged.lower(
            params, per_layer, ops["slots_i32"], ops["pool"], ops["pool"],
            ops["tables"], ops["slots_i32"], ops["slots_i32"], sizes.chunk,
            ops["key"]))
    # The stacked layer weights are no operand of the step (they back the
    # op-by-op prefill) but stay resident beside it.
    stacked = sum(_nbytes(x) for x in jax.tree.leaves(model._layer_stack(params)))
    assert held + stacked < HBM_BYTES, (held, stacked)


def test_smoke_tp4_programs(topo_2x2):
    """The four-chip path: the full 36-layer preset, TP=4 on the 2x2 mesh.
    One-shot prefill through the fused AG-GEMM / GEMM-RS kernels, chunked
    prefill through the fused GEMM-AR ring (aligned) and dot+psum (ragged),
    the paged decode chunk through the one-shot GEMM-AR kernel, the pool
    sharded by head — compiled with the hardware
    wait bound, so the bounded waits' ``semaphore_read`` polls go through
    Mosaic — and every device holds a quarter of the layers."""
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime import resilience
    from triton_dist_tpu.runtime.platform import force_mosaic

    _, sizes = _smoke_sizes()
    model, params = _abstract_model(topo_2x2.devices, sizes.depth)
    weights = sum(_nbytes(x) for x in jax.tree.leaves(params))
    # 16.4 GB of weights: embedding replicated, the rest in quarters.
    assert 4.5 * GIB < weights < 5.0 * GIB, weights / GIB
    with force_mosaic():
        assert resilience.wait_bound() == resilience.DEFAULT_WAIT_BOUND_HW
        eng = Engine(model, backend="dist", max_len=sizes.max_len)
        ops = _operands(eng, sizes, sizes.num_slots)
        sds, kv = ops["sds"], ops["kv"]
        prefill = eng._prefill.lower(
            params, sds((1, sizes.oneshot_len), jnp.int32))
        _, held = _compile(prefill, kernels=(
            "_ag_gemm_fused_kernel", "_gemm_rs_fused_kernel"))
        assert held < HBM_BYTES / 2, held
        for p_len, kernels in ((1024, ("_gemm_ar_fused_kernel",)), (1500, ())):
            _, held = _compile(eng._prefill_chunk_prog.lower(
                params, sds((1, p_len), jnp.int32), kv(1, p_len), kv(1, p_len),
                sds((), jnp.int32), sds((), jnp.int32)), kernels=kernels)
            assert held < HBM_BYTES / 2, (p_len, held)
        decode_args = (params, (), ops["slots_i32"], ops["pool"], ops["pool"],
                       ops["tables"], ops["slots_i32"], ops["slots_i32"],
                       sizes.chunk, ops["key"])
        chunk, held = _compile(
            eng._decode_chunk_paged.lower(*decode_args),
            kernels=("_gemm_ar_ll_kernel", "paged_flash_decode"))
        assert held < HBM_BYTES / 2, held
        jaxpr = str(eng._decode_chunk_paged.trace(*decode_args).jaxpr)
    assert "semaphore_read" in jaxpr
    # Each chip walks its own heads' pages: its quarter of the pool, in place.
    assert _pool_sized_copies(chunk.as_text(), jax.ShapeDtypeStruct(
        ops["pool"].sharding.shard_shape(ops["pool"].shape), jnp.bfloat16)) == []


# --------------------------------------------------- kernels under their names
#
# A device trace keeps an operation's HLO instruction name and little else,
# so every Pallas kernel the served path reaches is given its own
# (``pl.pallas_call(name=...)``; ``dist_pallas_call`` names every collective
# kernel after its function). XLA names the custom call after it.


def _flash_decode(sds):
    from triton_dist_tpu.kernels.flash_decode import flash_decode

    return flash_decode, (sds((4, 32, 128)), sds((4, 8, 2048, 128)),
                          sds((4, 8, 2048, 128)), sds((4,), jnp.int32))


def _paged_flash_decode(sds):
    from triton_dist_tpu.kernels.flash_decode import paged_flash_decode

    return paged_flash_decode, (sds((4, 32, 128)), sds((513, 8, 16, 128)),
                                sds((513, 8, 16, 128)), sds((4, 128), jnp.int32),
                                sds((4,), jnp.int32))


def _paged_flash_decode_quant(sds):
    """An int8 pool. Mosaic refuses a DMA of the scale pool's one real lane,
    so on the chip the quantized pool is gathered and the contiguous kernel
    runs (``paged_flash_decode``): it has to compile all the same."""
    from triton_dist_tpu.kernels.flash_decode import paged_flash_decode

    def fn(q, k, v, ks, vs, tables, lengths, layer):
        return paged_flash_decode(q, k, v, tables, lengths, layer=layer,
                                  k_scale=ks, v_scale=vs)

    pool = lambda last, dt: sds((24, 513, 8, 16, last), dt)
    return fn, (sds((4, 32, 128)), pool(128, jnp.int8), pool(128, jnp.int8),
                pool(1, jnp.float32), pool(1, jnp.float32),
                sds((4, 128), jnp.int32), sds((4,), jnp.int32),
                sds((), jnp.int32))


def _flash_attention(sds):
    from triton_dist_tpu.kernels.flash_attn import flash_attention

    return flash_attention, (sds((1, 32, 1024, 128)), sds((1, 8, 1024, 128)),
                             sds((1, 8, 1024, 128)))


def _dsa_kth_value(sds):
    """A prefill chunk's index scores over the longest prompt buffer there
    is: 2048 query rows, 16640 positions, the 2048th largest a row."""
    from triton_dist_tpu.kernels.kth_value import kth_value

    return (lambda x: kth_value(x, 2048)), (sds((2048, 16640), jnp.float32),)


def _dsa_flash_prefill(sds):
    """A prefill chunk's attention under the selection at the published
    widths: 2048 query rows of 64 heads (192 + 64 and 256 wide) over the
    longest prompt buffer's latent rows."""
    from triton_dist_tpu.kernels.latent_flash import dsa_flash_prefill

    return (lambda *a: dsa_flash_prefill(*a, 256 ** -0.5)), (
        sds((2048, 64, 192)), sds((2048, 64, 64)), sds((16384, 576)),
        sds((2048, 16384), jnp.bool_), sds((512, 64, 192)), sds((512, 64, 256)))


def _latent_flash_prefill(sds):
    """A prefill chunk's attention over everything before it at A.X-K1's
    widths: 2048 query rows of 64 heads (128 + 64, padded to 256 inside, and
    128 wide), no mask handed over, the last chunk of the longest prompt
    buffer's latent rows (576 values in 640)."""
    from triton_dist_tpu.kernels.latent_flash import latent_flash_prefill

    return (lambda *a: latent_flash_prefill(*a, 0.13086)), (
        sds((2048, 64, 128)), sds((2048, 64, 64)), sds((32768, 640)), sds((), jnp.int32),
        sds((512, 64, 128)), sds((512, 64, 128)))


def _latent_flash_decode(sds):
    """One layer's decode step over the latent pool where it lies: 8 slots of
    64 absorbed queries over rows of 640, a table of 2064 pages of 16."""
    from triton_dist_tpu.kernels.latent_flash import latent_flash_decode

    return (lambda q, pool, t, n: latent_flash_decode(q, pool, 3, t, n, rank=512, scale=0.13086)), (
        sds((8, 64, 640)), sds((5, 8 * 2064 + 1, 1, 16, 640)), sds((8, 2064), jnp.int32),
        sds((8,), jnp.int32))


def _ssm_scan(sds):
    """One Mamba layer's scan over a prefill chunk at the published widths:
    512 rows of 5120 channels, a state of 16 a channel."""
    from triton_dist_tpu.kernels.ssm_scan import ssm_scan

    f32 = lambda *shape: sds(shape, jnp.float32)
    return ssm_scan, (f32(512, 5120), f32(512, 5120), f32(16, 5120), f32(512, 16),
                      f32(512, 16), f32(5120), f32(16, 5120))


def _shared_kv_decode(sds):
    """One reading layer's decode step at the published widths: 32 slots of
    40 block-diagonal query rows over the pool's rows of 1280, a table of
    168 blocks of 16 a slot."""
    from triton_dist_tpu.kernels.shared_kv_decode import shared_kv_decode

    pool = sds((1, 32 * 168 + 1, 1, 16, 1280))
    return (lambda *a: shared_kv_decode(*a, scale=0.125)), (
        sds((32, 40, 1280)), pool, pool, sds((32, 168), jnp.int32), sds((32,), jnp.int32))


def _lightning_chunk(sds):
    """One lightning layer's prefill chunk at the published widths: 2048
    rows of 32 heads of 128, the float32 state in and out."""
    from triton_dist_tpu.kernels.lightning_attn import lightning_chunk

    f32 = lambda *shape: sds(shape, jnp.float32)
    return lightning_chunk, (sds((2048, 4096)), sds((2048, 4096)), sds((2048, 4096)),
                             f32(32, 128, 128), f32(32), sds((), jnp.int32))


def _bsa_select(sds):
    """A sparse layer's selection scores for a chunk deep in the longest
    prompt: 2048 rows of 2 x 16 heads over 1024 pooled keys."""
    from triton_dist_tpu.kernels.block_sparse_attn import bsa_group_scores

    return (lambda *a: bsa_group_scores(*a, kernel=32, stride=16)), (
        sds((2048, 2, 16, 128)), sds((1024, 256)), sds((), jnp.int32))


def _bsa_prefill(sds):
    """A sparse layer's attend for that chunk: the prompt's K and V rows
    of 256 as they lie, a selection of the 256 blocks a (head, row)."""
    from triton_dist_tpu.kernels.block_sparse_attn import bsa_prefill

    return (lambda *a: bsa_prefill(*a, block=64, scale=128 ** -0.5)), (
        sds((2048, 2, 16, 128)), sds((16384, 256)), sds((16384, 256)),
        sds((2, 2048, 256), jnp.bool_), sds((), jnp.int32))


def _bsa_decode(sds):
    """A sparse layer's decode step: 8 slots, 64 selected pages of 64 rows
    a (slot, K/V head) of a three-layer pool, a table of 260 pages a slot."""
    from triton_dist_tpu.kernels.block_sparse_attn import bsa_decode

    pool = sds((3, 8 * 260 + 1, 1, 64, 256))
    i32 = lambda *shape: sds(shape, jnp.int32)
    return (lambda q, pk, pv, *a: bsa_decode(q, pk, pv, 1, *a, scale=128 ** -0.5)), (
        sds((8, 2, 16, 128)), pool, pool, i32(8, 260), i32(8, 2, 64), i32(8, 2), i32(8))


@pytest.mark.parametrize(
    "case", [_flash_decode, _paged_flash_decode, _flash_attention, _dsa_kth_value,
             _dsa_flash_prefill, _ssm_scan, _shared_kv_decode, _lightning_chunk,
             _bsa_select, _bsa_prefill, _bsa_decode, _latent_flash_prefill,
             _latent_flash_decode],
    ids=lambda f: f.__name__.lstrip("_"))
def test_named_kernel_compiles_under_its_name(topo_2x2, case):
    """At Qwen3-8B head shapes, for one v5e chip: the kernel compiles and
    its custom call is the instruction ``%<name>``, not ``closed_call.N``."""
    from triton_dist_tpu.runtime.platform import force_mosaic

    one = SingleDeviceSharding(topo_2x2.devices[0])
    fn, args = case(lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one))
    with force_mosaic():
        lowered = jax.jit(fn).lower(*args)
        assert "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
    name = case.__name__.lstrip("_")
    calls = [l.strip().removeprefix("ROOT ") for l in compiled.as_text().splitlines()
             if "tpu_custom_call" in l]
    assert calls and all(l.startswith(f"%{name}") for l in calls), calls


def test_quantized_pool_decodes_through_the_gather_on_the_chip(topo_2x2):
    """The int8 pool at the same shapes: no table-walk kernel, the
    contiguous one over the gathered and dequantized layer, compiled."""
    from triton_dist_tpu.runtime.platform import force_mosaic

    one = SingleDeviceSharding(topo_2x2.devices[0])
    fn, args = _paged_flash_decode_quant(
        lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one))
    with force_mosaic():
        compiled = jax.jit(fn).lower(*args).compile()
    calls = [l for l in compiled.as_text().splitlines() if "tpu_custom_call" in l]
    assert calls and all(l.strip().startswith("%flash_decode") for l in calls), calls


def test_collective_kernel_is_named_after_its_function(tpu_mesh):
    """``dist_pallas_call`` passes the kernel's own name on: the fused
    AG-GEMM's custom call is ``%_ag_gemm_fused_kernel``."""
    from triton_dist_tpu.kernels import AGGemmMethod, ag_gemm_shard

    a = jax.ShapeDtypeStruct((WORLD * 256, 512), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((512, WORLD * 256), jnp.bfloat16)
    compiled = compile_sharded(
        tpu_mesh,
        lambda a_s, b_s: ag_gemm_shard(
            a_s, b_s, axis="tp", method=AGGemmMethod.PALLAS_FUSED),
        (a, b), (P("tp"), P(None, "tp")), P(None, "tp"),
    )
    calls = [l for l in compiled.as_text().splitlines() if "tpu_custom_call" in l]
    assert calls and all("%_ag_gemm_fused_kernel" in l.split("=")[0] for l in calls), calls


# ---------------------------------------------------------------------------
# The second configuration's prefill chunk, at its published widths
# ---------------------------------------------------------------------------


def _abstract_latent_sparse(devices, config="glm-5.2-ep16-d5"):
    """(model, params, configuration file) of a ``LatentSparseLLM``
    configuration of the benchmark over one described chip, the parameters
    as shapes."""
    import importlib
    import json
    import sys

    from triton_dist_tpu.models import LatentSparseLLM
    from triton_dist_tpu.models.latent_sparse import layer_ones, layer_tensors
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    with open(os.path.join(root, f"benchmark/configs/{config}.json")) as f:
        cfg = json.load(f)
    c = importlib.import_module(f"benchmark.build.{cfg['architecture']}").model_config(cfg)
    ctx = initialize_distributed(devices=list(devices), axis_names=("tp",), set_default=False)
    dt = jnp.dtype(c.dtype)
    sds = lambda shape, dtype=dt: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=ctx.replicated())
    params = {"embed": sds((c.vocab_size, c.hidden_size)), "final_norm": sds((c.hidden_size,)),
              "lm_head": sds((c.hidden_size, c.vocab_size)), "layers": []}
    for layer in range(c.num_layers):
        lp = {name: sds(shape, jnp.float32 if name.startswith("router") else dt)
              for name, shape, _ in layer_tensors(c, layer)}
        lp.update({name: sds((n,)) for name, n in layer_ones(c, layer)})
        if c.index_kinds[layer] == "full":
            lp["ik_norm_b"] = sds((c.index_head_dim,))
        params["layers"].append(lp)
    return LatentSparseLLM(c, ctx, params=params), params, cfg


def _sorted_shapes(hlo: str) -> list[str]:
    """The first operand's shape of every ``sort`` in a compiled module."""
    types = (l.split(" = ", 1)[1].split(" sort(", 1)[0]
             for l in hlo.splitlines() if " = " in l and " sort(" in l)
    return [re.search(r"\w+\[[\d,]*\]", t).group() for t in types]


@functools.lru_cache(maxsize=None)
def _longdoc_chunk_program(topo, p_len: int):
    """``longdoc``'s chunk program over a prompt buffer of ``p_len`` rows,
    compiled for one described v5e chip (once a length: the tests below
    share it). -> (configuration, compiled HLO text, its ``tpu_custom_call``
    lines, bytes the device holds while it runs beside the pools)."""
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime.platform import force_mosaic

    model, params, cfg = _abstract_latent_sparse(topo.devices[:1])
    c, sv = model.config, cfg["serving"]
    rows = int(sv["prefill_chunk"])
    assert rows == c.index_topk == 2048
    with force_mosaic():
        eng = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
        rep = model.ctx.replicated()
        buf = lambda layers, width: jax.ShapeDtypeStruct(
            (layers, 1, 1, p_len, width), jnp.dtype(c.dtype), sharding=eng._kv_sharding)
        i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
        compiled, held = _compile(eng._prefill_chunk_prog.lower(
            params, i32((1, rows)), buf(c.num_layers, c.latent_row),
            buf(len(c.index_layers), c.index_head_dim), i32(()), i32(())),
            kernels=("dsa_kth_value", "dsa_flash_prefill"))
    pools = int(sv["slots"]) * int(sv["max_len"]) * sum(
        r.layers * r.heads * r.width for r in model.cache_rows()) * 2
    hlo = compiled.as_text()
    calls = [l.strip() for l in hlo.splitlines() if "tpu_custom_call" in l]
    return c, hlo, calls, held + pools


@pytest.mark.parametrize("p_len", [4096, 8192, 16384])
def test_latent_sparse_chunk_selects_without_a_sort(topo_2x2, p_len):
    """``longdoc``'s chunk program at each of its prompt lengths, for one
    v5e chip: it fits beside the pools, each of its two selecting layers
    finds the k-th index score in the ``dsa_kth_value`` kernel, and no sort
    of the chunk's ``f32[2048, P]`` scores is left (XLA's lowering of
    ``lax.top_k``, 27.6 ms a call at P 16384 on the chip). The sorts that
    stay are the router's top 8 of 256 and the held experts' ordering."""
    c, hlo, calls, held = _longdoc_chunk_program(topo_2x2, p_len)
    assert held < HBM_BYTES, held
    assert sum(l.startswith("%dsa_kth_value") for l in calls) == len(c.index_layers), calls
    assert f"f32[2048,{p_len}]" not in _sorted_shapes(hlo), _sorted_shapes(hlo)
    # ... and the search does find the parent's, by its line in the ledger
    was = ("  %sort.34 = (f32[2048,16384]{1,0:T(8,128)}, s32[2048,16384]{1,0:T(8,128)}) "
           "sort(%fusion.1, %iota.2), dimensions={1}, is_stable=true")
    assert _sorted_shapes(was) == ["f32[2048,16384]"]


@pytest.mark.parametrize("p_len", [4096, 8192, 16384])
def test_latent_sparse_chunk_attends_without_a_score_matrix(topo_2x2, p_len):
    """The same programs: every layer's attention under the mask is one call
    of the ``dsa_flash_prefill`` kernel, the selection's kernel is the only
    other one, and no value of the XLA body's score matrix's shape, 16 heads
    by 2048 queries by 2048 keys in float32 (268 MB through HBM three times
    a key block and head group; 0.077 s a fusion in the ledger's PR 30
    trace), is left in the program."""
    c, hlo, calls, _ = _longdoc_chunk_program(topo_2x2, p_len)
    flash = [l for l in calls if l.startswith("%dsa_flash_prefill")]
    assert len(flash) == c.num_layers == 5, calls
    assert len(calls) == c.num_layers + len(c.index_layers), calls
    assert "f32[16,2048,2048]" not in hlo
    # ... and the search does find the parent's, by its line in the ledger
    was = "%fusion.431 = f32[16,2048,2048]{2,1,0:T(8,128)} fusion(%bitcast.9, %p.1), kind=kLoop"
    assert "f32[16,2048,2048]" in was


def test_latent_dense_programs_fit(topo_2x2):
    """``longread``'s two step programs for one v5e chip at A.X-K1's widths
    (11.1 GB of weights, 1.7 GB of pool): the prefill chunk over the longest
    prompt buffer attends through ``latent_flash_prefill`` once a layer, is
    handed no mask and leaves no score matrix in the program; the decode
    chunk at 8 slots carries the pool pair in place (aliased to its
    outputs), reads the latent pool through ``latent_flash_decode`` once a
    layer, and holds no copy of the pool and no gather of the table's
    extent. The index-key pool of a model that owns no indexer is empty."""
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime.platform import force_mosaic

    model, params, cfg = _abstract_latent_sparse(topo_2x2.devices[:1], "a.x-k1-ep16-d5")
    c, sv = model.config, cfg["serving"]
    slots, rows, bs = int(sv["slots"]), int(sv["prefill_chunk"]), int(sv["block_size"])
    max_blocks = -(-int(sv["max_len"]) // bs)
    p_len = 32768
    dt = jnp.dtype(c.dtype)
    with force_mosaic():
        eng = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
        rep = model.ctx.replicated()
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
        buf = lambda layers, width: jax.ShapeDtypeStruct(
            (layers, 1, 1, p_len, width), dt, sharding=eng._kv_sharding)
        pool = lambda layers, width: jax.ShapeDtypeStruct(
            (layers, slots * max_blocks + 1, 1, bs, width), dt, sharding=eng._pool_sharding)
        pk, pv = pool(c.num_layers, c.cache_row), pool(0, c.index_head_dim)
        assert _nbytes(pv) == 0 and abs(_nbytes(pk) - cfg["bytes"]["pool_bytes"]) < 2 * bs * 6400
        weights = sum(_nbytes(x) for x in jax.tree.leaves(params))
        assert abs(weights - cfg["bytes"]["weight_bytes"]) < 1e6, weights
        chunk, held = _compile(eng._prefill_chunk_prog.lower(
            params, i32(1, rows), buf(c.num_layers, c.cache_row), buf(0, c.index_head_dim),
            i32(), i32()), kernels=("latent_flash_prefill",))
        assert held + _nbytes(pk) < HBM_BYTES, held
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        step, held = _compile(eng._decode_chunk_paged.lower(
            params, (), i32(slots), pk, pv, i32(slots, max_blocks), i32(slots), i32(slots),
            int(sv["chunk"]), key), kernels=("latent_flash_decode",))
    assert held < HBM_BYTES, held
    assert step.memory_analysis().alias_size_in_bytes >= _nbytes(pk)
    for compiled, name in ((chunk, "latent_flash_prefill"), (step, "latent_flash_decode")):
        calls = [l.split("=")[0] for l in compiled.as_text().splitlines()
                 if "tpu_custom_call" in l]
        assert len(calls) == c.num_layers and all(f"%{name}" in l for l in calls), calls
    hlo = chunk.as_text()
    assert "f32[16,2048,2048]" not in hlo and f"pred[{rows},{p_len}]" not in hlo
    assert f"s8[{rows},{p_len}]" not in hlo
    hlo = step.as_text()
    assert _pool_sized_copies(hlo, pk) == []
    for gone in (f"bf16[{slots},{max_blocks * bs},", f"f32[{slots},{c.num_heads},{max_blocks * bs}]"):
        assert gone not in hlo, gone


# ---------------------------------------------------------------------------
# The third configuration's programs, whole and at its published widths
# ---------------------------------------------------------------------------


def _abstract_hybrid_ssm(devices):
    """(model, params, configuration file) of ``phi-4-mini-flash`` over one
    described chip, the parameters as shapes."""
    import json
    import sys

    from triton_dist_tpu.models import HybridSSMLLM
    from triton_dist_tpu.models.hybrid_ssm import SCAN_F32, layer_fixed, layer_tensors
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.build.phi4flash import model_config

    with open(os.path.join(root, "benchmark/configs/phi-4-mini-flash.json")) as f:
        cfg = json.load(f)
    c = model_config(cfg)
    ctx = initialize_distributed(devices=list(devices), axis_names=("tp",), set_default=False)
    dt = jnp.dtype(c.dtype)
    sds = lambda shape, dtype=dt: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=ctx.replicated())
    params = {"embed": sds((c.vocab_size, c.hidden_size)), "final_w": sds((c.hidden_size,)),
              "final_b": sds((c.hidden_size,)), "layers": []}
    for layer in range(c.num_layers):
        shapes = [(n, s) for n, s, _ in layer_tensors(c, layer)] + [
            (n, s) for n, (s, _) in layer_fixed(c, layer).items()]
        params["layers"].append(
            {n: sds(s, jnp.float32 if n in SCAN_F32 else dt) for n, s in shapes})
    return HybridSSMLLM(c, ctx, params=params), params, cfg


def test_hybrid_ssm_programs_fit_whole(topo_2x2):
    """``reason``'s programs for one v5e chip, the model whole (32 layers,
    7.7 GB of weights): the prefill chunk over each of the mix's prompt
    buffers, with one slot's state carried in and out and the scan in its
    kernel once a Mamba layer, and the decode chunk at 32 slots, which
    carries the pool pair AND the slots' state in place (both aliased to
    its outputs), holds no copy of a ring's or the pool's shape beside them
    and reads the pool through ``shared_kv_decode`` once a reading layer."""
    from triton_dist_tpu.models.engine import Engine
    from triton_dist_tpu.runtime.platform import force_mosaic

    model, params, cfg = _abstract_hybrid_ssm(topo_2x2.devices[:1])
    c, sv = model.config, cfg["serving"]
    slots, rows, bs = int(sv["slots"]), int(sv["prefill_chunk"]), int(sv["block_size"])
    max_blocks = -(-int(sv["max_len"]) // bs)
    with force_mosaic():
        eng = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
        rep = model.ctx.replicated()
        shaped = lambda tree: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
        pool = jax.ShapeDtypeStruct(
            (1, slots * max_blocks + 1, 1, bs, c.num_kv_heads * c.head_dim), jnp.dtype(c.dtype),
            sharding=eng._pool_sharding)
        state = shaped(jax.eval_shape(lambda: model.slot_state(slots)))
        resident = 2 * _nbytes(pool) + sum(_nbytes(x) for x in jax.tree.leaves(state))
        assert resident == cfg["bytes"]["pool"] + cfg["bytes"]["slot_state"]
        for p_len in (512, 1024, 1536):
            buf = jax.ShapeDtypeStruct((1, 1, 1, p_len, c.num_kv_heads * c.head_dim),
                                       jnp.dtype(c.dtype), sharding=eng._kv_sharding)
            one = shaped(jax.eval_shape(lambda: model.slot_state(1)))
            compiled, held = _compile(eng._prefill_chunk_prog.lower(
                params, i32(1, rows), buf, buf, i32(), i32(), one), kernels=("ssm_scan",))
            assert held + resident < HBM_BYTES, (p_len, held)
            calls = [l for l in compiled.as_text().splitlines() if "tpu_custom_call" in l]
            assert sum("%ssm_scan" in l.split("=")[0] for l in calls) == len(
                c.layers_of("mamba")), calls
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        chunk, held = _compile(eng._decode_chunk_paged.lower(
            params, (), i32(slots), pool, pool, i32(slots, max_blocks), i32(slots), i32(slots),
            int(sv["chunk"]), key, state), kernels=("shared_kv_decode",))
    assert held < HBM_BYTES, held
    assert chunk.memory_analysis().alias_size_in_bytes >= resident
    ring = state["ring_k"][0]
    hlo = chunk.as_text()
    assert _pool_sized_copies(hlo, jax.ShapeDtypeStruct((1,) + ring.shape, ring.dtype)) == []
    # the full layer's K/V is read where it lies, by the eight layers that
    # read it: no copy of the pool beside the kernel's operand, no gather of
    # the table's whole extent, none of the dense passes over it
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    assert sum("%shared_kv_decode" in l.split("=")[0] for l in calls) == 1 + len(
        c.layers_of("cross")), calls
    assert _pool_sized_copies(hlo, pool) == []
    extent = slots * max_blocks
    for gone in (f"bf16[{extent},{bs},", f"f32[{slots},{c.num_q_heads},{max_blocks * bs}]",
                 f"bf16[{slots},{max_blocks * bs},"):
        assert gone not in hlo, gone


# ---------------------------------------------------------------------------
# The fourth configuration's programs, at its published widths and its cut
# ---------------------------------------------------------------------------


def test_sparse_linear_programs_fit(topo_2x2):
    """``longqa``'s programs for one v5e chip at layers 9-20 (7.86 GB of
    weights): the prefill chunk over the longest prompt buffer, with one
    slot's state carried in and out, the lightning kernel once a lightning
    layer and the selection and the attend once a sparse layer; the decode
    chunk at 8 slots, which carries the pool pair AND the slots' state in
    place (both aliased to its outputs) and reads the pool through
    ``bsa_decode`` once a sparse layer, never gathered at the table's
    extent."""
    import json
    import sys

    from triton_dist_tpu.models import Engine, SparseLinearLLM
    from triton_dist_tpu.models.sparse_linear import layer_ones, layer_tensors
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.runtime.platform import force_mosaic

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.build.minicpm_sala import model_config

    with open(os.path.join(root, "benchmark/configs/minicpm-sala-d12.json")) as f:
        cfg = json.load(f)
    c, sv = model_config(cfg), cfg["serving"]
    ctx = initialize_distributed(devices=list(topo_2x2.devices[:1]), axis_names=("tp",),
                                 set_default=False)
    rep = ctx.replicated()
    dt = jnp.dtype(c.dtype)
    sds = lambda shape, dtype=dt: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=rep)
    params = {"embed": sds((c.vocab_size, c.hidden_size)),
              "head": sds((c.hidden_size, c.vocab_size)),
              "final_norm": sds((c.hidden_size,)), "layers": []}
    for layer in range(c.num_layers):
        lp = {n: sds(shape) for n, shape in layer_tensors(c, layer)}
        lp.update({n: sds((k,)) for n, k in layer_ones(c, layer)})
        params["layers"].append(lp)
    model = SparseLinearLLM(c, ctx, params=params)
    slots, rows, bs = int(sv["slots"]), int(sv["prefill_chunk"]), int(sv["block_size"])
    max_blocks = -(-int(sv["max_len"]) // bs)
    n_sparse, n_lin = len(c.layers_of("sparse")), len(c.layers_of("lightning"))
    width = c.num_kv_heads * c.head_dim
    with force_mosaic():
        eng = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
        shaped = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
        i32 = lambda *shape: sds(shape, jnp.int32)
        pool = jax.ShapeDtypeStruct((n_sparse, slots * max_blocks + 1, 1, bs, width), dt,
                                    sharding=eng._pool_sharding)
        state = shaped(jax.eval_shape(lambda: model.slot_state(slots)))
        resident = 2 * _nbytes(pool) + sum(_nbytes(x) for x in jax.tree.leaves(state))
        assert resident == cfg["bytes"]["pool"] + cfg["bytes"]["slot_state"]
        buf = jax.ShapeDtypeStruct((n_sparse, 1, 1, 16384, width), dt, sharding=eng._kv_sharding)
        one = shaped(jax.eval_shape(lambda: model.slot_state(1)))
        compiled, held = _compile(eng._prefill_chunk_prog.lower(
            params, i32(1, rows), buf, buf, i32(), i32(), one),
            kernels=("lightning_chunk", "bsa_select", "bsa_prefill"))
        assert held + resident < HBM_BYTES, held
        calls = [l.split("=")[0] for l in compiled.as_text().splitlines()
                 if "tpu_custom_call" in l]
        count = lambda name: sum(f"%{name}." in l or l.strip().endswith(f"%{name} ")
                                 for l in calls)
        assert (count("lightning_chunk"), count("bsa_select"), count("bsa_prefill")) == (
            n_lin, n_sparse, n_sparse), calls
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        chunk, held = _compile(eng._decode_chunk_paged.lower(
            params, (), i32(slots), pool, pool, i32(slots, max_blocks), i32(slots), i32(slots),
            int(sv["chunk"]), key, state), kernels=("bsa_decode",))
    assert held < HBM_BYTES, held
    assert chunk.memory_analysis().alias_size_in_bytes >= resident
    hlo = chunk.as_text()
    calls = [l.split("=")[0] for l in hlo.splitlines() if "tpu_custom_call" in l]
    assert sum("%bsa_decode" in l for l in calls) == n_sparse, calls
    assert _pool_sized_copies(hlo, pool) == []
    # no gather of a slot's whole table: nothing of (slots, extent, row) or (slots x pages, page, row)
    for gone in (f"bf16[{slots},{max_blocks * bs},", f"bf16[{slots * max_blocks},{bs},"):
        assert gone not in hlo, gone
