"""Ring-overlap schedule evidence (r4 verdict item 4).

The 1D/2D ring attentions claim their ``ppermute`` hops ride under the
in-flight flash step (2D: the DCN superblock hop rides under a whole ICI
ring). On TPU, XLA's latency-hiding scheduler converts a collective into an
async ``collective-permute-start/done`` pair hoisted across compute exactly
when the dataflow permits it — i.e. when the permute's operands do not
depend on that compute. The CPU backend lowers the same program to
synchronous ``collective-permute`` (verified here), so the chip-free,
XLA-version-stable form of the overlap claim is the dataflow property
itself: **no ring hop ever consumes a value produced (even transitively) by
a flash kernel call**. These tests walk the jaxpr and enforce that; a
negative control proves the walker actually catches a serialized ring.

The scheduled-module form of the same claim (async pairs bracketing the
flash custom-call) needs a multi-chip compile and lives with the other
deviceless compiles (``tests/test_tpu_lowering.py``).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.kernels.sp import (
    ring_attention_2d_shard,
    ring_attention_shard,
)

FLASH_PRIMS = {"pallas_call"}
HOP_PRIMS = {"ppermute"}
# Higher-order primitives whose sub-jaxpr we walk with operand alignment.
_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _taint_walk(closed_jaxpr):
    """Walk a (closed) jaxpr in topological order, propagating a "depends on
    a flash kernel output" taint. Returns (violations, n_hops, n_flash):
    ``violations`` lists every ring-hop eqn consuming a tainted operand —
    the dataflow evidence that a hop would WAIT on compute."""
    violations = []
    counts = {"hops": 0, "flash": 0}
    fresh = itertools.count()

    def walk(jaxpr, in_taints, const_taints=None):
        taint = {}
        for v, t in zip(jaxpr.invars, in_taints):
            taint[v] = t
        for v in jaxpr.constvars:
            taint[v] = False if const_taints is None else const_taints.get(v, False)

        def tof(v):
            return (False if isinstance(v, jax.extend.core.Literal)
                    else taint.get(v, False))

        for eqn in jaxpr.eqns:
            ins = [tof(v) for v in eqn.invars]
            name = eqn.primitive.name
            sub = None
            for p in _SUBJAXPR_PARAMS:
                if p in eqn.params:
                    sub = eqn.params[p]
                    break
            if name in HOP_PRIMS:
                counts["hops"] += 1
                if any(ins):
                    violations.append(name)
                outs = [any(ins)] * len(eqn.outvars)
            elif name in FLASH_PRIMS:
                counts["flash"] += 1
                outs = [True] * len(eqn.outvars)
            elif sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                if len(inner.invars) == len(ins):
                    outs = walk(inner, ins)
                else:  # custom-vjp style: the LEADING k eqn invars are
                    # consts (JAX packs them first); keep the trailing
                    # taints, which align with the inner jaxpr's invars
                    k = len(ins) - len(inner.invars)
                    outs = walk(inner, ins[k:])
                outs = list(outs)[: len(eqn.outvars)]
                outs += [any(ins)] * (len(eqn.outvars) - len(outs))
            else:  # ordinary op: taint flows through
                outs = [any(ins)] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, outs):
                taint[v] = t
        return [tof(v) for v in jaxpr.outvars]

    jxp = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    walk(jxp, [False] * len(jxp.invars))
    return violations, counts["hops"], counts["flash"]


def _mesh_axes(mesh):
    return {n: s for n, s in zip(mesh.axis_names, mesh.devices.shape)}


def test_ring_1d_hops_never_wait_on_flash(ctx4):
    """Every KV hop of the 1D ring consumes only the permute chain — the
    dataflow XLA's TPU scheduler needs to hoist each hop under the
    in-flight flash step."""
    b, hq, hkv, s_loc, d = 1, 4, 2, 64, 32

    def body(q, k, v):
        return ring_attention_shard(q, k, v, axis="tp", causal=True,
                                    block_q=64, block_k=64)

    f = jax.shard_map(
        body, mesh=ctx4.mesh, in_specs=(P(None, None, "tp"),) * 3,
        out_specs=P(None, None, "tp"), check_vma=False)
    world = 4
    s = world * s_loc
    args = [jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)
            for h in (hq, hkv, hkv)]
    jaxpr = jax.make_jaxpr(f)(*args)
    violations, hops, flash = _taint_walk(jaxpr)
    assert flash == world, (flash, world)  # one flash call per ring step
    assert hops == 2 * (world - 1), hops  # k and v, world-1 hops each
    assert violations == [], (
        f"{len(violations)} ring hops data-depend on flash output — "
        "the overlap the ring claims is impossible")


def test_ring_2d_hops_never_wait_on_flash(ctx24):
    """Two-level ring: the DCN superblock hops AND the ICI hops all consume
    only permute-chain values — in particular the early-issued outer hop of
    phase t+1 cannot wait on phase t's flash calls."""
    wo, wi = 2, 4
    b, hq, hkv, s_loc, d = 1, 4, 2, 32, 32

    def body(q, k, v):
        return ring_attention_2d_shard(q, k, v, axes=("dp", "tp"),
                                       causal=True, block_q=32, block_k=32)

    f = jax.shard_map(
        body, mesh=ctx24.mesh, in_specs=(P(None, None, ("dp", "tp")),) * 3,
        out_specs=P(None, None, ("dp", "tp")), check_vma=False)
    s = wo * wi * s_loc
    args = [jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)
            for h in (hq, hkv, hkv)]
    jaxpr = jax.make_jaxpr(f)(*args)
    violations, hops, flash = _taint_walk(jaxpr)
    assert flash == wo * wi, (flash, wo * wi)
    # k and v each: (wo-1) outer hops + wo·(wi-1) inner hops.
    assert hops == 2 * ((wo - 1) + wo * (wi - 1)), hops
    assert violations == [], (
        f"{len(violations)} hops data-depend on flash output")


def test_walker_catches_serialized_ring(ctx4):
    """Negative control: a deliberately serialized ring (each hop perturbed
    by the step's flash output, so the permute MUST wait for compute) is
    flagged — the overlap test fails when the overlap disappears."""
    from triton_dist_tpu.kernels.flash_attn import flash_attention

    world, b, hq, hkv, s_loc, d = 4, 1, 4, 2, 64, 32

    def serialized(q, k, v):
        perm = [(i, (i + 1) % world) for i in range(world)]
        k_cur, v_cur = k, v
        o = None
        for step in range(world):
            o_step = flash_attention(q, k_cur, v_cur, causal=False,
                                     block_q=64, block_k=64)
            o = o_step if o is None else o + o_step
            if step + 1 < world:
                # The 0·sum(o) term is numerically nothing but makes the
                # hop data-depend on this step's flash — serialization.
                k_cur = jax.lax.ppermute(
                    k_cur + 0.0 * jnp.sum(o), "tp", perm)
                v_cur = jax.lax.ppermute(v_cur, "tp", perm)
        return o

    f = jax.shard_map(
        serialized, mesh=ctx4.mesh, in_specs=(P(None, None, "tp"),) * 3,
        out_specs=P(None, None, "tp"), check_vma=False)
    s = world * s_loc
    args = [jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)
            for h in (hq, hkv, hkv)]
    jaxpr = jax.make_jaxpr(f)(*args)
    violations, hops, flash = _taint_walk(jaxpr)
    assert flash == world
    assert len(violations) == world - 1, (
        "the serialized k-hops must ALL be flagged", violations)


def test_cpu_backend_lowers_hops_synchronously(ctx4):
    """Documents WHY the schedule assertion is dataflow-level: the CPU
    backend emits synchronous ``collective-permute`` (no start/done pairs),
    so async bracketing is only observable in a TPU compile. If this ever
    starts failing because CPU gained async pairs, the scheduled-module
    assertion can move here."""
    world = 4

    def body(x):
        perm = [(i, (i + 1) % world) for i in range(world)]
        return jax.lax.ppermute(jnp.tanh(x), "tp", perm)

    f = jax.jit(jax.shard_map(
        body, mesh=ctx4.mesh, in_specs=(P("tp"),), out_specs=P("tp"),
        check_vma=False))
    txt = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile().as_text()
    assert "collective-permute" in txt
    assert "collective-permute-start" not in txt
