#!/usr/bin/env python3
"""chip_smoke.py — does the system still start, serve and agree on the chip?

One process on the attached TPU. With no arguments, on ONE chip:

1. *serve/dist*   ``DenseLLM`` at the published Qwen3-8B widths (bfloat16,
   seeded random weights, depth cut to what fits 16 GB beside the pool),
   ``Engine(backend="dist")`` under ``InferenceServer`` on the paged pool:
   more requests than slots, prompts of different lengths, driven to
   completion twice — a warm-up pass that compiles, then a pass that must
   compile nothing, finish every request normally with the token count asked
   for, repeat the warm-up's greedy streams byte for byte, and leave every
   fallback / degradation / recovery counter at zero.
2. *compare/dist-xla*  prefill logits and one paged decode step's logits from
   ``backend="xla"`` on the same weights, against the ``dist`` ones.
3. *serve/mega* + *compare/mega-xla*  the same on the megakernel backend, at
   the depth its second resident copy of the layer weights allows.

``--four-chips`` runs only the path that needs four: the full 36-layer
preset with TP=4 on one mesh, placement bytes per device, *serve/dist* (fused
GEMM-AR ring prefill chunks, one-shot GEMM-AR decode over ICI), the one-shot
``Engine.serve`` prefill (fused AG-GEMM / GEMM-RS), and the same logits
against ``xla`` (XLA collectives) on the same sharded weights.

One JSON object per phase on the earlier lines (times are set-up facts, not
metrics); the last line is ``{"ok": true, "device": {...}}``. Anything that
raises ends the run non-zero: nothing here catches a phase. With no TPU the
script exits 1 before building anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run serves. ``QWEN3_8B`` is the chip's; the CPU rehearsal
    (tests/test_chip_smoke.py) passes a toy of the same structure."""

    preset: str
    depth: int  # layers of the served model
    mega_depth: int  # layers the mega phase can hold twice
    max_len: int
    num_slots: int
    chunk: int
    #: (prompt tokens, new tokens) — more requests than slots, so that
    #: join-on-free-slot runs; lengths both aligned and ragged.
    requests: tuple[tuple[int, int], ...]
    #: Prompt lengths whose logits are compared across backends.
    compare_lens: tuple[int, ...]
    #: One-shot ``Engine.serve`` prompt length (four-chip path; must divide
    #: over the mesh for the sequence-sharded ``dist`` prefill).
    oneshot_len: int
    #: Max |logit difference| allowed between two backends on the same
    #: weights. Logits of these random models have unit-order spread; two
    #: bfloat16 backends with different kernels and reduction orders land
    #: within a few hundredths of each other, a wrong kernel is off by ~1.
    tol: float


# Memory at Qwen3-8B widths, bfloat16 (tests/test_tpu_lowering.py compiles
# these programs for a described v5e and holds them to 16 GB): 0.36 GiB a
# layer, 2.32 GiB embedding + head, 4 KiB of K/V a token a layer. At 24
# layers and 4 slots of 2048 tokens: weights 10.9 GiB, pool 0.75 GiB, which the
# paged decode reads and writes in place. The mega backend keeps every layer's
# weights twice, so it runs 12.
QWEN3_8B = Sizes(
    preset="qwen3-8b", depth=24, mega_depth=12, max_len=2048, num_slots=4,
    chunk=8,
    requests=((64, 32), (1500, 48), (512, 64), (300, 32), (1024, 40),
              (100, 56)),
    compare_lens=(64, 1500), oneshot_len=1024, tol=0.25,
)
#: The four-chip path: every layer of the preset, TP=4.
QWEN3_8B_TP4 = dataclasses.replace(QWEN3_8B, depth=36)

SEED = 0

#: Counters that must read zero after a healthy run.
ZERO_COUNTERS = (
    "tdt_engine_fallbacks_total",
    "tdt_serving_recoveries_total",
    "tdt_serving_recovery_retries_total",
    "tdt_serving_preemptions_total",
    "tdt_serving_restores_total",
    "tdt_resilience_watchdog_timeouts_total",
)


def lowerings() -> float:
    """Jit cache misses so far, as the program counts them from its first
    engine's build (each one traces and lowers a program, whether or not the
    persistent cache then spares the backend compile)."""
    from triton_dist_tpu.runtime import telemetry

    return telemetry.counter_total("tdt_jit_lowerings_total")


def emit(phase: str, **fields) -> None:
    dev = jax.devices()[0]
    print(json.dumps({
        "phase": phase, "platform": dev.platform,
        "device_kind": dev.device_kind, "device_count": len(jax.devices()),
        **fields,
    }), flush=True)


def memory_stat(devices, key: str) -> list[int | None]:
    """``key`` of every device's ``memory_stats()`` (None where the backend
    reports none: the CPU rehearsal)."""
    stats = [d.memory_stats() for d in devices]
    return [s.get(key) if s else None for s in stats]


def counters() -> dict[str, float]:
    from triton_dist_tpu.runtime import telemetry

    return {name: telemetry.counter_total(name) for name in ZERO_COUNTERS}


def build_model(sizes: Sizes, depth: int, devices):
    """Seeded model of ``depth`` layers at the preset's widths, created on a
    TP mesh over ``devices``. Returns (model, seconds)."""
    from triton_dist_tpu.models import PRESETS, DenseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed

    t0 = time.perf_counter()
    ctx = initialize_distributed(
        devices=list(devices), axis_names=("tp",), set_default=False
    )
    config = dataclasses.replace(PRESETS[sizes.preset], num_layers=depth)
    model = DenseLLM(config, ctx, key=jax.random.PRNGKey(SEED))
    jax.block_until_ready(model.params)
    return model, time.perf_counter() - t0


def make_prompts(sizes: Sizes, vocab: int, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n, _ in sizes.requests]


def serve_once(engine, sizes: Sizes, prompts):
    """One server's whole life on ``engine``: submit every request, drive
    ``run()`` to completion, shut down. Checks that every request finished
    as asked; returns (token streams, seconds in run(), programs lowered
    during run())."""
    from triton_dist_tpu.serving import InferenceServer, RequestState

    server = InferenceServer(
        engine, num_slots=sizes.num_slots, chunk=sizes.chunk
    )
    reqs = [
        server.submit(p, max_new)
        for p, (_, max_new) in zip(prompts, sizes.requests)
    ]
    before = lowerings()
    t0 = time.perf_counter()
    server.run()
    seconds = time.perf_counter() - t0
    lowered = int(lowerings() - before)
    server.shutdown()
    for req, (n_prompt, max_new) in zip(reqs, sizes.requests):
        if (req.state is not RequestState.DONE or req.finish_reason != "ok"
                or req.reject_reason is not None):
            raise AssertionError(
                f"request {req.req_id} ({n_prompt} prompt tokens): state "
                f"{req.state}, finish {req.finish_reason!r}, reject "
                f"{req.reject_reason!r}"
            )
        if len(req.tokens) != max_new:
            raise AssertionError(
                f"request {req.req_id}: {len(req.tokens)} tokens out, "
                f"{max_new} asked"
            )
    return [list(r.tokens) for r in reqs], seconds, lowered


def check_healthy(engine) -> dict[str, float]:
    """Nothing fell back, degraded or recovered behind the results."""
    from triton_dist_tpu.runtime import resilience

    if engine.backend != engine.preferred_backend:
        raise AssertionError(
            f"engine ended on {engine.backend}, asked for "
            f"{engine.preferred_backend}"
        )
    if resilience.any_degraded():
        raise AssertionError(f"degraded: {resilience.degraded_reasons()}")
    counts = counters()
    if any(counts.values()):
        raise AssertionError(f"fallback/recovery counters moved: {counts}")
    return counts


def serve_phase(model, backend: str, sizes: Sizes):
    """Build the engine and serve the requests twice: a warm-up pass that
    compiles every shape the server declares (one prefill program a prompt
    length, one decode program a chunk size), then the same requests on a
    fresh server, held to the contract. Returns the engine."""
    from triton_dist_tpu.models import Engine

    vocab = model.config.vocab_size
    devices = list(model.ctx.mesh.devices.flat)
    prompts = make_prompts(sizes, vocab, SEED + 1)
    t0 = time.perf_counter()
    engine = Engine(model, backend=backend, max_len=sizes.max_len)
    warm, _, lowered_warm = serve_once(engine, sizes, prompts)
    build_s = time.perf_counter() - t0
    if not lowered_warm:
        # A new engine's programs always lower once. None counted means the
        # program's counter is not live (TDT_TELEMETRY=0), and the check
        # below would pass blind.
        raise AssertionError(
            f"no program lowered in the warm-up on {backend}: "
            "tdt_jit_lowerings_total is not counting (is telemetry off?)"
        )
    streams, serve_s, lowered = serve_once(engine, sizes, prompts)
    if lowered:
        raise AssertionError(
            f"{lowered} program(s) lowered after warm-up on {backend}"
        )
    if streams != warm:
        raise AssertionError(
            f"two runs of backend {backend} gave different greedy streams"
        )
    counts = check_healthy(engine)
    emit(
        f"serve/{backend}", preset=sizes.preset, depth=model.config.num_layers,
        tp=len(devices), hidden=model.config.hidden_size,
        vocab=vocab, dtype=model.config.dtype, slots=sizes.num_slots,
        max_len=sizes.max_len, requests=len(sizes.requests),
        build_seconds=round(build_s, 2), serve_seconds=round(serve_s, 2),
        tokens_out=sum(len(t) for t in streams),
        lowerings_in_warmup=lowered_warm, lowerings_after_warmup=lowered,
        counters=counts,
        peak_bytes_in_use=memory_stat(devices, "peak_bytes_in_use"),
    )
    return engine


def paged_logits(engine, ids: list[int], block_size: int = 16):
    """(prefill logits, one decode step's logits) for one prompt through the
    engine's paged serving programs: chunked prefill into a context buffer,
    scatter into a one-slot block pool, one decode step over the pool."""
    p_len = len(ids)
    max_blocks = -(-engine.max_len // block_size)
    paged = engine.alloc_paged(
        1, block_size=block_size, num_blocks=max_blocks + 1
    )
    kbuf, vbuf = engine.paged_kbuf_zeros(p_len)
    logits_p, kbuf, vbuf = engine.prefill_chunk(
        kbuf, vbuf, jnp.asarray([ids], jnp.int32), 0, p_len - 1
    )
    table = np.arange(1, max_blocks + 1, dtype=np.int32)
    paged = engine.complete_paged_prefill(paged, kbuf, vbuf, table, 0)
    paged = dataclasses.replace(
        paged, tables=jnp.asarray(table[None]),
        lengths=jnp.asarray([p_len], jnp.int32),
    )
    token = jnp.argmax(logits_p, axis=-1).astype(jnp.int32)
    logits_d = engine.decode_logits_paged(paged, token)
    return np.asarray(logits_p, np.float32), np.asarray(logits_d, np.float32)


def check_logits(name: str, got: np.ndarray, ref: np.ndarray, tol: float):
    if got.shape != ref.shape or not np.isfinite(got).all():
        raise AssertionError(f"{name}: shape {got.shape} vs {ref.shape}, "
                             f"finite={np.isfinite(got).all()}")
    diff = float(np.abs(got - ref).max())
    if diff > tol:
        raise AssertionError(f"{name}: max |logit difference| {diff:.4f} "
                             f"over tolerance {tol}")
    return diff


def compare_phase(engine, model, sizes: Sizes, *, oneshot: bool = False):
    """Hold ``engine``'s logits to ``backend="xla"`` on the same weights."""
    from triton_dist_tpu.models import Engine

    t0 = time.perf_counter()
    ref = Engine(model, backend="xla", max_len=sizes.max_len)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(SEED + 3)
    diffs, agree = {}, []
    for n in sizes.compare_lens:
        ids = rng.integers(0, vocab, size=n).tolist()
        got_p, got_d = paged_logits(engine, ids)
        ref_p, ref_d = paged_logits(ref, ids)
        diffs[f"prefill_{n}"] = check_logits(
            f"prefill logits, {n} tokens", got_p, ref_p, sizes.tol)
        diffs[f"decode_{n}"] = check_logits(
            f"decode logits after {n} tokens", got_d, ref_d, sizes.tol)
        agree += [bool((got_p.argmax(-1) == ref_p.argmax(-1)).all()),
                  bool((got_d.argmax(-1) == ref_d.argmax(-1)).all())]
    if oneshot:
        # The one-shot prefill program (Engine.serve's): on a TP mesh the
        # dist backend runs it sequence-sharded through the fused AG-GEMM /
        # GEMM-RS kernels, which the chunked serving prefill never reaches.
        ids = jnp.asarray(
            [rng.integers(0, vocab, size=sizes.oneshot_len)], jnp.int32)
        got = np.asarray(engine._prefill(model.params, ids)[0], np.float32)
        want = np.asarray(ref._prefill(model.params, ids)[0], np.float32)
        diffs[f"oneshot_prefill_{sizes.oneshot_len}"] = check_logits(
            "one-shot prefill logits", got, want, sizes.tol)
        toks = np.asarray(engine.serve(ids, 8))
        if toks.shape != (1, 8) or (toks < 0).any() or (toks >= vocab).any():
            raise AssertionError(f"Engine.serve gave {toks!r}")
    counts = check_healthy(engine)
    emit(
        f"compare/{engine.backend}-xla", depth=model.config.num_layers,
        tolerance=sizes.tol,
        max_abs_logit_diff={k: round(v, 5) for k, v in diffs.items()},
        argmax_agree=f"{sum(agree)}/{len(agree)}",
        seconds=round(time.perf_counter() - t0, 2), counters=counts,
        peak_bytes_in_use=memory_stat(
            list(model.ctx.mesh.devices.flat), "peak_bytes_in_use"),
    )


def one_chip(devices, sizes: Sizes) -> None:
    model, init_s = build_model(sizes, sizes.depth, devices)
    emit("init", depth=sizes.depth, seconds=round(init_s, 2),
         bytes_in_use=memory_stat(devices, "bytes_in_use"))
    engine = serve_phase(model, "dist", sizes)
    compare_phase(engine, model, sizes)
    # The mega backend holds the layer weights twice: make room first. An
    # engine and its jitted closures refer to each other, so the weights go
    # only when the collector has run.
    del engine, model
    gc.collect()
    model, init_s = build_model(sizes, sizes.mega_depth, devices)
    emit("init", depth=sizes.mega_depth, seconds=round(init_s, 2),
         bytes_in_use=memory_stat(devices, "bytes_in_use"))
    engine = serve_phase(model, "mega", sizes)
    compare_phase(engine, model, sizes)


def four_chips(devices, sizes: Sizes) -> None:
    model, init_s = build_model(sizes, sizes.depth, devices)
    placed = memory_stat(devices, "bytes_in_use")
    emit("init", depth=sizes.depth, tp=len(devices), seconds=round(init_s, 2),
         bytes_in_use=placed)
    if None not in placed and max(placed) > 1.25 * min(placed):
        raise AssertionError(f"weights not spread evenly: {placed}")
    engine = serve_phase(model, "dist", sizes)
    compare_phase(engine, model, sizes, oneshot=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the TP=4 path of the full 36-layer preset",
    )
    args = parser.parse_args(argv)
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(
            f"chip_smoke: needs {need} TPU device(s), jax reports "
            f"{len(devices)} x {devices[0].platform}", file=sys.stderr,
        )
        return 1
    from triton_dist_tpu.runtime.platform import enable_compile_cache

    enable_compile_cache()
    if args.four_chips:
        four_chips(devices[:4], QWEN3_8B_TP4)
    else:
        one_chip(devices[:1], QWEN3_8B)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
