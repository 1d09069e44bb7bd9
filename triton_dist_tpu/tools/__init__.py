"""Tooling layer: autotuner + tune cache, timing, profiler, perf models.

Reference: ``python/triton_dist/{autotuner,tune}.py`` and
``python/triton_dist/tools/`` (AOT compiler, intra-kernel profiler, offline
GEMM tuner). TPU redesign notes:

* The reference's *contextual* autotuner re-runs the whole distributed op so
  ``triton.autotune`` candidates get timed collectively, allreducing timings
  across ranks (``autotuner.py:43-250``). Our runtime is single-controller
  (one process drives every device in the mesh), so host wall-clock around a
  jitted sharded op *is* the collective time — candidates are timed whole-op
  with no cross-rank reduction needed.
* Tuning can't happen under ``jit`` tracing (configs are static Python), so
  tuning is offline: ``autotune()`` measures candidates eagerly and persists
  the winner in a JSON cache keyed by op/shape/dtype/device-kind
  (reference ``tune.py:175-255``); hot paths read the cache via
  ``lookup()``/``gemm_config_for()`` at trace time.
"""

from triton_dist_tpu.tools.timing import bench_device_time
from triton_dist_tpu.tools.tune import TuneCache, autotune, lookup, default_cache
from triton_dist_tpu.tools.perf_model import (
    ChipSpec,
    chip_spec,
    gemm_time_s,
    attention_time_s,
    allgather_time_s,
    reduce_scatter_time_s,
    allreduce_time_s,
    all_to_all_time_s,
    overlap_fraction,
    overlap_efficiency,
)
from triton_dist_tpu.tools.profiler import (
    TRACE_TAGS,
    ChromeTrace,
    KernelTrace,
    decode_to_chrome,
    profile_op,
    trace,
)
from triton_dist_tpu.tools.xplane import (
    overlap_ps,
    overlap_report,
    parse_xspace,
    select_events,
)

__all__ = [
    "KernelTrace",
    "bench_device_time",
    "TuneCache",
    "autotune",
    "lookup",
    "default_cache",
    "ChipSpec",
    "chip_spec",
    "gemm_time_s",
    "attention_time_s",
    "allgather_time_s",
    "reduce_scatter_time_s",
    "allreduce_time_s",
    "all_to_all_time_s",
    "overlap_fraction",
    "overlap_efficiency",
    "ChromeTrace",
    "TRACE_TAGS",
    "decode_to_chrome",
    "profile_op",
    "trace",
    "parse_xspace",
    "select_events",
    "overlap_ps",
    "overlap_report",
]
