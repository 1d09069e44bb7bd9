#!/usr/bin/env python
"""Lint: telemetry metric names follow ``tdt_<subsystem>_<name>``.

The registry in ``triton_dist_tpu.runtime.telemetry`` keys metrics by bare
string — nothing structural stops a call site from minting
``my_cool_counter`` or, worse, interpolating a shape into the metric NAME
(unbounded cardinality, the classic Prometheus foot-gun). This lint makes
the convention (see ``docs/observability.md``) machine-enforced:

* the first argument of ``telemetry.inc`` / ``observe`` / ``set_gauge`` /
  ``counter_value`` must be a **string literal** — dynamic metric names are
  rejected outright (dynamic dimensions belong in label VALUES);
* the literal must match ``tdt_<subsystem>_<name>`` — lowercase
  ``[a-z0-9_]``, at least three underscore-separated segments, ``tdt_``
  prefix;
* ``telemetry.emit`` kinds must be literal snake-case strings (the event
  ring is grep'd by kind; a dynamic kind is un-greppable);
* SPAN names (``runtime.tracing``) follow the exact same registry
  discipline: ``tracing.start_trace`` / ``root_span`` / ``point_current`` /
  ``span_current`` and ``<anything>trace<anything>.span`` / ``.record`` / ``.point`` (the
  ``req.trace.span(...)`` call shape) must pass a literal
  ``tdt_<subsystem>_<name>`` — a trace timeline is queried by name just
  like a metric, so span names must not drift from metric names.

Escape hatch: a trailing ``# metric-name-ok: <reason>`` comment on the
offending line — for a call site that genuinely needs to forward a
caller-supplied name (none exist today; keep it that way).

Usage: ``python scripts/check_metric_names.py [paths...]`` (default:
``triton_dist_tpu/`` — which includes the ``serving/`` package and its
``tdt_serving_*`` series — plus ``bench.py`` and ``scripts/``). Exit 1
with ``file:line`` diagnostics on violations. Scans by AST, so aliased
imports (``from ... import telemetry as t``) are caught too, as long as
the module is bound to a name containing ``telemetry``.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_ROOTS = (REPO / "triton_dist_tpu", REPO / "bench.py", REPO / "scripts")

WAIVER = "# metric-name-ok:"

#: Registry entry points whose first argument is a METRIC name.
METRIC_FNS = {"inc", "observe", "set_gauge", "counter_value", "counter_total",
              "observe_digest", "digest_quantile", "digest_merged"}
#: Entry point whose first argument is an event KIND.
EVENT_FNS = {"emit", "events"}
#: Tracing entry points whose first argument is a SPAN name, recognized on
#: receivers whose name mentions trace/tracing (``tracing.start_trace``,
#: ``req.trace.span``, ``self._trace.record``).
TRACING_FNS = {"span", "record", "point", "start_trace", "root_span",
               "point_current", "span_current", "start_remote_trace"}

METRIC_NAME = re.compile(r"^tdt_[a-z0-9]+_[a-z0-9_]+$")
EVENT_KIND = re.compile(r"^[a-z][a-z0-9_]*$")

#: Drift guard for the SLO-guardrail series: docs, dashboards, and the
#: chaos/regression tooling reference these names, so a rename that passes
#: the per-line lint is still a breakage. Enforced only on a default-roots
#: run (explicit paths lint third-party files that owe us nothing).
REQUIRED_NAMES = {
    # shed / deadline / cancel (serving)
    "tdt_serving_shed_total",
    "tdt_serving_cancelled_total",
    "tdt_serving_deadline_expiries_total",
    "tdt_serving_deadline_overrun_seconds",
    # circuit breaker / probe / chaos (resilience)
    "tdt_degrade_state",
    "tdt_resilience_breaker_transitions_total",
    "tdt_resilience_probes_total",
    "tdt_resilience_chaos_injected_total",
    "tdt_mesh_connect_retries_total",
    # rank health / epoch fencing (mesh + resilience)
    "tdt_mesh_epoch",
    "tdt_health_beats_total",
    "tdt_health_deaths_total",
    "tdt_health_rank_alive",
    "tdt_resilience_dead_peer_failfast_total",
    "tdt_resilience_stale_epoch_total",
    # write-ahead journal / crash recovery / shutdown (serving)
    "tdt_serving_journal_records_total",
    "tdt_serving_journal_fsyncs_total",
    "tdt_serving_journal_replayed_total",
    "tdt_serving_journal_replay_seconds",
    "tdt_serving_drain_seconds",
    # paged KV: block pool / prefix reuse / chunked prefill (serving)
    "tdt_kv_blocks_free",
    "tdt_kv_blocks_used",
    "tdt_kv_blocks_shared",
    "tdt_kv_prefix_hits_total",
    "tdt_kv_prefix_blocks_reused_total",
    "tdt_kv_evictions_total",
    "tdt_kv_cow_copies_total",
    "tdt_serving_prefill_chunks",
    "tdt_serving_kv_budget_wait_total",
    # fleet front door: replica router placement / migration / rebuild
    # (fleet/router.py) plus the serving-side drain/resume hooks it drives
    "tdt_fleet_requests_total",
    "tdt_fleet_tokens_total",
    "tdt_fleet_placements_total",
    "tdt_fleet_prefix_hits_total",
    "tdt_fleet_prefix_hit_rate",
    "tdt_fleet_migrations_total",
    "tdt_fleet_replica_failures_total",
    "tdt_fleet_replicas_alive",
    "tdt_fleet_pending_requests",
    "tdt_fleet_rebuilds_total",
    "tdt_serving_resumed_total",
    "tdt_serving_drains_total",
    # expert-parallel MoE: AUTO routing + per-expert load (models/moe.py,
    # kernels/low_latency_a2a.py) — surfaced on /metrics and /requests
    "tdt_ep_auto_route_total",
    "tdt_ep_dispatch_total",
    "tdt_ep_expert_tokens_total",
    "tdt_ep_expert_load",
    "tdt_ep_dropped_tokens_total",
    "tdt_ep_wire_bytes_total",
    # fleet observability: cross-process trace propagation, federation,
    # flight recorder (fleet/router.py, runtime/telemetry.py)
    "tdt_fleet_trace_propagated_total",
    "tdt_fleet_trace_fetches_total",
    "tdt_fleet_http_errors_total",
    "tdt_fleet_postmortems_total",
    "tdt_flight_records_total",
    # gray-failure tolerance: health state machine, wire retries, progress
    # watchdog, supervised respawn (fleet/router.py)
    "tdt_fleet_health_state",
    "tdt_fleet_wire_retries_total",
    "tdt_fleet_stall_migrations_total",
    "tdt_fleet_respawns_total",
    "tdt_fleet_migration_seconds",
    # megakernel serving decode: scheduler + launch shape (megakernel/
    # builder.py, models/engine.py) — the perf path's audit surface
    "tdt_mega_tasks_scheduled_total",
    "tdt_mega_fusion_hits_total",
    "tdt_mega_steps_per_launch",
    "tdt_mega_ready_depth",
    # speculative decoding: drafter proposals vs k-wide verify acceptance
    # (serving/server.py, models/engine.py) — see docs/speculative.md
    "tdt_spec_proposed_total",
    "tdt_spec_accepted_total",
    "tdt_spec_accept_len",
    "tdt_spec_k",
    # elasticity: load-adaptive autoscaler (fleet/router.py)
    "tdt_fleet_scale_events_total",
    "tdt_fleet_scale_demand",
    "tdt_fleet_scale_target_replicas",
    # multi-tenant QoS: per-tenant accounting, WFQ sheds, prefix-cache
    # quotas (fleet/router.py, serving/scheduler.py)
    "tdt_tenant_requests_total",
    "tdt_tenant_pending_requests",
    "tdt_tenant_shed_total",
    "tdt_tenant_prefix_blocks",
    "tdt_tenant_prefix_evictions_total",
    # live SLO engine: per-tenant TTFT/TPOT/e2e digests, goodput vs
    # violation counters, burn-rate alerting, and step-phase profiling
    # (runtime/slo.py, fleet/router.py, models/engine.py) — see
    # docs/observability.md "SLO engine"
    "tdt_slo_ttft_seconds",
    "tdt_slo_tpot_seconds",
    "tdt_slo_e2e_seconds",
    "tdt_slo_goodput_total",
    "tdt_slo_violations_total",
    "tdt_slo_burn_rate",
    "tdt_slo_alerts_total",
    "tdt_engine_phase_seconds",
    # quantization: quantized-operand collective dispatches, wire/operand
    # byte accounting, and the quantized KV pool's real per-block HBM cost
    # (kernels/allgather_gemm.py note_quant_dispatch, serving/server.py) —
    # see docs/quantization.md
    "tdt_quant_ops_total",
    "tdt_quant_operand_bytes_total",
    "tdt_quant_wire_bytes_total",
    "tdt_kv_bytes_per_block",
    # disaggregated prefill/decode: TP×PP engine pipeline accounting
    # (models/engine.py, layers/pp_schedule.py) and the paged-KV handoff
    # channel + pool placement (serving/server.py, fleet/router.py) — see
    # docs/disagg.md
    "tdt_pp_stages",
    "tdt_pp_prefill_microbatches_total",
    "tdt_pp_ticks_total",
    "tdt_disagg_pool_role",
    "tdt_disagg_handoffs_total",
    "tdt_disagg_handoff_bytes_total",
    "tdt_disagg_handoff_seconds",
    "tdt_disagg_pool_fallbacks_total",
    # the serving loop on the profiler's clock: self-time digest, the
    # counts its ratios divide by, jit cache misses (runtime/tracing.py,
    # serving/server.py) — read by benchmark/layer_metrics/loop_*.py and
    # join_self_ms.py; see docs/observability.md "The loop's spans"
    "tdt_span_self_seconds",
    "tdt_serving_joins_total",
    "tdt_serving_decode_chunks_total",
    "tdt_jit_lowerings_total",
    # how a decode chunk was landed: behind the next one's issue, or first
    # and why (serving/server.py) — docs/serving.md "One chunk in flight";
    # the two sum to tdt_serving_decode_chunks_total
    "tdt_serving_decode_chunks_ahead_total",
    "tdt_serving_decode_sync_boundaries_total",
    # prefill chunks issued and not waited for (every chunk of a prompt
    # but its last), of the tdt_serving_prefill_chunks histogram's sum
    "tdt_serving_prefill_chunks_unfenced_total",
    # which way a paged decode chunk ran: against the pool in place, or
    # bounced through the contiguous layout (models/engine.py)
    "tdt_engine_decode_chunks_total",
    # span names
    "tdt_serving_step",
    "tdt_serving_join",
    "tdt_serving_prefill_arm",
    "tdt_serving_prefill_complete",
    "tdt_scheduler_join_free_slots",
    "tdt_engine_decode_steps_paged",
    "tdt_engine_prefill_chunk",
    "tdt_engine_complete_paged_prefill",
    "tdt_engine_cache_scatter",
    "tdt_serving_probe",
    "tdt_serving_restore",
    "tdt_serving_recovery",
    "tdt_fleet_request",
    "tdt_fleet_placement",
    "tdt_fleet_migration",
}


def _is_telemetry_call(node: ast.Call, bare_ok: bool = False) -> str | None:
    """Return the called function name when this is ``telemetry.<fn>(...)``
    (or an alias whose receiver name contains 'telemetry'), else None.
    ``bare_ok`` also accepts receiver-less ``inc(...)`` calls — the registry
    module instruments itself (the flight recorder's own counter)."""
    fn = node.func
    if bare_ok and isinstance(fn, ast.Name) and \
            fn.id in (METRIC_FNS | EVENT_FNS):
        return fn.id
    if not isinstance(fn, ast.Attribute):
        return None
    recv = fn.value
    if isinstance(recv, ast.Name) and "telemetry" in recv.id:
        return fn.attr
    # runtime.telemetry.inc(...) style: Attribute receiver named telemetry.
    if isinstance(recv, ast.Attribute) and recv.attr == "telemetry":
        return fn.attr
    return None


def _is_tracing_call(node: ast.Call) -> str | None:
    """Return the called function name when this is a span-name-taking call
    on a receiver whose name mentions trace/tracing (``tracing.start_trace``,
    ``req.trace.span``, ``self._trace.record``), else None."""
    fn = node.func
    if not isinstance(fn, ast.Attribute) or fn.attr not in TRACING_FNS:
        return None
    recv = fn.value
    if isinstance(recv, ast.Name) and "trac" in recv.id:
        return fn.attr
    if isinstance(recv, ast.Attribute) and "trac" in recv.attr:
        return fn.attr
    return None


def check_file(path: pathlib.Path, seen: set[str] | None = None) -> list[str]:
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:  # a broken file is some other tool's problem
        return [f"{path}:{e.lineno}: syntax error while linting: {e.msg}"]
    lines = src.splitlines()
    try:
        rel = path.relative_to(REPO)
    except ValueError:
        rel = path

    errors = []

    def err(node: ast.AST, msg: str) -> None:
        line = lines[node.lineno - 1] if node.lineno - 1 < len(lines) else ""
        if WAIVER in line:
            return
        errors.append(f"{rel}:{node.lineno}: {msg}\n    {line.strip()}")

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        tname = _is_tracing_call(node)
        if tname is not None and node.args:
            first = node.args[0]
            if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
                err(node, "dynamic span name — span names must be string "
                          "literals (put dynamic dimensions in span attrs)")
            elif not METRIC_NAME.match(first.value):
                err(node, f"span name {first.value!r} does not match "
                          "tdt_<subsystem>_<name> (lowercase, >=3 segments)")
            elif seen is not None:
                seen.add(first.value)
            continue
        fname = _is_telemetry_call(node, bare_ok=path.name == "telemetry.py")
        if fname is None or not node.args:
            continue
        first = node.args[0]
        if fname in METRIC_FNS:
            if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
                err(node, "dynamic metric name — metric names must be string "
                          "literals (put dynamic dimensions in label values)")
            elif not METRIC_NAME.match(first.value):
                err(node, f"metric name {first.value!r} does not match "
                          "tdt_<subsystem>_<name> (lowercase, >=3 segments)")
            elif seen is not None:
                seen.add(first.value)
        elif fname in EVENT_FNS:
            if isinstance(first, ast.Constant) and first.value is None:
                continue  # events(kind=None) positional form
            if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
                err(node, "dynamic event kind — emit/filter kinds must be "
                          "string literals")
            elif not EVENT_KIND.match(first.value):
                err(node, f"event kind {first.value!r} is not snake_case")
    return errors


def main(argv: list[str]) -> int:
    default_run = not argv
    roots = [pathlib.Path(a) for a in argv] or list(DEFAULT_ROOTS)
    files: list[pathlib.Path] = []
    for root in roots:
        if root.is_dir():
            files.extend(sorted(root.rglob("*.py")))
        else:
            files.append(root)

    errors = []
    seen: set[str] = set()
    for f in files:
        errors.extend(check_file(f, seen))

    if default_run:
        for name in sorted(REQUIRED_NAMES - seen):
            errors.append(
                f"required metric/span name {name!r} is referenced nowhere in "
                "the scanned sources — renamed without updating "
                "REQUIRED_NAMES (and docs/dashboards)?"
            )

    if errors:
        print(f"check_metric_names: {len(errors)} violation(s)")
        for e in errors:
            print(e)
        return 1
    print(f"check_metric_names: OK ({len(files)} file(s) scanned)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
