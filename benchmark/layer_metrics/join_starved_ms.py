"""Milliseconds a join in which the device had no step program to run
while the loop was in the join's spans: ``tdt_span_starved_seconds``
between the window's two snapshots over ``join_self_ms``'s three spans and
what opens beneath them (the scheduler's ``join_free_slots``, the engine's
buffer of zeros, the pool scatter's call, the sampling of token 0), over
``tdt_serving_joins_total``. The prefill chunks themselves and the fetch
and emit of token 0 are under ``loop_starved_ms_per_chunk`` (the digest is
keyed by span name alone; see there). 0 where requests joined and no such
span starved the device; nothing on a program without the ledger."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_mean_ms"

LEDGER = "tdt_engine_device_starved_seconds_total"
PHASES = ("tdt_serving_join", "tdt_serving_prefill_arm", "tdt_serving_prefill_complete",
          "tdt_scheduler_join_free_slots", "tdt_engine_paged_kbuf",
          "tdt_engine_complete_paged_prefill", "tdt_engine_sample_logits")


def read(run):
    joins = run.telemetry.counter("tdt_serving_joins_total")
    if not joins or LEDGER not in run.telemetry.after.get("counters", {}):
        return None
    starved = sum(run.telemetry.digest("tdt_span_starved_seconds", phase=p)[0] for p in PHASES)
    return 1e3 * starved / joins
