"""What a join costs the host beyond its programs, in milliseconds a join:
the self seconds of the server's join (the scheduler's ``join_free_slots``
is a child span of its own and so left out), of the prefill's arming and of
its completion (the engine's calls inside them are child spans too),
between the window's two snapshots of ``tdt_span_self_seconds``, over
``tdt_serving_joins_total``. A program without them reads nothing."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_mean_ms"

PHASES = ("tdt_serving_join", "tdt_serving_prefill_arm", "tdt_serving_prefill_complete")


def read(run):
    joins = run.telemetry.counter("tdt_serving_joins_total")
    if not joins:
        return None
    total = sum(run.telemetry.digest("tdt_span_self_seconds", phase=p)[0] for p in PHASES)
    return 1e3 * total / joins
