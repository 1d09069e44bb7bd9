"""Mean wait from arrival to a slot, over the requests that joined inside
the window: the program's ``tdt_serving_queue_wait_seconds`` histogram, as
the difference of two snapshots (it keeps a sum and a count; its buckets
are powers of two, too coarse for a median)."""

LAYER = "scheduler (serving/scheduler.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_mean_ms"


def read(run):
    total, n = run.telemetry.histogram("tdt_serving_queue_wait_seconds")
    return 1e3 * total / n if n else None
