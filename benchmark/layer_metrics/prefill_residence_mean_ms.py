"""Mean time a request sat in its slot before its first token, over the
requests whose first token streamed inside the window: sum over count of
the program's ``tdt_serving_prefill_residence_seconds`` histogram, from the
admission (where ``queue_wait_mean_ms`` ends) to token 0, on the server's
clock. Queue wait + residence is the program's time to the first token;
residence less ``prefill_own_mean_ms`` is what the request spent in a slot
while the loop served the others. A program without the histogram reads
nothing."""

LAYER = "scheduler (serving/scheduler.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_mean_ms"


def read(run):
    total, n = run.telemetry.histogram("tdt_serving_prefill_residence_seconds")
    return 1e3 * total / n if n else None
