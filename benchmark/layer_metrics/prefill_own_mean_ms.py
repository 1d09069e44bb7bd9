"""Mean time of a request's own prefill work, over the requests whose
first token streamed inside the window: sum over count of the program's
``tdt_serving_prefill_own_seconds`` histogram, the request's own prefill
chunk calls (issue to the fence's return) plus its prefill's completion up
to token 0. About chunks a join x a chunk's time; what
``prefill_residence_mean_ms`` holds beyond it is turn-taking. A program
without the histogram reads nothing."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_mean_ms"


def read(run):
    total, n = run.telemetry.histogram("tdt_serving_prefill_own_seconds")
    return 1e3 * total / n if n else None
