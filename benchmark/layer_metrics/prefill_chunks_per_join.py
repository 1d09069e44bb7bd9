"""Prefill chunks a joined prompt took, mean over the prompts that finished
prefill inside the window: sum over count of the program's
``tdt_serving_prefill_chunks`` histogram. 1 where every prompt prefills
whole; a prompt of n chunks waits n loop iterations for its first token,
with the other slots' decode chunks between them."""

LAYER = "server loop (serving/server.py)"
UNIT = "chunks"
SOURCE = "program_counter"
MOVES = "ttft_mean_ms"


def read(run):
    total, n = run.telemetry.histogram("tdt_serving_prefill_chunks")
    return total / n if n else None
