"""Mean wall time of a decode chunk as the loop sees it, over the chunks
landed inside the window: sum over count of the program's
``tdt_serving_decode_chunk_seconds`` histogram, each chunk's time from the
landing before it (or from its own issue, if that came later) to its
landing. Near the device's time for the chunk where chunks follow one
another; the prefills between two landings are in it. A program without
the histogram reads nothing."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    total, n = run.telemetry.histogram("tdt_serving_decode_chunk_seconds")
    return 1e3 * total / n if n else None
