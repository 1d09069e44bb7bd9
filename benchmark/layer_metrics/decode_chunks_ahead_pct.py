"""Share of the window's decode chunks that were landed behind the next
one's issue, so that the device went from one to the next with no word
from the host: 100 x ``tdt_serving_decode_chunks_ahead_total`` /
``tdt_serving_decode_chunks_total``. The rest were landed first, and
``tdt_serving_decode_sync_boundaries_total{why}`` says why. 0 where the
loop counts its boundaries and none ran ahead; nothing on a program that
counts neither (before PR 36)."""

LAYER = "server loop (serving/server.py)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

AHEAD = "tdt_serving_decode_chunks_ahead_total"
LANDED_FIRST = "tdt_serving_decode_sync_boundaries_total"


def read(run):
    chunks = run.telemetry.counter("tdt_serving_decode_chunks_total")
    counted = run.telemetry.after.get("counters", {})
    if not chunks or not (AHEAD in counted or LANDED_FIRST in counted):
        return None
    return 100.0 * run.telemetry.counter(AHEAD) / chunks
