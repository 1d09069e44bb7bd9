"""Rows a decode step really advances, of the configuration's slots: the
program's ``tdt_serving_decode_rows_total`` (for every decode chunk
dispatched, the steps each decoding slot still had to run, at most the
chunk's) over ``tdt_serving_decode_chunks_total`` times the configuration's
``serving.chunk``. A step reads every weight whatever the batch, so this is
what the weights' bytes are shared by: joins that wait for their prefill,
slots that run out inside a chunk and the opening's ramp all lower it.
Nothing to read where the program does not count its rows."""

LAYER = "server loop (serving/server.py)"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    rows = run.telemetry.counter("tdt_serving_decode_rows_total")
    chunks = run.telemetry.counter("tdt_serving_decode_chunks_total")
    chunk = run.cfg.get("serving", {}).get("chunk")
    return rows / (chunks * int(chunk)) if rows and chunks and chunk else None
