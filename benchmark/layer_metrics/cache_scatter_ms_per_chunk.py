"""Seconds in the engine's ``cache_scatter`` phase (fenced on the pool) a
decode chunk. The phase brackets the scatter after each decode chunk and
also each finished prefill's scatter into the pool, so a prefill-heavy mix
reads higher for the same decode-side cost."""

LAYER = "engine (models/engine.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "out_tokens_per_s"


def read(run):
    total, _ = run.telemetry.digest("tdt_engine_phase_seconds", phase="cache_scatter")
    chunks = run.telemetry.counter("tdt_serving_decode_chunks_total")
    return 1e3 * total / chunks if chunks else None
