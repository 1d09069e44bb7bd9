"""Milliseconds a decode chunk in which the device had no step program to
run while the loop was in its own spans: ``tdt_span_starved_seconds`` (a
span's share of the ledger's starved seconds, the spans opened inside it
taken out, as self time is) between the window's two snapshots, summed
over the spans ``loop_self_ms_per_chunk`` sums and the engine's spans
opened beneath them, less the join's (``join_starved_ms`` has those), over
``tdt_serving_decode_chunks_total``. The digest is keyed by span name
alone, so a name opened in two places lies where ``loop_self_ms_per_chunk``
/ ``join_self_ms`` put it: ``tdt_serving_fetch``, ``_emit``,
``_table_push`` and ``_finish_slot`` are the loop's, also where a
prefill's completion opens them for token 0. 0 where chunks landed and no
span starved the device; nothing on a program without the ledger."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "out_tokens_per_s"

LEDGER = "tdt_engine_device_starved_seconds_total"
PHASES = tuple("tdt_serving_" + p for p in (
    "step", "health", "prefill", "reap", "decode_prep", "dispatch", "fetch", "emit",
    "finish_slot", "table_push")) + tuple("tdt_engine_" + p for p in (
        "prefill_chunk", "decode_steps_paged", "dispatch", "cache_scatter", "host_sync"))


def read(run):
    chunks = run.telemetry.counter("tdt_serving_decode_chunks_total")
    if not chunks or LEDGER not in run.telemetry.after.get("counters", {}):
        return None
    starved = sum(run.telemetry.digest("tdt_span_starved_seconds", phase=p)[0] for p in PHASES)
    return 1e3 * starved / chunks
