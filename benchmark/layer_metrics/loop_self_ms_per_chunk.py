"""Host milliseconds the server loop spends in its own Python a decode
chunk: the self seconds of the spans ``InferenceServer.step()`` opens in an
iteration (the iteration itself, its phases, the request's prefill span:
each one's duration less the spans opened inside it, so the engine's and
the scheduler's calls are out) between the window's two snapshots of the
``tdt_span_self_seconds`` digest, over ``tdt_serving_decode_chunks_total``.
The spans of a probe, a restore or a recovery are not the loop's steady
work and are left out. An iteration that finds nothing to do still counts
its sweep, join and reap: in a cell that does not keep the slots full the
number holds the polling too. A program without the digest reads nothing."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_mean_ms"

PHASES = tuple("tdt_serving_" + p for p in (
    "step", "health", "join", "prefill_arm", "prefill", "prefill_complete", "reap",
    "decode_prep", "dispatch", "fetch", "emit", "finish_slot", "table_push"))


def read(run):
    chunks = run.telemetry.counter("tdt_serving_decode_chunks_total")
    spans = [run.telemetry.digest("tdt_span_self_seconds", phase=p) for p in PHASES]
    if not chunks or not any(n for _, n in spans):
        return None
    return 1e3 * sum(s for s, _ in spans) / chunks
