"""Device milliseconds lost a decode chunk while the loop ran its own
Python, on the clock the host's spans share with the device: the seconds of
the trace's idle gaps whose label (the innermost host event over the gap) is
one of the program's own ``tdt_serving_*`` / ``tdt_engine_*`` /
``tdt_scheduler_*`` spans, over the executions of ``jit_decode_chunk`` in
the trace. ``idle_gaps`` keeps the ten largest labels: what it drops is
under a millisecond. A gap under one of jax's own events (``np.asarray``,
a transfer, a dispatch) is not counted: the loop is inside jax there. Reads
nothing without a trace, or where no gap carries a span of the program's."""

import re

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

SPANS = re.compile(r"tdt_(serving|engine|scheduler)_\w+")
CHUNK = re.compile(r"jit_decode_chunk\w*")


def read(run):
    if run.trace is None:
        return None
    lost = [s for label, s in run.trace["idle_gaps"] if SPANS.fullmatch(label)]
    _, chunks = run.trace_mod.program_seconds(run.trace, CHUNK)
    return 1e3 * sum(lost) / chunks if lost and chunks else None
