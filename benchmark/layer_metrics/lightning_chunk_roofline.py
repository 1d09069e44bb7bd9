"""The lightning (decayed linear attention) chunk kernel's share of its
roofline, from the device trace: the calls of the operations named
``lightning_chunk`` in the trace (one a lightning layer a prefill chunk),
times the least time one chip could take for a call over the
configuration's ``prefill_chunk`` rows (``counts.lightning_chunk``: the
chunk's q, k, v read and o written in the model's type, the state in and
out, ``4 D^2`` operations a head a row; the larger of the two bounds, which
is HBM's), over those operations' own device seconds
(``trace.op_seconds``). The kernel writes o in float32 and spends its time
on four small MXU products and a ``(c, c)`` decay mask a sub-chunk, so the
share reads well under 100; a chunk of fewer rows than ``prefill_chunk``
(there is none in a mix of whole chunks) would read it lower still. Nothing
to read where the trace holds no such kernel or the architecture's counts
have no ``lightning_chunk``."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"lightning_chunk")


def read(run):
    rows = run.cfg.get("serving", {}).get("prefill_chunk")
    count = getattr(run.counts.architecture, "lightning_chunk", None)
    if run.peaks is None or run.trace is None or rows is None or count is None:
        return None
    spent, calls = run.trace_mod.op_seconds(run.trace, KERNEL)
    if not calls or spent <= 0:
        return None
    work = run.counts.per_chip(count(run.cfg, int(rows)), run.tp)
    return 100.0 * calls * run.counts.least_seconds(work, run.peaks)["seconds"] / spent
