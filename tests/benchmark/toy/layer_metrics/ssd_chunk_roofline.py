"""The chunked state-space (Mamba-2) kernel's share of its roofline, from the
device trace: the calls of the operations named ``ssd_chunk`` in the trace
(one a Mamba-2 layer a prefill chunk), times the least time one chip could
take for a call over the configuration's ``prefill_chunk`` rows
(``counts.ssd_chunk``: x, dt, B, C read and y written in the model's type,
the state in and out; ``C B^T`` a sub-chunk and three products a head; the
larger of the two bounds), over those operations' own device seconds
(``trace.op_seconds``). The kernel writes y in float32 and spends much of
its time on a ``(c, c)`` decay mask a head a sub-chunk on the vector unit,
so the share reads under 100. Nothing to read where the trace holds no such
kernel or the architecture's counts have no ``ssd_chunk``."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"ssd_chunk")


def read(run):
    rows = run.cfg.get("serving", {}).get("prefill_chunk")
    count = getattr(run.counts.architecture, "ssd_chunk", None)
    if run.peaks is None or run.trace is None or rows is None or count is None:
        return None
    spent, calls = run.trace_mod.op_seconds(run.trace, KERNEL)
    if not calls or spent <= 0:
        return None
    work = run.counts.per_chip(count(run.cfg, int(rows)), run.tp)
    return 100.0 * calls * run.counts.least_seconds(work, run.peaks)["seconds"] / spent
