"""The dense latent prefill attend's share of its roofline, from the device
trace: the calls of the operations named ``latent_flash_prefill`` in the
trace (one a layer a prefill chunk), times the least time one chip could
take for a call over the configuration's ``prefill_chunk`` rows at position
0 (``counts.latent_prefill``: causal attention over the chunk's own
positions, K and V made from their latent rows, the queries and the result;
the larger of the two bounds, which is the MXU's), over those operations'
own device seconds (``trace.op_seconds``). The trace does not say at which
offset a chunk ran, so this is a FLOOR: a chunk deep in a 32k prompt attends
to sixteen times what the first does, and the share can only read low.
Nothing to read where the trace holds no such kernel or the architecture's
counts have no ``latent_prefill``."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"latent_flash_prefill")


def read(run):
    rows = run.cfg.get("serving", {}).get("prefill_chunk")
    count = getattr(run.counts.architecture, "latent_prefill", None)
    if run.peaks is None or run.trace is None or rows is None or count is None:
        return None
    spent, calls = run.trace_mod.op_seconds(run.trace, KERNEL)
    if not calls or spent <= 0:
        return None
    work = run.counts.per_chip(count(run.cfg, int(rows)), run.tp)
    return 100.0 * calls * run.counts.least_seconds(work, run.peaks)["seconds"] / spent
