"""The selective-scan kernel's share of its roofline, from the device trace:
the calls of the operations named ``ssm_scan`` in the trace (one a Mamba
layer a prefill chunk), times the least time one chip could take for a call
over the configuration's ``prefill_chunk`` rows (``counts.ssm_scan``: its
operands read once and its results written once, its ``rows x d_in x N``
updates' arithmetic; the larger of the two bounds), over those operations'
own device seconds (``trace.op_seconds``). The peaks are the MXU's and
HBM's, and the scan is the vector unit's work in a thousand dependent
steps, so this share is a floor that reads low; a chunk of fewer rows than
``prefill_chunk`` (there is none in a mix of whole chunks) would read it
lower still. Nothing to read where the trace holds no such kernel or the
architecture's counts have no ``ssm_scan``."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"ssm_scan")


def read(run):
    rows = run.cfg.get("serving", {}).get("prefill_chunk")
    count = getattr(run.counts.architecture, "ssm_scan", None)
    if run.peaks is None or run.trace is None or rows is None or count is None:
        return None
    spent, calls = run.trace_mod.op_seconds(run.trace, KERNEL)
    if not calls or spent <= 0:
        return None
    work = run.counts.per_chip(count(run.cfg, int(rows)), run.tp)
    return 100.0 * calls * run.counts.least_seconds(work, run.peaks)["seconds"] / spent
