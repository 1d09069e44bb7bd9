"""The state-space (Mamba-2) decode kernel's share of its roofline, from the
device trace: the least time one chip could take for the kernel's calls of
the traced decode steps (``counts.ssd_step``, once a Mamba-2 layer a step:
the advancing slots' state read and written, their rows' x, dt, B and C
read and y written; the larger of the two bounds, which is HBM's) over the
own device seconds of the operations named ``ssd_step``, every one of them
(``trace.op_seconds``). The slots a step advances come from the harness's
token log of the traced steps. A step in which no slot advances still calls
the kernel, which then moves nothing, so the share reads a little low.
Nothing to read where the trace holds no such kernel or the architecture's
counts have no ``ssd_step``."""

import re

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"ssd_step")


def read(run):
    count = getattr(run.counts.architecture, "ssd_step", None)
    if run.peaks is None or run.trace is None or count is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    layers = list(run.cfg["layer_types"]).count("mamba")
    least = 0.0
    for st in steps.values():
        if not st["decode"]:
            continue
        # a chunk's step j advances every request still decoding
        for j in range(max(n for n, _ in st["decode"].values())):
            rows = sum(1 for n, _ in st["decode"].values() if j < n)
            work = run.counts.per_chip(count(run.cfg, rows), run.tp)
            least += layers * run.counts.least_seconds(work, run.peaks)["seconds"]
    spent, _ = run.trace_mod.op_seconds(run.trace, KERNEL)
    return 100.0 * least / spent if spent > 0 and least > 0 else None
