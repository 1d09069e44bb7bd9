"""The table-walk attention kernel's share of its roofline, from the device
trace: the least time one chip could take for the kernel's calls of the
traced decode steps (K/V of the live lengths read once, the rows' q and
output, QK^T and PV over the live positions; the larger of the two bounds)
over the own device seconds of the operations named ``paged_flash_decode``,
every one of them (``trace.op_seconds``). The rows come from the harness's
token log of the traced steps."""

import re

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"paged_flash_decode")


def read(run):
    if run.peaks is None or run.trace is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    least = 0.0
    for st in steps.values():
        rows = [first + j for n, first in st["decode"].values() for j in range(n)]
        if rows:
            work = run.counts.per_chip(run.counts.paged_flash_decode(run.cfg, rows), run.tp)
            least += run.counts.least_seconds(work, run.peaks)["seconds"]
    spent, _ = run.trace_mod.op_seconds(run.trace, KERNEL)
    return 100.0 * least / spent if spent > 0 and least > 0 else None
