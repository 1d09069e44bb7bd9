"""The block-sparse decode attend's share of its roofline, from the device
trace: the least time one chip could take for the kernel's calls of the
traced decode steps (``counts.bsa_decode``, once a sparse layer a step: the
selected pages' K and V, which is the attended positions' rows, the query
rows and the results, ``4 D`` operations a (query head, position); the
larger of the two bounds, which is HBM's) over the own device seconds of
the operations named ``bsa_decode``, every one of them
(``trace.op_seconds``). The rows come from the harness's token log of the
traced steps. A page is fetched whole and a tile of pages whole, and a walk
of eight tiles a (slot, K/V head) starts from an empty pipe, so the share
reads under 100. Nothing to read where the trace holds no such kernel or
the architecture's counts have no ``bsa_decode``."""

import re

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"bsa_decode")


def read(run):
    count = getattr(run.counts.architecture, "bsa_decode", None)
    if run.peaks is None or run.trace is None or count is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    layers = list(run.cfg["mixer_types"]).count("minicpm4")
    least = 0.0
    for st in steps.values():
        if not st["decode"]:
            continue
        # a chunk's step j computes one row for every request still decoding
        for j in range(max(n for n, _ in st["decode"].values())):
            rows = [first + j for n, first in st["decode"].values() if j < n]
            work = run.counts.per_chip(count(run.cfg, rows), run.tp)
            least += layers * run.counts.least_seconds(work, run.peaks)["seconds"]
    spent, _ = run.trace_mod.op_seconds(run.trace, KERNEL)
    return 100.0 * least / spent if spent > 0 and least > 0 else None
