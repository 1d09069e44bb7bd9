"""The dense latent decode attend's share of its roofline, from the device
trace: the least time one chip could take for the kernel's calls of the
traced decode steps (``counts.latent_decode``, once a layer a step: each
slot's visible latent rows read once for all heads, the absorbed queries and
the latent results, scores and the weighted sum in the latent space; the
larger of the two bounds, which is HBM's) over the own device seconds of the
operations named ``latent_flash_decode``, every one of them
(``trace.op_seconds``). The rows come from the harness's token log of the
traced steps. A row counts at its 576 values and lies in 640, a page is
fetched whole and a tile of pages whole, and a slot's walk starts under the
slot before it, so the share reads under 100. Nothing to read where the
trace holds no such kernel or the architecture's counts have no
``latent_decode``."""

import re

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"latent_flash_decode")


def read(run):
    count = getattr(run.counts.architecture, "latent_decode", None)
    if run.peaks is None or run.trace is None or count is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    layers = int(run.cfg["num_hidden_layers"])
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
