"""The decode programs' share of their roofline, from the device trace: the
least time one chip could take for the traced decode steps (weights read
once a step, K/V read for the live lengths, the operations; the larger of
the two bounds) over the device seconds of the programs that decode: the
pool's gather, the chunk program with its kernels, and the scatter back.
The rows come from the harness's token log of the traced steps, the
seconds from the trace's ``XLA Modules`` events inside those steps."""

import re

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: Programs of the decode side, by the names jit gives them in a trace.
PROGRAMS = re.compile(r"jit_decode_chunk\w*|jit_paged_gather|jit_paged_scatter_(decode|rows)")


def read(run):
    if run.peaks is None or run.trace is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    least = 0.0
    for st in steps.values():
        if not st["decode"]:
            continue
        n = max(n for n, _ in st["decode"].values())
        rows = [first + j for n_, first in st["decode"].values() for j in range(n_)]
        work = run.counts.per_chip(run.counts.decode_steps(run.cfg, n, rows), run.tp)
        least += run.counts.least_seconds(work, run.peaks)["seconds"]
    spent, _ = run.trace_mod.program_seconds(run.trace, PROGRAMS)
    return 100.0 * least / spent if spent > 0 and least > 0 else None
