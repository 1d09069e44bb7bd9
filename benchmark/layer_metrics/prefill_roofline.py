"""The prefill programs' share of their roofline, from the device trace:
the least time one chip could take for every prompt prefilled in the
traced steps, by its length, over the device seconds of the programs that
prefill: the context buffer's zeros, the prefill chunk with its kernels,
and the scatter of the finished buffer into the pool."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: Programs of the prefill side, by the names jit gives them in a trace.
PROGRAMS = re.compile(r"jit_chunk_fn|jit_prefill\w*|jit_paged_scatter_prefill|jit_paged_seed_kbuf")


def read(run):
    if run.peaks is None or run.trace is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    least = 0.0
    for st in steps.values():
        for p_len in st["prefill"]:
            work = run.counts.per_chip(run.counts.prefill(run.cfg, p_len), run.tp)
            least += run.counts.least_seconds(work, run.peaks)["seconds"]
    spent, _ = run.trace_mod.program_seconds(run.trace, PROGRAMS)
    return 100.0 * least / spent if spent > 0 and least > 0 else None
