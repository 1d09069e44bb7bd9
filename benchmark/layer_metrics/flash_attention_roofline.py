"""The prefill attention kernel's share of its roofline, from the device
trace: the least time one chip could take for the kernel's calls of every
prompt prefilled in the traced steps, by its length (QK^T and PV over the
causal half; q, K, V read and the output written once), over the own device
seconds of the operations named ``flash_attention``, every one of them
(``trace.op_seconds``)."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"flash_attention")


def read(run):
    if run.peaks is None or run.trace is None:
        return None
    steps = run.traced_steps()
    if steps is None:
        return None
    least = 0.0
    for st in steps.values():
        for p_len in st["prefill"]:
            work = run.counts.per_chip(run.counts.flash_attention(run.cfg, p_len), run.tp)
            least += run.counts.least_seconds(work, run.peaks)["seconds"]
    spent, _ = run.trace_mod.op_seconds(run.trace, KERNEL)
    return 100.0 * least / spent if spent > 0 and least > 0 else None
