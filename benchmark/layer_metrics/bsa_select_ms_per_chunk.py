"""What the block selection's scores cost a prefill chunk, from the device
trace: own device seconds of the operations named ``bsa_select``
(``trace.op_seconds``: the kernel that gives each query group its sum of
softmaxes over the pooled keys, once a sparse layer) over the executions of
the chunk program (``jit_chunk_fn``), in milliseconds. The block max and
the rank that picks the blocks from those scores are XLA fusions, which the
trace does not tell from their neighbours, and are not in this number.
Nothing to read where the trace holds no such kernel (a model that does not
select) or no chunk."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The selection, by its kernel's ``name``.
KERNEL = re.compile(r"bsa_select")
CHUNK = re.compile(r"jit_chunk_fn")


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace_mod.op_seconds(run.trace, KERNEL)
    _, chunks = run.trace_mod.program_seconds(run.trace, CHUNK)
    return 1e3 * spent / chunks if calls and chunks else None
