"""What a prefill chunk's attention over the whole visible cache costs, from
the device trace: own device seconds of the operations named
``latent_flash_prefill`` (``trace.op_seconds``: the flash kernel that
attends a chunk's queries over the prompt's latent rows under causality
alone, once a layer) over the executions of the chunk program
(``jit_chunk_fn``), in milliseconds. It grows with the chunk's offset, so
the mean follows the mix of prompt lengths the traced seconds held. Nothing
to read where the trace holds no such kernel (a program that attends in
plain XLA, a model that selects) or no chunk."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

#: The kernel, by the name it gives its ``pallas_call``.
KERNEL = re.compile(r"latent_flash_prefill")
CHUNK = re.compile(r"jit_chunk_fn")


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace_mod.op_seconds(run.trace, KERNEL)
    _, chunks = run.trace_mod.program_seconds(run.trace, CHUNK)
    return 1e3 * spent / chunks if calls and chunks else None
