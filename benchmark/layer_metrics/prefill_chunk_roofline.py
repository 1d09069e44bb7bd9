"""The prefill programs' share of their roofline where prompts prefill in
chunks over several loop iterations, from the device trace: the executions
of the chunk program (``jit_chunk_fn``) in the trace, times the least time
one chip could take for a chunk of the configuration's ``prefill_chunk``
rows whatever its offset (``counts.prefill_chunk``: the layers' weights
once, the matrix work of its rows, both cache rows written, attention and
index scores as at offset 0), over the device seconds of the programs that
prefill. The trace does not say at which offset a chunk ran, so this is a
FLOOR: a chunk deep in a long prompt attends to more and reads its index
keys, and the share can only read low. Nothing to read where the
architecture's counts have no ``prefill_chunk`` or the configuration no
``serving.prefill_chunk``."""

import re

LAYER = "model step, prefill (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"

CHUNK = re.compile(r"jit_chunk_fn")
#: Programs of the prefill side, by the names jit gives them in a trace.
PROGRAMS = re.compile(r"jit_chunk_fn|jit_paged_kbuf_zeros|jit_paged_scatter_prefill|jit_paged_seed_kbuf")


def read(run):
    rows = run.cfg.get("serving", {}).get("prefill_chunk")
    count = getattr(run.counts.architecture, "prefill_chunk", None)
    if run.peaks is None or run.trace is None or rows is None or count is None:
        return None
    _, chunks = run.trace_mod.program_seconds(run.trace, CHUNK)
    spent, _ = run.trace_mod.program_seconds(run.trace, PROGRAMS)
    if not chunks or spent <= 0:
        return None
    work = run.counts.per_chip(count(run.cfg, int(rows)), run.tp)
    return 100.0 * chunks * run.counts.least_seconds(work, run.peaks)["seconds"] / spent
