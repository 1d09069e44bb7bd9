"""Of the blocks a sparse layer's queries could see, the share their groups
selected: the program's ``tdt_bsa_blocks_selected_total`` over
``tdt_bsa_blocks_visible_total``, prefill chunks and decode steps together.
Both are counted on the device, on the layers that select, from the
selection's own mask (its sum beside the sum of the queries' own block
index + 1, over the K/V heads and the rows somebody sent; one more small
output of the step programs), so a selection that is skipped or replaced by
the forced blocks alone reads off the exact one's value, which is ``topk``
over the visible blocks once a query sees more than ``topk`` and falls as
contexts grow. Nothing to read where the model attends to everything."""

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    visible = run.telemetry.counter("tdt_bsa_blocks_visible_total")
    selected = run.telemetry.counter("tdt_bsa_blocks_selected_total")
    return 100.0 * selected / visible if visible else None
