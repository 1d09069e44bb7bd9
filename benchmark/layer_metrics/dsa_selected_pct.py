"""Of the positions a decode step's queries could see, the share that was
selected for them: the program's ``tdt_dsa_positions_selected_total`` over
``tdt_dsa_positions_visible_total`` (``phase="decode"``). Both are counted
on the device, on the layers that select, from the selection itself (the
mask's sum beside the live lengths' sum, one more small output of the decode
chunk), so a selection that is skipped reads 100 and one that takes fewer or
more than ``index_topk`` reads off the exact one's value, which is
``index_topk`` over the live length and falls as contexts grow. Nothing to
read where the model attends to everything."""

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    visible = run.telemetry.counter("tdt_dsa_positions_visible_total", phase="decode")
    selected = run.telemetry.counter("tdt_dsa_positions_selected_total", phase="decode")
    return 100.0 * selected / visible if visible else None
