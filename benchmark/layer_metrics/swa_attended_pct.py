"""Of the positions a decode step's queries could see, the share their
sliding-window layers attended to: the program's
``tdt_swa_positions_attended_total`` over
``tdt_swa_positions_visible_total`` (``phase="decode"``). Both are counted
on the device, on the window layers, from the layers' own masks and the
live lengths (one more small output of the decode chunk), over the rows
somebody sent: ``min(length, sliding_window)`` over ``length``, summed over
the steps, which falls as contexts grow. A program that keeps or reads
every position reads 100. Nothing to read where the model has no such
layer."""

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    visible = run.telemetry.counter("tdt_swa_positions_visible_total", phase="decode")
    attended = run.telemetry.counter("tdt_swa_positions_attended_total", phase="decode")
    return 100.0 * attended / visible if visible else None
