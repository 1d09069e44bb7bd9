"""Of the slot-layer states a decode step advanced, how many the Mamba-2
decode kernel read and wrote, as a share: the program's
``tdt_ssd_state_slots_total{kind="moved"}`` over ``{kind="advanced"}``
(``phase="decode"``). Both are counted on the device from the step's active
mask (one more small output of the decode chunk): the kernel moves the
state of the slots that advance and no other, so 100 is exact and the
least there is; a step that swept every slot's state, as a ``where(active,
...)`` over the whole array does, would read 32 / active x 100. Nothing to
read where the model has no Mamba-2 layer."""

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    advanced = run.telemetry.counter("tdt_ssd_state_slots_total", kind="advanced",
                                     phase="decode")
    moved = run.telemetry.counter("tdt_ssd_state_slots_total", kind="moved", phase="decode")
    return 100.0 * moved / advanced if advanced else None
