"""Share of the traced seconds in which no operation ran on the device (on
four chips, the busiest one): 1 - union of the device's operation intervals
over the traced span."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(run):
    return None if run.trace is None else run.trace["idle_pct"]
