"""Of set-up, the seconds in the architecture's ``build``: the weights
drawn on the device from the seed and there, the engine and the server
made. What is left of ``setup_s`` beside this and ``setup_warm_up_s`` is the
process's start: imports and the runtime reaching the chip."""

LAYER = "set-up (build and warm-up, before the window)"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.build_s if run.build_s > 0 else None
