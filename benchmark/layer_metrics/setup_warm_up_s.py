"""Of set-up, the seconds in the warm-up: one request of every prompt
length of the mix served through the server itself, which lowers every
program the window will use (and compiles it, in a checkout's first run)."""

LAYER = "set-up (build and warm-up, before the window)"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.warm_up_s if run.warm_up_s > 0 else None
