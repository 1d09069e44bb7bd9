"""Process start to the first timed submit: import, weights on the device
from the seed, engine, one warm-up request of each prompt length of the
mix. The comparison with the reference is not in it."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
