"""90th percentile, over every request submitted inside the window with
two tokens or more, of its time a token after the first. Recorded, not
judged: the tail of some hundred requests follows the seed's schedule."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


def read(run):
    return run.stats.percentile(run.stats.tpot_ms(run.reqs, run.t_open, run.t_close), 90)
