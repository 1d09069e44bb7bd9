"""Median time between successive token bursts of the decode batch, from
the harness's own ``on_token`` times: one decode chunk with everything the
server loop does around it."""

LAYER = "server loop (serving/server.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


def read(run):
    gaps = run.stats.burst_periods_ms(run.reqs, run.first_step, run.last_step)
    return run.stats.percentile(gaps, 50)
