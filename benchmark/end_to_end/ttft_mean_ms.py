"""Mean, over every request submitted inside the window, of first token
time less submit time. A request that failed, was rejected or had no first
token when the drain ended counts at the drain's end."""

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    waits = run.stats.ttft_ms(run.reqs, run.t_open, run.t_close, run.t_drain_end)
    return sum(waits) / len(waits) if waits else None
