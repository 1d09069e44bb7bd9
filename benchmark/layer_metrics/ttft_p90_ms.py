"""90th percentile of the time to first token over every request submitted
inside the window (one that never got a token counts at the drain's end).
Recorded, not judged: in a closed loop on lockstep slots the tail follows
the order of the sizes, and the seeds' schedules spread it by a tenth."""

LAYER = "scheduler (serving/scheduler.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_mean_ms"


def read(run):
    waits = run.stats.ttft_ms(run.reqs, run.t_open, run.t_close, run.t_drain_end)
    return run.stats.percentile(waits, 90)
