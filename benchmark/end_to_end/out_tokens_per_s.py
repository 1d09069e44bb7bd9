"""Output tokens a second over the window: all the work and all the time,
a burst's tokens laid over the time the server took to make them
(``stats.out_tokens_per_s``)."""

UNIT = "tokens/s"
SOURCE = "host_clock"


def read(run):
    return run.stats.out_tokens_per_s(run.reqs, run.t_open, run.t_close)
