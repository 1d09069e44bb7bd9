"""Mean, over every request submitted inside the window that streamed two
tokens or more, of (last token time - first token time) / (tokens - 1)."""

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    paces = run.stats.tpot_ms(run.reqs, run.t_open, run.t_close)
    return sum(paces) / len(paces) if paces else None
