"""A metric that a test adds by a file and an entry alone: the requests
finished inside the window, from the harness's own log."""

LAYER = "server loop (serving/server.py)"
UNIT = "requests"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    return float(sum(1 for r in run.reqs if r.ok and run.t_open <= r.submit_t <= run.t_close))
