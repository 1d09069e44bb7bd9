"""Share of the window's joins whose prefill was issued behind the decode
chunk that freed their slot, before that chunk landed (the server's join
ahead): 100 x ``tdt_serving_joins_ahead_total`` /
``tdt_serving_joins_total``. 0 where requests joined and none went ahead
(every slot was free before its request came: a cell with a slot for each
client); nothing on a program that counts no joins ahead (one from before
the join ahead)."""

LAYER = "server loop (serving/server.py)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

AHEAD = "tdt_serving_joins_ahead_total"


def read(run):
    joins = run.telemetry.counter("tdt_serving_joins_total")
    if not joins or AHEAD not in run.telemetry.after.get("counters", {}):
        return None
    return 100.0 * run.telemetry.counter(AHEAD) / joins
