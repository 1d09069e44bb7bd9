"""Share of the window in which the device had no step program to run, by
the program's own ledger (``runtime/tracing.py``): the engine tells it each
issue of a prefill chunk, a pool scatter or a decode chunk and each wait
for one that returns, and it counts the seconds from a wait that left
nothing issued to the next issue into
``tdt_engine_device_starved_seconds_total{after}``. 100 x that counter's
move between the window's two snapshots, every ``after`` but ``no_work``
(the server had nothing to serve), over the window's seconds. It is
``device_idle_pct`` seen from the host over the whole window and not two
traced seconds: over it by the device's time in the small programs
between steps (a key split, zeros, sampling a row), which the ledger
counts as the host's, and under it by the device's idle time inside a
fence (from an issue to the program's start, from its end to the host's
wake-up), which the ledger cannot see: about a point either way. An
interval is counted whole when it ends: the one open as the window opens
is in, the one open as it closes is out (milliseconds of forty seconds). A
program without the counter reads nothing."""

LAYER = "server loop (serving/server.py)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"

COUNTER = "tdt_engine_device_starved_seconds_total"


def read(run):
    if COUNTER not in run.telemetry.after.get("counters", {}) or not run.window_s > 0:
        return None
    starved = run.telemetry.counter(COUNTER) - run.telemetry.counter(COUNTER, after="no_work")
    return 100.0 * starved / run.window_s
