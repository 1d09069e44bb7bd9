"""Rows a held expert is given a call of the expert layer, prefill chunks
and decode steps together (most of the rows are prefill's, most of the calls
decode's): the program's ``tdt_ep_expert_tokens_total`` of the experts the
configuration holds (``experts_held``) over their number times
``tdt_ep_dispatch_total``, both counted on the device over the rows somebody
sent (no padding, no inactive slot). One chip of a wide expert-parallel
deployment would be sent the rows of every rank's batch; here an expert
sees this chip's alone, so the number says how far the cell is from the
deployment's load. Nothing to read where the model has no such layer."""

LAYER = "whole step (serving/server.py down to the device)"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    held = run.cfg.get("experts_held")
    calls = run.telemetry.counter("tdt_ep_dispatch_total")
    if not held or not calls:
        return None
    first, count = held
    rows = sum(run.telemetry.counter("tdt_ep_expert_tokens_total", expert=str(e))
               for e in range(first, first + count))
    return rows / (count * calls)
