"""The whole step's share of the chips' peak: the model's operations for
every prompt and every output token processed inside the window (attention
included, nothing recomputed counted) over the window's seconds times the
chips' peak operations a second."""

LAYER = "whole step (serving/server.py down to the device)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "out_tokens_per_s"


def read(run):
    if run.peaks is None:
        return None
    flops = 0.0
    for st in run.window_steps().values():
        for p_len in st["prefill"]:
            flops += run.counts.prefill(run.cfg, p_len)["flops"]
        rows = [first + j for n, first in st["decode"].values() for j in range(n)]
        if rows:
            flops += run.counts.decode_steps(run.cfg, 0, rows)["flops"]
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (run.window_s * peak) if flops > 0 else None
