"""What the algorithm needs, counted: one file an architecture,
``<path>/counts/<architecture>.py``, which gives ``prefill(cfg, p_len)`` and
``decode_steps(cfg, steps, row_lengths)`` as ``{"flops", "bytes"}`` (and one
function for each kernel whose roofline a reader reports). What is arithmetic
of any model is here, and :class:`Counts` puts the two together: it is what a
reader sees as ``run.counts``.
"""

from __future__ import annotations


def per_chip(work: dict, tp: int) -> dict:
    return {k: v / tp for k, v in work.items()}


def least_seconds(work: dict, peaks: dict) -> dict:
    """The least time one chip could take for ``work``, and which bound
    sets it."""
    tf = work["flops"] / peaks["bf16_flops_per_s"]
    tb = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(tf, tb), "bound": "compute" if tf >= tb else "memory"}


class Counts:
    """An architecture's counts with the common arithmetic beside them. The
    common names win: no architecture's file replaces the yardstick."""

    per_chip = staticmethod(per_chip)
    least_seconds = staticmethod(least_seconds)

    def __init__(self, architecture):
        self.architecture = architecture

    def __getattr__(self, name):
        return getattr(self.architecture, name)
