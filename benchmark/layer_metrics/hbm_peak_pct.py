"""Largest ``peak_bytes_in_use`` over the cell's devices, read after the
drain and before the reference runs, as a share of the chip's HBM."""

LAYER = "device"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    if run.peaks is None or run.memory_peak_bytes is None:
        return None
    return 100.0 * run.memory_peak_bytes / run.peaks["hbm_bytes"]
