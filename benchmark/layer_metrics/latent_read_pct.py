"""Of the latent rows a decode step's queries could see, how many the attend
fetched, as a share: the program's ``tdt_latent_rows_read_total`` over
``tdt_latent_rows_visible_total`` (``phase="decode"``). Both are counted on
the device on the layers with no indexer, from the live lengths (one more
small output of the decode chunk): what was read is whole tiles of pages up
to each slot's length where the kernel reads the pool in place, and the
table's whole extent where the step gathers it. 100 is exact and the least
there is; a step that gathered 33k rows a slot for contexts of 16k would
read 200. Nothing to read where every layer selects."""

LAYER = "model step, decode (models/engine.py, layers/, kernels/)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    visible = run.telemetry.counter("tdt_latent_rows_visible_total", phase="decode")
    read_ = run.telemetry.counter("tdt_latent_rows_read_total", phase="decode")
    return 100.0 * read_ / visible if visible else None
