"""ingest_wait_ms (layer: ingest): milliseconds per window that
``fit_stream``'s loop waited for its next window, in the program's
``stream.wait`` span (``data/device_prefetch.py``): the queue wait behind
the prefetch thread, or the whole preparation on the synchronous path.
Read from the traced slice (``bench/program_spans.py``)."""
from bench import program_spans

SPAN = "stream.wait"


def read(ctx):
    return program_spans.mean_ms(ctx.trace, SPAN)
