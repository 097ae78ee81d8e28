"""h2d_ms (layer: ingest): milliseconds per window that placing the
sanitized window on the device held the prefetch thread, in the program's
``h2d.put`` span (``data/device_prefetch.py``). An asynchronous
``device_put`` returns before its copy ends: this is the host's part.
Read from the traced slice (``bench/program_spans.py``)."""
from bench import program_spans

SPAN = "h2d.put"


def read(ctx):
    return program_spans.mean_ms(ctx.trace, SPAN)
