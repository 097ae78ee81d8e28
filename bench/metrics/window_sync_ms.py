"""window_sync_ms (layer: streaming entry): milliseconds per window that
``fit_stream`` blocked on the round program's results, in the program's
``stream.sync`` span (``core/hpclust.py``), the one per-window fetch.
Read from the traced slice (``bench/program_spans.py``)."""
from bench import program_spans

SPAN = "stream.sync"


def read(ctx):
    return program_spans.mean_ms(ctx.trace, SPAN)
