"""sanitize_ms (layer: ingest): milliseconds per window in the program's
``sanitize.window`` span (``data/device_prefetch.py``), the host's
non-finite scan of each window before it is put on the device."""


def read(ctx):
    durs = [r["dur"] for r in ctx.spans
            if r.get("type") == "span" and r.get("name") == "sanitize.window"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
