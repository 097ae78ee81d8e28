"""reseed_rounds_pct (layer: round body): the share of the timed call's
rounds in which the K-means++ reseed ran, from the program's
``hpclust.round`` events (``core/hpclust.py``), each of which lists in
``reseed_rows`` the degenerate rows every worker's warm start redrew. A
round counts where any worker's count is above 0. Read only in a run that
traced a device, beside the device metrics; None where the events carry no
``reseed_rows``."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    rounds = [r.get("attrs", {}).get("reseed_rows") for r in ctx.spans
              if r.get("type") == "event" and r.get("name") == "hpclust.round"]
    rounds = [rows for rows in rounds if rows is not None]
    if not rounds:
        return None
    return 100.0 * sum(1 for rows in rounds if any(rows)) / len(rounds)
