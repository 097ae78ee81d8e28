"""lloyd_iters (layer: round body): Lloyd iterations a worker runs in a
round, from the program's ``hpclust.round`` events (``core/hpclust.py``),
each of which lists every worker's iterations in ``lloyd_iters``. The mean
over the rounds of the timed call of each round's mean over workers. Read
only in a run that traced a device, beside the device metrics."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    means = []
    for r in ctx.spans:
        if r.get("type") == "event" and r.get("name") == "hpclust.round":
            iters = r.get("attrs", {}).get("lloyd_iters")
            if iters:
                means.append(sum(iters) / len(iters))
    if not means:
        return None
    return sum(means) / len(means)
