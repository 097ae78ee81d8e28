"""round_body_ms (layer: round body): device milliseconds per round of the
round program outside the two kernel scopes: sample gather, K-means++
reseed, loop control, the kernels' callers. Counted over the executions of
the round program that lie wholly inside the traced slice."""
from bench import trace

KERNEL_SCOPES = ("kernel.assign", "kernel.update")


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    module = trace.main_module(tr)
    runs = trace.complete_runs(tr, module) if module else []
    if not runs:
        return None
    ops = trace.ops_in(tr, runs)
    outside = sum(o.dur for o in ops
                  if not any(trace.in_scope(o, s) for s in KERNEL_SCOPES))
    rounds = len(runs) * int(ctx.traffic["rounds_per_window"])
    return outside / 1e6 / rounds
