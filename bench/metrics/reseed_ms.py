"""reseed_ms (layer: round body): device milliseconds per round under the
round program's ``round.reseed`` scope (``core/strategies.py``), the
K-means++ redraw of degenerate centroids. Counted as ``round_body_ms`` is,
over the executions of the round program wholly inside the traced slice;
a part of ``round_body_ms``. The scope is opened inside the ``vmap`` over
workers, so JAX names it ``vmap(round.reseed)`` in the op's scope path."""
from bench import trace

SCOPE = "round.reseed"


def _under_scope(op) -> bool:
    """``SCOPE`` is a part of the op's scope path, bare or wrapped by a
    transformation, as in ``vmap(round.reseed)``."""
    return any(p.rstrip(")").rsplit("(", 1)[-1] == SCOPE
               for p in op.scope.split("/"))


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    module = trace.main_module(tr)
    runs = trace.complete_runs(tr, module) if module else []
    ops = [o for o in trace.ops_in(tr, runs) if _under_scope(o)]
    if not ops:
        return None
    rounds = len(runs) * int(ctx.traffic["rounds_per_window"])
    return sum(o.dur for o in ops) / 1e6 / rounds
