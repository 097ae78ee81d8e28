"""update_roofline_pct (layer: kernels): the cluster-sum kernel's share of
its roofline, read as ``assign_roofline_pct`` is, under the
``kernel.update`` scope."""
from bench import trace

SCOPE = "kernel.update"


def work(s: int, k: int, d: int) -> tuple[int, int]:
    """(operations, bytes) one worker's call needs: one add per element of
    x, x and its labels read once, sums and counts written once."""
    return s * d, s * d * 4 + s * 4 + k * d * 4 + k * 4


def read(ctx):
    got = trace.kernel_share(ctx, SCOPE, work)
    return None if got is None else got[0]
