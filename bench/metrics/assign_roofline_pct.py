"""assign_roofline_pct (layer: kernels): the nearest-centroid kernel's
share of its roofline. The least time the chip needs for the work the
algorithm asks of each call, over the device time of every op under the
``kernel.assign`` scope (the wrapper's padding copies included), in the
round program's executions that lie wholly inside the traced slice."""
from bench import trace

SCOPE = "kernel.assign"


def work(s: int, k: int, d: int) -> tuple[int, int]:
    """(operations, bytes) one worker's call needs: an s x k x d distance
    product, and x, c read once and labels and distances written once."""
    return 2 * s * k * d, s * d * 4 + k * d * 4 + s * 8


def read(ctx):
    got = trace.kernel_share(ctx, SCOPE, work)
    return None if got is None else got[0]
