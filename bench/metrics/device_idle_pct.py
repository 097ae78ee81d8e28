"""device_idle_pct (layer: device): the share of the traced slice in which
no operation ran on the device, averaged over the chips used."""
from bench import trace


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_ns <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s(tr) * 1e9 / tr.window_ns)
