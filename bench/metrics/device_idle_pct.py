"""device_idle_pct: share of the traced window in which no op ran on the
device, in percent: 100 * (1 - union of device-op intervals / window)."""
from bench import reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - reduce.busy_s(ctx.trace) / ctx.trace.window_s)
