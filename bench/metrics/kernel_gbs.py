"""kernel_gbs: the paper's §3.5 bandwidth from device time.

The useful bytes of every execution of each request's program that the
trace shows (the warm-up call's too, since its device time is in the
denominator) over the device seconds of the programs that ran in the
traced window, in GB/s.
"""
from bench import reduce, work


def read(ctx):
    if ctx.trace is None:
        return None
    device_s = reduce.program_s(ctx.trace)
    done = [r for r in ctx.requests if r.ok and r.executions]
    if device_s <= 0 or not done:
        return None
    useful = sum(work.useful_bytes(r.pattern, ctx.cell.row_width)
                 * r.executions for r in done)
    return useful / device_s / 1e9
