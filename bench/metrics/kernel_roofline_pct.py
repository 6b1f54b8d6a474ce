"""kernel_roofline_pct: the device programs' share of the HBM roofline.

The least bytes one execution needs (``bench/work.py``, from the pattern
alone), times the executions of each request's program that the trace
shows, over the chip's peak HBM bandwidth is the least time the work
could take; divided by the device seconds of the programs that ran in
the traced window, in percent.  Host-device transfers are not programs
and do not count.
"""
from bench import reduce, work


def read(ctx):
    if ctx.trace is None or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    device_s = reduce.program_s(ctx.trace)
    done = [r for r in ctx.requests if r.ok and r.executions]
    if device_s <= 0 or not done:
        return None
    least = sum(work.least_bytes(r.pattern, ctx.cell.row_width)
                * r.executions for r in done)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / device_s
