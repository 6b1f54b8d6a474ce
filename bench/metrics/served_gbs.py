"""served_gbs: the paper's §3.5 useful bytes served per second, end to end.

Useful bytes (``index_len * count * 4`` per pattern) times the request's
``runs``, summed over every request completed in the window, over the
window's seconds on the client's clock.  Host assembly, dispatch, the
warm-up call, the digest and the kernels all fall inside it.
"""
from bench import work


def read(ctx):
    done = [r for r in ctx.requests if r.ok]
    if not done or ctx.window_s <= 0:
        return None
    moved = sum(work.useful_bytes(r.pattern, ctx.cell.row_width) * r.runs
                for r in done)
    return moved / ctx.window_s / 1e9
