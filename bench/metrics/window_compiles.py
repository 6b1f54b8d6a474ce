"""window_compiles: executables compiled inside the window, summed from
the responses' ``cache.misses`` (spatterd's exact compile count)."""


def read(ctx):
    misses = [r.misses for r in ctx.requests if r.ok]
    return sum(misses) if misses else None
