"""http_overhead_ms: mean over requests of the client's latency minus the
daemon's own ``elapsed_s`` (submit to resolve), in milliseconds: HTTP,
JSON, validation and planning outside the scheduler."""


def read(ctx):
    gaps = [r.latency_s - r.elapsed_s for r in ctx.requests
            if r.ok and r.elapsed_s is not None]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
