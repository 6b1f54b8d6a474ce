"""table_rows_per_lane: table rows the host drew per useful lane: sum of
the ``rows=`` ids (a member's table rows, its pattern's footprint) over
sum of the ``lanes=`` ids of the ``plan.draw`` spans that start in the
traced window (one span per member of a launch).  None where no span
carries ``rows``: no trace, or a program that predates the id."""
import os

from bench import reduce, run

SPAN = "plan.draw"


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        path = reduce.find_xplane(os.path.join(run.TRACE_DIR, ctx.cell.name))
    except ValueError:
        return None
    return ratio(draw_ids(path, *ctx.trace.window))


def draw_ids(path: str, lo: float, hi: float) -> list[dict]:
    """The ids of the ``plan.draw`` spans that start in [lo, hi) ns, one
    dict of name -> int per span."""
    from jax.profiler import ProfileData
    ids = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN and lo <= ev.start_ns < hi:
                    ids.append({k: int(v) for k, v in ev.stats
                                if k in ("rows", "lanes")})
    return ids


def ratio(ids: list[dict]) -> float | None:
    """Sum of ``rows`` over sum of ``lanes``, over the spans that carry
    both; None without such a span or without a lane."""
    both = [d for d in ids if "rows" in d and "lanes" in d]
    lanes = sum(d["lanes"] for d in both)
    return sum(d["rows"] for d in both) / lanes if lanes else None
