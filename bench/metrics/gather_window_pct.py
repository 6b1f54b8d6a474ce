"""gather_window_pct: share of the pallas line gather's grid steps that
fetched one window of lines rather than one line per element, in
percent: 100 * sum of window steps / sum of grid steps over the
``window=<window steps>;<grid steps>`` ids of the ``plan.runs`` spans
in the traced window (``gather_rows.ops.window_steps``, one span per
launch).  None where no span carries the id: no pallas gather ran, or
the program predates the counter."""
import os

from bench import reduce, run

SPAN = "plan.runs"


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        path = reduce.find_xplane(os.path.join(run.TRACE_DIR, ctx.cell.name))
    except ValueError:
        return None
    return share(window_ids(path, *ctx.trace.window))


def window_ids(path: str, lo: float, hi: float) -> list[str]:
    """The ``window`` ids of the ``plan.runs`` spans that start in
    [lo, hi) ns."""
    from jax.profiler import ProfileData
    ids = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN and lo <= ev.start_ns < hi:
                    ids.extend(str(v) for k, v in ev.stats if k == "window")
    return ids


def share(ids: list[str]) -> float | None:
    """100 * sum of hits / sum of steps over ``<hits>;<steps>`` ids;
    None without a step."""
    hits = steps = 0
    for s in ids:
        h, n = s.split(";")
        hits += int(h)
        steps += int(n)
    return 100.0 * hits / steps if steps else None
