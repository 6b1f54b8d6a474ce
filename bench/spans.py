"""The program's own spans in a traced run (``repro.core.spans``).

spatterd writes flat spans on the profiler's clock: ``spatterd.parse``,
``spatterd.plan`` and ``spatterd.respond`` on the handler thread, and
``plan.draw``, ``plan.keep_mask``, ``plan.stack``, ``plan.to_device``,
``plan.warmup``, ``plan.runs``, ``plan.fetch`` and ``plan.digest`` on a
scheduler worker.  ``load`` keeps them among the trace's host events.

A span that is missing reads as missing (None), never as 0: a program
without spans, as before they existed, leaves the metrics out.
"""
from __future__ import annotations

from bench import reduce

PROGRAM = ("spatterd.", "plan.")


def per_request(ctx, names) -> float | None:
    """Seconds per completed request under the spans named ``names``:
    their durations, clipped to the window, summed, over the requests
    completed.  None without a trace, without such a span in the window,
    or without a completed request."""
    if ctx.trace is None:
        return None
    done = sum(r.ok for r in ctx.requests)
    lo, hi = ctx.trace.window
    spans = reduce.clip([e for e in ctx.trace.host if e.name in names],
                        lo, hi)
    if not done or not spans:
        return None
    return sum(b - a for a, b in spans) / 1e9 / done


def idle_under_spans(trace: reduce.Trace, prefixes=PROGRAM) -> float | None:
    """Share of the first device's idle time in the window that lies
    under a host span whose name starts with one of ``prefixes`` (0..1);
    None without a device or without idle time."""
    if not trace.devices:
        return None
    gaps = reduce.idle_gaps(trace, trace.devices[0])
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    lo, hi = trace.window
    covered = reduce.merge(reduce.clip(
        [e for e in trace.host if e.name.startswith(prefixes)], lo, hi))
    under, i = 0.0, 0
    for a, b in gaps:                 # both lists sorted and disjoint
        while i < len(covered) and covered[i][1] <= a:
            i += 1
        j = i
        while j < len(covered) and covered[j][0] < b:
            under += min(b, covered[j][1]) - max(a, covered[j][0])
            j += 1
    return under / idle
