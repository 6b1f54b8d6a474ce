"""From a JAX profiler trace to the numbers the per-layer metrics read.

The traced run writes one ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` and keeps three kinds of event:

- device ops: the "XLA Ops" line of each ``/device:TPU:<n>`` plane;
- device programs: the "XLA Modules" line of the same planes, one event
  per executed program;
- host events: every event on a host thread (``/host:CPU``), among them
  the benchmark's own annotations ``bench.window`` (the measured window)
  and ``bench.request ...`` (one per request).

A trace recorded on the CPU has no device plane: there the XLA ops run on
host threads and carry an ``hlo_op`` stat, and ``load`` takes those as
the ops of device 0 so that the reduction can be tested without a chip.
The benchmark itself never reports a CPU run.

Everything after ``load`` works on plain intervals in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
REQUEST = "bench.request"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns, on the trace's one clock
    end: float
    device: int = 0


@dataclasses.dataclass
class Trace:
    ops: list[Event]
    modules: list[Event]
    host: list[Event]
    devices: list[int]

    @property
    def window(self) -> tuple[float, float]:
        spans = [e for e in self.host if e.name == WINDOW]
        if len(spans) != 1:
            raise ValueError(f"trace holds {len(spans)} {WINDOW!r} spans, "
                             f"expected 1")
        return spans[0].start, spans[0].end

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def requests(self) -> list[Event]:
        """The request spans, in order of start."""
        return sorted((e for e in self.host if e.name.startswith(REQUEST)),
                      key=lambda e: e.start)


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"{len(found)} xplane files under {log_dir}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host, cpu_ops = [], [], [], []
    devices = set()
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            devices.add(dev)
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend(_events(line, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    e = Event(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                    host.append(e)
                    if any(k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append(e)
    if not devices and cpu_ops:
        ops, devices = cpu_ops, {0}
    return Trace(ops=ops, modules=modules, host=host,
                 devices=sorted(devices))


def _events(line, dev: int) -> list[Event]:
    return [Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dev)
            for ev in line.events]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which some op ran, averaged over devices."""
    lo, hi = trace.window
    if not trace.devices:
        return 0.0
    total = 0.0
    for dev in trace.devices:
        total += sum(b - a for a, b in merge(
            clip([e for e in trace.ops if e.device == dev], lo, hi)))
    return total / len(trace.devices) / 1e9


def program_s(trace: Trace) -> float:
    """Device seconds of the programs executed in the window, summed over
    devices (each program's execution once per device it ran on)."""
    lo, hi = trace.window
    return sum(b - a for a, b in clip(trace.modules, lo, hi)) / 1e9


def executions(trace: Trace) -> list[int]:
    """For each request span, in order of start: how many times its
    program ran on the first device.  The program is the one with the
    most device seconds among the "XLA Modules" events whose midpoint
    falls in the span; other programs (a zero-fill, a copy) do not
    count."""
    if not trace.devices:
        return [0 for _ in trace.requests()]
    mods = [e for e in trace.modules if e.device == trace.devices[0]]
    out = []
    for r in trace.requests():
        runs: dict[str, list[float]] = {}
        for e in mods:
            if r.start <= (e.start + e.end) / 2 < r.end:
                runs.setdefault(e.name, []).append(e.end - e.start)
        out.append(len(max(runs.values(), key=sum)) if runs else 0)
    return out


def idle_gaps(trace: Trace, device: int) -> list[tuple[float, float]]:
    """Intervals of the window in which ``device`` ran no op."""
    lo, hi = trace.window
    busy = merge(clip([e for e in trace.ops if e.device == device], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def top_ops(trace: Trace, k: int = 10, width: int = 160) -> list[list]:
    """The ``k`` ops with the most device seconds in the window, each
    named by the first ``width`` characters of its HLO text."""
    lo, hi = trace.window
    by_name: dict[str, float] = {}
    for e in trace.ops:
        if e.end > lo and e.start < hi:
            s = (min(e.end, hi) - max(e.start, lo)) / 1e9
            by_name[e.name] = by_name.get(e.name, 0.0) + s
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name[:width], s] for name, s in ranked]


def name_gap(trace: Trace, gap: tuple[float, float]) -> str:
    """What the host was doing in an idle gap: the host event that
    overlaps it most, when that covers half of it or more; otherwise the
    request it falls in, as untraced host work."""
    a, b = gap
    best, best_overlap = None, 0.0
    for e in trace.host:
        if e.name == WINDOW or e.name.startswith(REQUEST):
            continue
        overlap = min(e.end, b) - max(e.start, a)
        if overlap > best_overlap:
            best, best_overlap = e, overlap
    if best is not None and best_overlap >= (b - a) / 2:
        return best.name
    mid = (a + b) / 2
    for e in trace.requests():
        if e.start <= mid < e.end:
            return f"{e.name[len(REQUEST):].strip()}: untraced host work"
    return "between requests"


def longest_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps of the first device, each named."""
    if not trace.devices:
        return []
    gaps = sorted(idle_gaps(trace, trace.devices[0]),
                  key=lambda g: g[0] - g[1])[:k]
    return [[name_gap(trace, g), (g[1] - g[0]) / 1e9] for g in gaps]
