"""The program's spans (``repro.core.spans``) in a profiler trace recorded
here on the CPU, and the per-layer metrics that read them
(``bench/spans.py``, ``bench/metrics/``).

An in-process spatterd serves one gather and one store scatter at count
64, each request under a trace of its own; the spans must carry the
exact names the metrics read, lie flat on every thread, cover no wait,
and agree with the response's ``serve.phases_ms``.
"""
import dataclasses
import json
import os
import time

import jax
import pytest

from bench import reduce, run, spans
from bench.reduce import Event, Trace
from repro.core.spans import span

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
S = 1e9     # ns per second
SEED = 3_000_000_019
OPTIONS = {"backend": "xla", "mode": "store", "runs": 3, "digest": True}
HANDLER = {"spatterd.parse", "spatterd.plan", "spatterd.respond"}
WORKER = {"plan.draw", "plan.stack", "plan.to_device", "plan.warmup",
          "plan.runs", "plan.fetch", "plan.digest"}
READERS = {"assemble_s", "keep_mask_s", "transfer_s", "digest_s",
           "warmup_s", "daemon_ms"}


def _patterns():
    with open(os.path.join(ROOT, "bench", "configs", "lulesh.json")) as f:
        pats = {p["name"]: dict(p, count=64)
                for p in json.load(f)["patterns"]}
    return {"gather": pats["LULESH-G0"], "scatter": pats["LULESH-S0"]}


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@dataclasses.dataclass
class Served:
    doc: dict            # the /run response
    path: str            # its .xplane.pb
    trace: Trace         # reduce.load of it


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.core.plan import ExecutorCache
    from repro.serve.client import SpatterClient
    from repro.serve.daemon import SpatterDaemon
    out = {}
    with SpatterDaemon("127.0.0.1", 0, cache=ExecutorCache()) as daemon:
        client = SpatterClient(daemon.url, timeout=120)
        t0 = time.perf_counter()
        while not client.readyz().get("ready"):
            assert time.perf_counter() - t0 < 60, "spatterd never ready"
            time.sleep(0.05)
        for kind, pat in _patterns().items():
            client.warm([pat], backend="xla", mode="store", runs=3)
            log_dir = str(tmp_path_factory.mktemp(kind))
            with jax.profiler.trace(log_dir,
                                    profiler_options=_profile_options()):
                with jax.profiler.TraceAnnotation(reduce.WINDOW):
                    with jax.profiler.TraceAnnotation(
                            f"{reduce.REQUEST} {pat['name']}"):
                        doc = client.run_suite([pat], seed=SEED, **OPTIONS)
            path = reduce.find_xplane(log_dir)
            out[kind] = Served(doc, path, reduce.load(path))
    return out


def _program_events_by_thread(path):
    """The program's spans of each host thread (one profiler line each),
    in order of start."""
    from jax.profiler import ProfileData
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted((Event(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(spans.PROGRAM)),
                         key=lambda e: e.start)
            if evs:
                threads.append(evs)
    return threads


def _ctx(s: Served, trace: Trace | None = None) -> run.Context:
    pat = _patterns()["gather" if s.doc["stats"]["table"][0]["kind"]
                      == "gather" else "scatter"]
    req = run.Request(pat, OPTIONS["runs"], 1.0, elapsed_s=0.5, misses=0,
                      digest=s.doc["stats"]["table"][0]["digest"])
    cell = run.load_cell("lulesh.scatter.xla")
    return run.Context(cell=cell, requests=[req], window_s=1.0, setup_s=1.0,
                       peaks={}, trace=s.trace if trace is None else trace)


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_every_span_appears_by_its_exact_name(served, kind):
    s = served[kind]
    names = {e.name for e in s.trace.host
             if e.name.startswith(spans.PROGRAM)}
    want = HANDLER | WORKER | ({"plan.keep_mask"} if kind == "scatter"
                               else set())
    assert names == want
    rid = s.doc["request_id"]
    assert isinstance(rid, int) and rid >= 1


def test_request_ids_differ(served):
    assert served["gather"].doc["request_id"] \
        != served["scatter"].doc["request_id"]


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_spans_are_flat_on_every_thread(served, kind):
    threads = _program_events_by_thread(served[kind].path)
    assert len(threads) >= 2          # a handler and a worker
    for evs in threads:
        for prev, nxt in zip(evs, evs[1:]):
            assert nxt.start >= prev.end, (prev, nxt)


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_spans_cover_no_wait(served, kind):
    """The handler waits on its ticket while a worker launches: a handler
    span over that wait would overlap the worker's spans, and a worker
    span over its queue wait the handler's.  The submit's notify may let
    the worker start within the instant the handler's span closes."""
    host = served[kind].trace.host
    handler = [e for e in host if e.name in HANDLER]
    worker = [e for e in host if e.name.startswith("plan.")]
    busy = sum(e.end - e.start for e in worker)
    assert busy > 0
    for h in handler:
        overlap = sum(max(0.0, min(h.end, w.end) - max(h.start, w.start))
                      for w in worker)
        assert overlap < min(0.5e6, busy / 4), h


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_response_phases_match_the_trace(served, kind):
    s = served[kind]
    phases = s.doc["serve"]["phases_ms"]
    want = WORKER | ({"plan.keep_mask"} if kind == "scatter" else set())
    assert set(phases) == want
    for name, ms in phases.items():
        traced = sum(e.end - e.start for e in s.trace.host
                     if e.name == name) / 1e6
        assert traced > 0
        assert abs(ms - traced) <= max(2.0, 0.2 * traced), (name, ms, traced)


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_readers_read_the_spans(served, kind):
    ctx = _ctx(served[kind])
    got = {name: run.reader(name)(ctx) for name in READERS}
    for name, value in got.items():
        if name == "keep_mask_s" and kind == "gather":
            assert value is None
        else:
            assert value is not None and value > 0, name


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_readers_miss_without_program_spans(served, kind):
    t = served[kind].trace
    bare = dataclasses.replace(t, host=[e for e in t.host if not
                                        e.name.startswith(spans.PROGRAM)])
    ctx = _ctx(served[kind], bare)
    for name in READERS:
        assert run.reader(name)(ctx) is None, name
    ctx.trace = None
    for name in READERS:
        assert run.reader(name)(ctx) is None, name
    ctx.trace = t
    ctx.requests = [dataclasses.replace(ctx.requests[0], error="refused")]
    for name in READERS:
        assert run.reader(name)(ctx) is None, name


def test_per_request_clips_to_the_window():
    host = [Event(reduce.WINDOW, 1 * S, 9 * S),
            Event("plan.draw", 0, 2 * S),             # 1 s inside
            Event("plan.draw", 3 * S, 4 * S),
            Event("plan.stack", 8 * S, 10 * S),       # 1 s inside
            Event("plan.draw", 9.5 * S, 10 * S)]      # outside
    t = Trace(ops=[], modules=[], host=host, devices=[0])
    reqs = [run.Request({}, 10, 1.0), run.Request({}, 10, 1.0),
            run.Request({}, 10, 1.0, error="503")]
    ctx = run.Context(cell=None, requests=reqs, window_s=8.0, setup_s=1.0,
                      peaks={}, trace=t)
    assert spans.per_request(ctx, {"plan.draw"}) == pytest.approx(1.0)
    assert spans.per_request(ctx, {"plan.draw", "plan.stack"}) \
        == pytest.approx(1.5)
    assert spans.per_request(ctx, {"plan.fetch"}) is None


def test_idle_under_spans_by_hand():
    host = [Event(reduce.WINDOW, 0, 10 * S),
            Event(reduce.REQUEST + " P", 0, 10 * S),  # not a program span
            Event("plan.draw", 0, 1.5 * S),
            Event("plan.to_device", 1.5 * S, 2 * S),
            Event("spatterd.respond", 8.5 * S, 9.5 * S)]
    ops = [Event("op", 2 * S, 8 * S)]
    t = Trace(ops=ops, modules=[], host=host, devices=[0])
    # idle: [0, 2] all under spans, [8, 10] half: 3 of 4 s
    assert spans.idle_under_spans(t) == pytest.approx(0.75)
    assert spans.idle_under_spans(
        Trace(ops=[Event("op", 0, 10 * S)], modules=[], host=host,
              devices=[0])) is None
    assert spans.idle_under_spans(dataclasses.replace(t, devices=[])) \
        is None


@pytest.mark.parametrize("kind", ["gather", "scatter"])
def test_idle_on_the_recorded_trace_is_named(served, kind):
    t = served[kind].trace
    share = spans.idle_under_spans(t)
    assert share is not None and 0 < share <= 1
    names = [name for name, _ in reduce.longest_gaps(t)]
    assert any(n.startswith(spans.PROGRAM) for n in names), names


def test_span_sink_accumulates_milliseconds():
    sink = {}
    for _ in range(2):
        with span("plan.draw", sink, launch=1, lanes=64):
            time.sleep(0.01)
    with span("plan.fetch", None, bytes=4):
        pass
    assert set(sink) == {"plan.draw"}
    assert 20.0 <= sink["plan.draw"] < 2000.0


def test_span_records_nothing_without_a_profiler(tmp_path):
    for _ in range(1000):
        with span("plan.idle_probe", {}, req=1):
            pass
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=_profile_options()):
        with span("plan.traced_probe"):
            pass
    t = reduce.load(reduce.find_xplane(str(tmp_path)))
    names = [e.name for e in t.host]
    assert names.count("plan.traced_probe") == 1
    assert "plan.idle_probe" not in names
