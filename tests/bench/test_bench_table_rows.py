"""``table_rows_per_lane``: table rows drawn per useful lane, read from the
``rows=`` and ``lanes=`` ids of the ``plan.draw`` spans in a trace
recorded here on the CPU."""
import dataclasses
import os

import jax
import pytest

from bench import reduce, run
from bench.metrics import table_rows_per_lane as metric
from repro.core import ExecutorCache, SuitePlan, appdb, make_pattern
from repro.core import plan as plan_mod
from repro.core.spans import span

CELL = "pennant.sparse.xla"


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _ctx(path: str | None) -> run.Context:
    return run.Context(cell=run.load_cell(CELL), requests=[], window_s=1.0,
                       setup_s=1.0, peaks={},
                       trace=None if path is None else reduce.load(path))


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    return os.path.join(str(tmp_path), CELL)


def _patterns():
    # two gathers far apart in rows per lane
    return [make_pattern([0, 2, 0, 2], kind="gather", delta=4099, count=5,
                         name="sparse"),
            make_pattern([0, 1, 2, 3], kind="gather", delta=4, count=64,
                         name="dense")]


def _launch_under_trace(log_dir: str, patterns) -> str:
    """``patterns`` launched on xla inside a traced window; a draw
    span with both ids lies before the window and must not count."""
    work = plan_mod.make_work(SuitePlan.build(patterns), backend="xla",
                              runs=1)
    with jax.profiler.trace(log_dir, profiler_options=_options()):
        with span("plan.draw", None, lanes=1, rows=10**6):
            pass
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for w in work:
                plan_mod.launch([w], ExecutorCache())
    return reduce.find_xplane(log_dir)


def test_reads_the_ratio_of_a_traced_launch(trace_dir):
    pats = _patterns()
    path = _launch_under_trace(trace_dir, pats)
    lo, hi = reduce.load(path).window
    ids = metric.draw_ids(path, lo, hi)
    want = [{"lanes": p.useful_elements(), "rows": p.footprint()}
            for p in pats]
    assert sorted(ids, key=lambda d: d["rows"]) \
        == sorted(want, key=lambda d: d["rows"])
    assert run.reader("table_rows_per_lane")(_ctx(path)) == pytest.approx(
        sum(p.footprint() for p in pats)
        / sum(p.useful_elements() for p in pats))


def test_draw_span_rows_is_the_footprint(trace_dir):
    # G8's 15-offset buffer at 33 ops: 495 lanes over 4,147,459 rows
    g8 = dataclasses.replace(appdb.PENNANT_GATHERS[8], count=33)
    path = _launch_under_trace(trace_dir, [g8])
    lo, hi = reduce.load(path).window
    assert metric.draw_ids(path, lo, hi) == [
        {"lanes": 495, "rows": g8.footprint()}]
    assert g8.footprint() == 4_147_459


def test_none_without_the_rows_id(trace_dir):
    read = run.reader("table_rows_per_lane")
    assert read(_ctx(None)) is None
    with jax.profiler.trace(trace_dir, profiler_options=_options()):
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            with span("plan.draw", None, launch=1, lanes=64):
                pass
    path = reduce.find_xplane(trace_dir)
    assert metric.draw_ids(path, *reduce.load(path).window) == [
        {"lanes": 64}]
    assert read(_ctx(path)) is None
    assert metric.ratio([]) is None
    assert metric.ratio([{"rows": 5, "lanes": 0}]) is None


def test_none_without_a_trace_file(trace_dir, tmp_path):
    path = _launch_under_trace(str(tmp_path / "elsewhere"), _patterns())
    assert run.reader("table_rows_per_lane")(_ctx(path)) is None
