"""``gather_window_pct``: the pallas line gather's share of grid steps that
fetched one window of lines, read from the ``window=<hits>;<steps>`` id
of the ``plan.runs`` spans in a trace recorded here on the CPU."""
import os

import jax
import pytest

from bench import reduce, run
from bench.metrics import gather_window_pct as metric
from repro.core import ExecutorCache, SuitePlan, make_pattern
from repro.core import plan as plan_mod
from repro.core.spans import span
from repro.kernels.gather_rows import ops as gather_ops

CELL = "lulesh.gather.pallas"


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _record(log_dir: str, ids: list[dict]) -> str:
    """A trace whose window holds one ``plan.runs`` span per entry of
    ``ids``, each with those ids; one more span with an id lies before
    the window and must not count."""
    with jax.profiler.trace(log_dir, profiler_options=_options()):
        with span("plan.runs", None, window="7;7"):
            pass
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for kw in ids:
                with span("plan.runs", None, launch=1, **kw):
                    pass
    return reduce.find_xplane(log_dir)


def _ctx(path: str | None) -> run.Context:
    return run.Context(cell=run.load_cell(CELL), requests=[], window_s=1.0,
                       setup_s=1.0, peaks={},
                       trace=None if path is None else reduce.load(path))


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    return os.path.join(str(tmp_path), CELL)


def test_sums_the_ids_over_requests(trace_dir):
    path = _record(trace_dir, [{"window": "3;4"}, {"window": "5;12"}, {}])
    lo, hi = reduce.load(path).window
    assert sorted(metric.window_ids(path, lo, hi)) == ["3;4", "5;12"]
    assert run.reader("gather_window_pct")(_ctx(path)) \
        == pytest.approx(100.0 * 8 / 16)


def test_none_without_the_id(trace_dir):
    read = run.reader("gather_window_pct")
    assert read(_ctx(None)) is None
    path = _record(trace_dir, [{}, {"reqs": "1;2"}])
    assert read(_ctx(path)) is None
    assert metric.share([]) is None
    assert metric.share(["0;0"]) is None


def test_none_without_a_trace_file(trace_dir, tmp_path):
    path = _record(str(tmp_path / "elsewhere"), [{"window": "1;1"}])
    assert run.reader("gather_window_pct")(_ctx(path)) is None


def _launch_under_trace(log_dir: str, backend: str):
    # element 2^20 puts the table past the VMEM regime's 4 MiB, so the
    # pallas gather takes the DMA regime; every step reaches both ends
    pat = make_pattern([0, 1, 2, 1 << 20], kind="gather", delta=1,
                       count=512, name="far")
    work = plan_mod.make_work(SuitePlan.build([pat]), backend=backend,
                              runs=1)
    with jax.profiler.trace(log_dir, profiler_options=_options()):
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            plan_mod.launch(work, ExecutorCache())
    return pat, reduce.find_xplane(log_dir)


def test_a_traced_pallas_gather_launch_carries_the_id(tmp_path):
    pat, path = _launch_under_trace(str(tmp_path / "p"), "pallas")
    lo, hi = reduce.load(path).window
    ids = metric.window_ids(path, lo, hi)
    idx = pat.absolute_indices().reshape(1, -1)
    rows = SuitePlan.build([pat]).buckets[0].spec.footprint + 1
    hits, steps = gather_ops.window_steps(idx, rows, 1)
    assert steps > 0
    assert ids == [f"{hits};{steps}"]
    _, path = _launch_under_trace(str(tmp_path / "x"), "xla")
    lo, hi = reduce.load(path).window
    assert metric.window_ids(path, lo, hi) == []
