"""bench/reduce.py on intervals made by hand and on a profiler trace
recorded here on the CPU (where XLA's ops run on host threads)."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import reduce
from bench.reduce import Event, Trace

S = 1e9     # ns per second


def _trace(ops, modules=(), host=(), devices=(0,)):
    host = [Event(reduce.WINDOW, 0, 10 * S)] + list(host)
    return Trace(ops=list(ops), modules=list(modules), host=host,
                 devices=list(devices))


def test_merge_and_busy():
    assert reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    t = _trace([Event("a", 1 * S, 3 * S), Event("b", 2 * S, 4 * S),
                Event("c", 9 * S, 12 * S),            # clipped at 10 s
                Event("d", 0, 5 * S, device=1)], devices=(0, 1))
    # device 0: [1, 4] + [9, 10] = 4 s; device 1: 5 s; mean 4.5 s
    assert reduce.busy_s(t) == pytest.approx(4.5)
    assert t.window_s == pytest.approx(10.0)
    assert reduce.idle_gaps(t, 0) == [(0, 1 * S), (4 * S, 9 * S)]


def test_program_seconds_and_top_ops():
    t = _trace([Event("gather", 0, 2 * S), Event("gather", 3 * S, 4 * S),
                Event("copy", 5 * S, 5.5 * S)],
               modules=[Event("jit_fn", 0, 4.5 * S),
                        Event("jit_fn", 9 * S, 11 * S)])
    assert reduce.program_s(t) == pytest.approx(5.5)
    assert reduce.top_ops(t) == [["gather", pytest.approx(3.0)],
                                 ["copy", pytest.approx(0.5)]]


def test_executions_count_the_request_program():
    req = reduce.REQUEST
    host = [Event(f"{req} B", 5 * S, 9 * S), Event(f"{req} A", 0, 5 * S)]
    modules = ([Event("jit_fn(1)", k * S, k * S + 0.4 * S) for k in range(3)]
               + [Event("jit_zeros(2)", 3 * S, 3.1 * S),
                  Event("jit_zeros(2)", 3.2 * S, 3.3 * S),
                  Event("jit_zeros(2)", 3.4 * S, 3.5 * S),
                  Event("jit_zeros(2)", 3.6 * S, 3.7 * S),
                  # straddles the boundary: counts where its middle lies
                  Event("jit_fn(1)", 4.7 * S, 5.1 * S),
                  Event("jit_fn(7)", 6 * S, 6.5 * S),
                  Event("jit_fn(7)", 6.6 * S, 7.1 * S),
                  Event("jit_fn(7)", 7.2 * S, 7.7 * S, device=1)])
    t = _trace([], modules=modules, host=host, devices=(0, 1))
    assert [e.name for e in t.requests()] == [f"{req} A", f"{req} B"]
    # A: jit_fn(1) four times, more device time than the four zero-fills;
    # B: jit_fn(7) twice on the first device
    assert reduce.executions(t) == [4, 2]
    assert reduce.executions(_trace([], host=host)) == [0, 0]


def test_gaps_are_named_by_the_host():
    host = [Event(reduce.REQUEST + " LULESH-G7", 0, 6 * S),
            Event("TransferToDevice", 4.2 * S, 5 * S)]
    t = _trace([Event("op", 4 * S, 4.2 * S), Event("op", 5 * S, 10 * S)],
               host=host)
    gaps = reduce.longest_gaps(t)
    assert gaps == [["LULESH-G7: untraced host work", pytest.approx(4.0)],
                    ["TransferToDevice", pytest.approx(0.8)]]
    assert reduce.name_gap(t, (6.5 * S, 7 * S)) == "between requests"


def test_window_must_be_unique():
    t = Trace(ops=[], modules=[], host=[], devices=[0])
    with pytest.raises(ValueError):
        t.window


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda a, i: a[i] * 2.0)
    a = jnp.arange(1 << 16, dtype=jnp.float32)
    i = (jnp.arange(1 << 14) * 7) % (1 << 16)
    f(a, i).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for k in range(3):
                with jax.profiler.TraceAnnotation(f"{reduce.REQUEST} P{k}"):
                    f(a, i).block_until_ready()
                    time.sleep(0.05)
    t = reduce.load(reduce.find_xplane(str(tmp_path)))
    assert t.devices == [0]
    assert [e.name for e in t.requests()] == [
        f"{reduce.REQUEST} P{k}" for k in range(3)]
    assert 0.15 <= t.window_s < 5.0
    names = {e.name for e in t.ops}
    assert len(t.ops) % 3 == 0 and t.ops
    busy = reduce.busy_s(t)
    assert 0 < busy < t.window_s
    gaps = reduce.idle_gaps(t, 0)
    assert sum(b - a for a, b in gaps) / S + busy == pytest.approx(
        t.window_s, rel=1e-6)
    top = reduce.top_ops(t)
    assert {n for n, _ in top} <= names
    label, seconds = reduce.longest_gaps(t)[0]
    assert label.endswith("untraced host work") and seconds >= 0.04
