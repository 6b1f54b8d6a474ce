"""Regression tests for GSEngine reuse after dst donation.

The scatter executable donates its dst (engine.build); caching the
donated buffer in ``self._built`` made the SECOND ``run()`` on any
scatter engine — and ``sharded()`` after ``run()`` — die with
"buffer has been deleted or donated".  Repeated execution on one engine
is the serving regime, so every backend pins it here.
"""
import numpy as np
import pytest

from repro.core import GSEngine, make_pattern
from repro.core import backends as B
from repro.core.compat import make_mesh


def _scatter_pattern():
    # delta 2 < index span -> duplicate writes exercise the keep mask too
    return make_pattern("UNIFORM:4:2", kind="scatter", delta=2, count=16)


@pytest.mark.parametrize("backend", sorted(B.BACKENDS))
def test_scatter_run_twice(backend):
    eng = GSEngine(_scatter_pattern(), backend=backend)
    r1 = eng.run(runs=2)
    r2 = eng.run(runs=2)          # crashed before the fix
    assert r1.measured_gbs > 0 and r2.measured_gbs > 0


@pytest.mark.parametrize("backend", sorted(B.BACKENDS))
def test_scatter_sharded_after_run(backend):
    eng = GSEngine(_scatter_pattern(), backend=backend)
    eng.run(runs=1)
    mesh = make_mesh((1,), ("data",))
    fn, args = eng.sharded(mesh)   # crashed before the fix (stale dst)
    out1 = np.asarray(fn(*args))
    # and the sharded executable itself is reusable: build() hands out a
    # fresh dst every call, so a second launch sees zeros again
    fn2, args2 = eng.sharded(mesh)
    out2 = np.asarray(fn2(*args2))
    np.testing.assert_array_equal(out1, out2)


@pytest.mark.parametrize("backend", sorted(B.BACKENDS))
def test_scatter_rerun_results_identical(backend):
    # donation must not leak state between calls: a rerun starts from a
    # fresh zero dst, so store-mode results are bit-identical
    eng = GSEngine(_scatter_pattern(), backend=backend)
    fn, args = eng.build()
    out1 = np.asarray(fn(*args))
    fn, args = eng.build()
    out2 = np.asarray(fn(*args))
    np.testing.assert_array_equal(out1, out2)


def test_gather_run_twice():
    eng = GSEngine(make_pattern("UNIFORM:4:1", kind="gather", delta=4,
                                count=16), backend="xla")
    r1 = eng.run(runs=2)
    r2 = eng.run(runs=2)
    assert r1.measured_gbs > 0 and r2.measured_gbs > 0


@pytest.mark.parametrize("backend", sorted(B.BACKENDS))
def test_engine_add_mode(backend):
    # mode= reaches the executable: duplicate writes accumulate in add
    # mode and last-write-win in store mode
    p = make_pattern("BROADCAST:4:2", kind="scatter", delta=0, count=4)
    store = GSEngine(p, backend=backend, mode="store")
    add = GSEngine(p, backend=backend, mode="add")
    fn_s, args_s = store.build()
    fn_a, args_a = add.build()
    out_s = np.asarray(fn_s(*args_s))
    out_a = np.asarray(fn_a(*args_a))
    assert not np.array_equal(out_s, out_a)
    # add twice through fresh dsts stays deterministic
    fn_a, args_a = add.build()
    np.testing.assert_allclose(np.asarray(fn_a(*args_a)), out_a,
                               rtol=1e-6, atol=1e-6)


def test_engine_rejects_unknown_mode():
    with pytest.raises(ValueError):
        GSEngine(_scatter_pattern(), mode="max")
