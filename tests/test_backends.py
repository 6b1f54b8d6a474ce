"""Cross-backend equivalence — hypothesis property tests.

The paper's four backends must be bit-compatible up to dtype rounding:
gather is exact; scatter-add is compared with tolerance (summation order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dep: property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import backends as B

BACKENDS = list(B.BACKENDS)


def _src(f, r, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((f, r)), jnp.float32)


@st.composite
def gather_case(draw):
    f = draw(st.integers(4, 200))
    r = draw(st.sampled_from([1, 2, 8, 128]))
    n = draw(st.integers(1, 300))
    idx = draw(st.lists(st.integers(0, f - 1), min_size=n, max_size=n))
    return f, r, np.asarray(idx, np.int32)


@settings(max_examples=25, deadline=None)
@given(gather_case())
def test_gather_backends_agree(case):
    f, r, idx = case
    src = _src(f, r)
    ref = np.asarray(src)[idx]
    for b in BACKENDS:
        out = B.gather(src, jnp.asarray(idx), backend=b)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6,
                                   err_msg=f"backend={b}")


@settings(max_examples=25, deadline=None)
@given(gather_case())
def test_scatter_add_backends_agree(case):
    f, r, idx = case
    vals = _src(len(idx), r, seed=1)
    dst = jnp.zeros((f, r), jnp.float32)
    ref = np.zeros((f, r), np.float32)
    np.add.at(ref, idx, np.asarray(vals))
    for b in BACKENDS:
        out = B.scatter(dst, jnp.asarray(idx), vals, mode="add", backend=b)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5, err_msg=f"backend={b}")


@settings(max_examples=15, deadline=None)
@given(gather_case())
def test_scatter_store_last_write_wins(case):
    """Store semantics are pinned to deterministic last-write-wins on every
    backend (the paper leaves duplicate order unspecified; we don't)."""
    f, r, idx = case
    vals = _src(len(idx), r, seed=2)
    dst = _src(f, r, seed=3)
    ref = np.asarray(dst).copy()
    for i, j in enumerate(idx):            # sequential = last write wins
        ref[j] = np.asarray(vals)[i]
    for b in BACKENDS:
        out = B.scatter(dst, jnp.asarray(idx), vals, mode="store", backend=b)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6,
                                   err_msg=f"backend={b}")


def test_onehot_guard():
    big = jnp.zeros((B._ONEHOT_MAX_FOOTPRINT + 1, 1))
    with pytest.raises(ValueError):
        B.gather_onehot(big, jnp.zeros((4,), jnp.int32))


def test_engine_end_to_end():
    from repro.core import GSEngine, make_pattern
    p = make_pattern("UNIFORM:8:2", kind="gather", delta=4, count=64)
    for b in BACKENDS:
        r = GSEngine(p, backend=b).run(runs=2)
        assert r.measured_gbs > 0
        assert r.time_s > 0
    ps = make_pattern("UNIFORM:8:2", kind="scatter", delta=4, count=64)
    r = GSEngine(ps, backend="xla").run(runs=2)
    assert r.measured_gbs > 0


def test_sharded_engine_subprocess():
    """GSEngine.sharded(): the count dim splits over the data axis (the
    paper's OpenMP-thread dimension) — 8 fake devices, subprocess."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        from repro.core.compat import make_mesh
        import jax, numpy as np
        from repro.core import GSEngine, make_pattern

        mesh = make_mesh((8,), ("data",))
        p = make_pattern("UNIFORM:8:2", kind="gather", delta=16, count=128)
        eng = GSEngine(p, backend="xla")
        fn, args = eng.sharded(mesh, "data")
        out = fn(*args)
        # oracle: unsharded gather
        src, idx = args
        ref = np.asarray(src)[np.asarray(idx)]
        assert np.allclose(np.asarray(out), ref)
        # sharded scatter keeps build()'s store (last-write-wins) semantics,
        # including on duplicate indices (delta 4 < span 15 -> overlaps)
        import jax.numpy as jnp
        from repro.core import backends as B
        ps = make_pattern("UNIFORM:8:2", kind="scatter", delta=4, count=128)
        engs = GSEngine(ps, backend="xla")
        fns, argss = engs.sharded(mesh, "data")
        dst, idx, vals, keep = argss
        outs = fns(dst, idx, vals, keep)
        ref = np.asarray(B.scatter(jnp.zeros_like(dst), idx, vals,
                                   mode="store", backend="xla"))
        assert np.array_equal(np.asarray(outs), ref)
        print("OK")
    """) % os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
