"""Sharded bucket launches + batch-polymorphic executor cache (plan.py).

In-process tests use a 1-device mesh (conftest pins the suite to one
device); the 8-fake-device acceptance run — sharded results bit-identical
to the single-device planner, membership changes compiling nothing —
happens in a subprocess with its own XLA_FLAGS, like the dry-run tests.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (ExecutorCache, ShardedExecutor, SuitePlan,
                        execute_bucket, gs_shardings, make_pattern,
                        pad_batch, run_suite)
from repro.core import backends as B
from repro.core.compat import make_mesh
from repro.core.plan import ExecKey

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _suite(n_gather=4, n_scatter=4, count=32):
    pats = []
    for i in range(n_gather):
        pats.append(make_pattern(f"UNIFORM:8:{i + 1}", kind="gather",
                                 delta=8, count=count, name=f"g{i}"))
    for i in range(n_scatter):
        pats.append(make_pattern(f"UNIFORM:8:{i + 1}", kind="scatter",
                                 delta=8, count=count, name=f"s{i}"))
    return pats


def _mesh1():
    return make_mesh((1,), ("data",))


# ---------------------------------------------------------------------------
# batch padding
# ---------------------------------------------------------------------------

def test_pad_batch():
    assert [pad_batch(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 16]
    # shard-count multiple: even split for any mesh size, applied ON TOP
    # of the pow-2 bracket (smallest n_shards-multiple >= next_pow2(nb))
    assert pad_batch(5, 8) == 8
    assert pad_batch(9, 8) == 16
    assert pad_batch(1, 3) == 3        # non-pow2 shard counts work too
    assert pad_batch(5, 6) == 12       # bracket 8 -> first multiple of 6
    assert pad_batch(13, 6) == 18      # bracket 16 -> first multiple of 6
    # the anti-fragmentation property best_batch relies on: every member
    # count in a pow-2 bracket maps to ONE padded batch (5 and 7 share
    # bracket 8; the old code gave them 6 and 12 with n_shards=3)
    assert pad_batch(5, 3) == pad_batch(7, 3) == 9
    with pytest.raises(ValueError):
        pad_batch(0)
    with pytest.raises(ValueError):
        pad_batch(4, 0)


# ---------------------------------------------------------------------------
# batch-polymorphic cache: membership drift never compiles
# ---------------------------------------------------------------------------

def test_membership_change_zero_compiles():
    pats = _suite()
    cache = ExecutorCache()
    s1 = run_suite(pats, backend="xla", runs=1, cache=cache)
    m1 = cache.misses
    assert m1 == s1.plan.n_buckets
    # shrink within a pow2 bracket and across brackets: zero new compiles
    run_suite(pats[:3] + pats[4:7], backend="xla", runs=1, cache=cache)
    assert cache.misses == m1
    run_suite(pats[:2] + pats[4:6], backend="xla", runs=1, cache=cache)
    assert cache.misses == m1
    run_suite([pats[0], pats[4]], backend="xla", runs=1, cache=cache)
    assert cache.misses == m1
    # the exact-compile-count invariant: every cached executable holds
    # exactly one trace (it is only ever called at its padded batch)
    for fn in cache._entries.values():
        assert fn._cache_size() == 1


def test_membership_growth_within_bracket_zero_compiles():
    # strides 2..5 (delta 8, count 32) share one bucket: footprints
    # 263..284 all pad to 512, idx_len 256
    def gp(s):
        return make_pattern(f"UNIFORM:8:{s}", kind="gather", delta=8,
                            count=32, name=f"g{s}")
    cache = ExecutorCache()
    run_suite([gp(2), gp(3), gp(4)], backend="xla", runs=1, cache=cache)
    m1 = cache.misses
    # 3 -> 4 members stays in the pow2-4 bracket: same executable
    run_suite([gp(2), gp(3), gp(4), gp(5)], backend="xla", runs=1,
              cache=cache)
    assert cache.misses == m1


def test_results_correct_after_polymorphic_reuse():
    # a bucket executed through a larger warm executable (extra scratch
    # patterns) must still produce per-pattern outputs identical to a
    # freshly-compiled exact-size launch
    pats = [make_pattern(f"UNIFORM:4:{s}", kind="gather", delta=4, count=16,
                         name=f"g{s}") for s in (1, 2, 3, 5)]
    plan4 = SuitePlan.build(pats)
    plan2 = SuitePlan.build(pats[:2])
    warm = ExecutorCache()
    for bucket in plan4.buckets:
        execute_bucket(plan4, bucket, backend="xla", cache=warm)
    m = warm.misses
    for bucket in plan2.buckets:
        outs = execute_bucket(plan2, bucket, backend="xla", cache=warm)
        ref = execute_bucket(plan2, bucket, backend="xla",
                             cache=ExecutorCache())
        for o, r in zip(outs, ref):
            np.testing.assert_array_equal(o, r)
    assert warm.misses == m            # reused the batch-4 executable


def test_best_batch_lookup():
    cache = ExecutorCache()
    def key(batch):
        return ExecKey(backend="xla", kind="gather", idx_len=64,
                       footprint=64, dtype="float32", row_width=1,
                       mode="", batch=batch, placement="")
    cache.get(key(8), lambda: (lambda: 8))
    cache.get(key(2), lambda: (lambda: 2))
    assert cache.best_batch(key(4)).batch == 8      # smallest >= 4
    assert cache.best_batch(key(1)).batch == 2
    assert cache.best_batch(key(16)) is None        # growth: must compile
    # any other field mismatch disqualifies
    other = ExecKey(backend="scalar", kind="gather", idx_len=64,
                    footprint=64, dtype="float32", row_width=1,
                    mode="", batch=4, placement="")
    assert cache.best_batch(other) is None


# ---------------------------------------------------------------------------
# sharded launches (1-device mesh in-process; 8 devices in the subprocess)
# ---------------------------------------------------------------------------

def test_gs_shardings_specs():
    mesh = _mesh1()
    # batched: every operand shards dim 0 (the pattern-batch)
    in_sh, out_sh = gs_shardings(mesh, "data", "gather", batched=True)
    assert [s.spec for s in in_sh] == [P("data"), P("data")]
    assert out_sh.spec == P("data")
    # scatter executables take (dst, idx, vals, keep) — four operands
    in_sh, out_sh = gs_shardings(mesh, "data", "scatter", batched=True)
    assert [s.spec for s in in_sh] == [P("data")] * 4
    assert out_sh.spec == P("data")
    # unbatched (GSEngine.sharded): lane dim shards, gather table and
    # scatter result stay replicated
    in_sh, out_sh = gs_shardings(mesh, "data", "gather")
    assert [s.spec for s in in_sh] == [P(), P("data")]
    assert out_sh.spec == P("data")
    in_sh, out_sh = gs_shardings(mesh, "data", "scatter")
    assert [s.spec for s in in_sh] == [P(), P("data"), P("data"), P("data")]
    assert out_sh.spec == P()
    with pytest.raises(ValueError):
        gs_shardings(mesh, "data", "neither")


def test_sharded_executor_validates_axis():
    with pytest.raises(ValueError):
        ShardedExecutor(_mesh1(), axis="model")


def test_sharded_matches_unsharded_all_backends():
    pats = [make_pattern(f"UNIFORM:4:{s}", kind="gather", delta=2, count=16,
                         name=f"g{s}") for s in (1, 2, 3)]
    pats += [make_pattern(f"UNIFORM:4:{s}", kind="scatter", delta=2,
                          count=16, name=f"s{s}") for s in (1, 2, 3)]
    plan = SuitePlan.build(pats)
    mesh = _mesh1()
    for backend in B.BACKENDS:
        for mode in ("store", "add"):
            for bucket in plan.buckets:
                ref = execute_bucket(plan, bucket, backend=backend,
                                     mode=mode, cache=ExecutorCache())
                out = execute_bucket(plan, bucket, backend=backend,
                                     mode=mode, cache=ExecutorCache(),
                                     mesh=mesh)
                for o, r in zip(out, ref):
                    np.testing.assert_array_equal(
                        o, r, err_msg=f"{backend}/{mode}")


def test_sharded_and_unsharded_executables_never_collide():
    pats = _suite(n_gather=2, n_scatter=0)
    cache = ExecutorCache()
    run_suite(pats, backend="xla", runs=1, cache=cache)
    m1 = cache.misses
    run_suite(pats, backend="xla", runs=1, cache=cache, mesh=_mesh1())
    assert cache.misses > m1           # placement is part of the key
    keys = list(cache._entries)
    assert {k.placement for k in keys} == {"", "data=1/1dev"}


def test_run_suite_mesh_requires_batch():
    pats = _suite(n_gather=1, n_scatter=0)
    with pytest.raises(ValueError):
        run_suite(pats, mesh=_mesh1(), batch=False)


def test_run_suite_sharded_stats():
    pats = _suite(n_gather=2, n_scatter=2)
    stats = run_suite(pats, backend="xla", runs=2, cache=ExecutorCache(),
                      mesh=_mesh1())
    assert len(stats.results) == len(pats)
    for p, r in zip(pats, stats.results):
        assert r.pattern is p
        assert r.measured_gbs > 0 and r.time_s > 0


# ---------------------------------------------------------------------------
# acceptance: 8 fake devices, subprocess with its own XLA_FLAGS
# ---------------------------------------------------------------------------

ACCEPTANCE_8DEV = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, %r)
    from repro.core.compat import make_mesh
    import jax, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import (ExecutorCache, SuitePlan, execute_bucket,
                            make_pattern, run_suite)
    from repro.core import backends as B

    pats = []
    for i in range(12):
        kind = "gather" if i %% 2 == 0 else "scatter"
        pats.append(make_pattern("UNIFORM:8:%%d" %% ((i %% 4) + 1),
                                 kind=kind, delta=4, count=32,
                                 name="p%%d" %% i))
    plan = SuitePlan.build(pats)
    mesh = make_mesh((8,), ("data",))

    # sharded bucket launches bit-identical to the single-device planner
    for backend in B.BACKENDS:
        for mode in ("store", "add"):
            for bucket in plan.buckets:
                ref = execute_bucket(plan, bucket, backend=backend,
                                     mode=mode, cache=ExecutorCache())
                out = execute_bucket(plan, bucket, backend=backend,
                                     mode=mode, cache=ExecutorCache(),
                                     mesh=mesh)
                for o, r in zip(out, ref):
                    if (backend, mode) == ("onehot", "add"):
                        # a sharded one-hot dot may sum duplicate-index
                        # terms in another order: add mode's documented
                        # float-reassociation tolerance (DESIGN.md §11),
                        # not bit-identity
                        np.testing.assert_allclose(
                            o, r, rtol=1e-6, atol=1e-6,
                            err_msg="onehot/add")
                        continue
                    np.testing.assert_array_equal(
                        o, r, err_msg="%%s/%%s" %% (backend, mode))

    # membership change across streamed sharded runs: zero new compiles
    cache = ExecutorCache()
    run_suite(pats, backend="xla", runs=2, cache=cache, mesh=mesh)
    m1 = cache.misses
    run_suite(pats[:9], backend="xla", runs=2, cache=cache, mesh=mesh)
    run_suite(pats[:5], backend="xla", runs=2, cache=cache, mesh=mesh)
    assert cache.misses == m1, (cache.misses, m1)
    for fn in cache._entries.values():
        assert fn._cache_size() == 1
    print("OK")
    """) % SRC


def test_acceptance_sharded_suite_8dev_subprocess():
    r = subprocess.run([sys.executable, "-c", ACCEPTANCE_8DEV],
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout
