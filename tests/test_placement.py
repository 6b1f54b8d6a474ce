"""The 2-D placement layer (plan.Placement, DESIGN.md §11).

In-process tests cover the pure rules (gs_specs, pad_lanes, placement
strings, ``as_placement`` normalization) plus the ExecutorCache
concurrency/eviction satellites on the one device conftest pins.  The
real 2-D acceptance — ``(4, 2)`` runs of suites/demo.json and
suites/widelane.json bit-identical to the single-device planner on all
four backends, warm repeats compiling nothing — runs in a subprocess
with 8 forced host devices, like the other sharded acceptance tests.
"""
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (ExecutorCache, Placement, ShardedExecutor, SuitePlan,
                        as_placement, execute_bucket, make_pattern,
                        pad_lanes, run_suite)
from repro.core.compat import make_mesh
from repro.core.plan import ExecKey
from repro.runtime.sharding import gs_specs

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
SUITES = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "suites"))


def _key(i: int = 0, batch: int = 4, placement: str = "") -> ExecKey:
    return ExecKey(backend="xla", kind="gather", idx_len=64 * (i + 1),
                   footprint=64, dtype="float32", row_width=1, mode="",
                   batch=batch, placement=placement)


# ---------------------------------------------------------------------------
# pure rules: pad_lanes, gs_specs, placement strings
# ---------------------------------------------------------------------------

def test_pad_lanes():
    # identity on pow2 lane dims with pow2 shard counts (the 1-D cases)
    assert pad_lanes(256) == 256
    assert pad_lanes(256, 8) == 256
    assert pad_lanes(100) == 128
    # non-pow2 lane shards: smallest shard multiple >= the pow-2 bracket
    assert pad_lanes(256, 3) == 258
    assert pad_lanes(100, 3) == 129
    with pytest.raises(ValueError):
        pad_lanes(0)
    with pytest.raises(ValueError):
        pad_lanes(8, 0)


def test_gs_specs_2d():
    # batched, both axes live: batch on dim 0 everywhere, lane on the lane
    # dim of idx/vals/keep/gather-out; tables replicated over the lane axis
    in_sp, out_sp = gs_specs("gather", batched=True, batch_axis="b",
                             lane_axis="l")
    assert in_sp == (P("b"), P("b", "l")) and out_sp == P("b", "l")
    in_sp, out_sp = gs_specs("scatter", batched=True, batch_axis="b",
                             lane_axis="l")
    assert in_sp == (P("b"), P("b", "l"), P("b", "l"), P("b", "l"))
    assert out_sp == P("b")                 # any lane shard, any row
    # degenerate lane: exactly the PR 2 batch-only specs
    in_sp, out_sp = gs_specs("gather", batched=True, batch_axis="data")
    assert in_sp == (P("data"), P("data")) and out_sp == P("data")
    # degenerate batch: the lane-only (GSEngine.sharded) specs
    in_sp, out_sp = gs_specs("gather", batched=False, lane_axis="data")
    assert in_sp == (P(), P("data")) and out_sp == P("data")
    in_sp, out_sp = gs_specs("scatter", batched=False, lane_axis="data")
    assert in_sp == (P(), P("data"), P("data"), P("data"))
    assert out_sp == P()
    # a lane-only BATCHED launch: dim 0 unsharded, lane dim split
    in_sp, out_sp = gs_specs("gather", batched=True, lane_axis="l")
    assert in_sp == (P(), P(None, "l")) and out_sp == P(None, "l")
    with pytest.raises(ValueError):
        gs_specs("neither", batched=True, batch_axis="b")
    with pytest.raises(ValueError):        # no batch dim to shard unbatched
        gs_specs("gather", batched=False, batch_axis="b")


def test_placement_validation_and_strings():
    mesh = make_mesh((1,), ("data",))
    p = Placement(mesh, batch_axis="data", lane_axis=None)
    assert p.grid == (1, 1)
    assert p.placement == "data=1/1dev"     # PR 2 canonical string
    lane = Placement(mesh, batch_axis=None, lane_axis="data")
    assert lane.grid == (1, 1)
    assert lane.placement == "lane:data=1/1dev"   # never collides w/ batch
    mesh2 = make_mesh((1, 1), ("data", "lane"))
    both = Placement(mesh2, batch_axis="data", lane_axis="lane")
    assert both.grid == (1, 1)
    assert both.placement == "data=1xlane=1/1dev"
    with pytest.raises(ValueError):
        Placement(mesh, batch_axis=None, lane_axis=None)
    with pytest.raises(ValueError):
        Placement(mesh, batch_axis="data", lane_axis="data")
    with pytest.raises(ValueError):
        Placement(mesh, batch_axis="model")
    # legacy shim is the same layer
    assert isinstance(ShardedExecutor(mesh, "data"), Placement)
    with pytest.raises(ValueError):
        ShardedExecutor(mesh, axis="model")


def test_placement_create_normalizes_degenerate_axes():
    # (n, 1) and n give the SAME canonical placement (shared executables);
    # (1, n) is lane-only
    assert Placement.create(1).placement == "data=1/1dev"
    assert Placement.create((1, 1)).placement == "data=1/1dev"
    with pytest.raises(ValueError):
        Placement.create((0, 1))
    with pytest.raises(ValueError):
        Placement.create((1, 2, 3))
    with pytest.raises(ValueError, match="devices"):
        Placement.create((4096, 4096))


def test_as_placement_normalization():
    assert as_placement(None) is None
    assert as_placement(0) is None
    assert as_placement(()) is None
    p = as_placement(1)
    assert isinstance(p, Placement) and p.placement == "data=1/1dev"
    assert as_placement(p) is p
    mesh = make_mesh((1,), ("x",))
    pm = as_placement(mesh, "x")
    assert pm.batch_axis == "x" and pm.lane_axis is None
    pt = as_placement((1, 1))
    assert pt.placement == "data=1/1dev"
    with pytest.raises(ValueError, match="devices"):
        as_placement((64, 64))


def test_run_suite_accepts_mesh_forms():
    pats = [make_pattern("UNIFORM:4:1", kind="gather", delta=4, count=16,
                         name="g"),
            make_pattern("UNIFORM:4:1", kind="scatter", delta=4, count=16,
                         name="s")]
    cache = ExecutorCache()
    s0 = run_suite(pats, backend="xla", runs=1, cache=cache, digest=True)
    d0 = [r.out_digest for r in s0.results]
    for mesh in (1, (1, 1), Placement.create(1),
                 make_mesh((1,), ("data",))):
        s = run_suite(pats, backend="xla", runs=1, cache=cache, mesh=mesh,
                      digest=True)
        assert [r.out_digest for r in s.results] == d0
    # int/tuple normalization reuses ONE placement string -> one ExecKey
    # family per (shape), so the four runs above compiled at most twice
    # (unsharded + the shared data=1/1dev placement)
    assert {k.placement for k in cache._entries} == {"", "data=1/1dev"}


def test_engine_sharded_rejects_batch_placements():
    from repro.core import GSEngine
    p = make_pattern("UNIFORM:4:1", kind="gather", delta=4, count=16)
    eng = GSEngine(p)
    batchy = Placement(make_mesh((1,), ("data",)), batch_axis="data")
    with pytest.raises(ValueError, match="lane-only"):
        eng.sharded(batchy)
    # a lane-only Placement is accepted and matches the unsharded run
    lane_only = Placement(make_mesh((1,), ("l",)), batch_axis=None,
                          lane_axis="l")
    fn, args = eng.sharded(lane_only)
    ref_fn, ref_args = eng.build()
    np.testing.assert_array_equal(np.asarray(fn(*args)),
                                  np.asarray(ref_fn(*ref_args)))


# ---------------------------------------------------------------------------
# ExecutorCache: concurrent builds (satellite), best_batch index + eviction
# ---------------------------------------------------------------------------

def test_distinct_keys_build_in_parallel():
    # two threads miss on DIFFERENT keys: both builders must be in flight
    # at once (the old cache held the global lock across builder(), which
    # serialized every compile in the process and would deadlock this
    # barrier)
    cache = ExecutorCache()
    barrier = threading.Barrier(2, timeout=15)

    def builder():
        barrier.wait()
        return lambda: None

    errs = []

    def get(i):
        try:
            cache.get(_key(i), builder)
        except Exception as e:           # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=get, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs
    assert cache.stats().misses == 2 and len(cache) == 2


def test_same_key_race_builds_once():
    # N threads race on ONE key: exactly one builds (misses == 1), the
    # rest wait on the in-flight future and count as hits
    cache = ExecutorCache()
    started = threading.Event()
    release = threading.Event()
    builds = []

    def builder():
        started.set()
        assert release.wait(timeout=15)
        builds.append(1)
        return "the-exec"

    results = []

    def get():
        results.append(cache.get(_key(), builder))

    threads = [threading.Thread(target=get) for _ in range(4)]
    threads[0].start()
    assert started.wait(timeout=15)      # owner is inside builder()
    for t in threads[1:]:
        t.start()
    release.set()
    for t in threads:
        t.join(timeout=30)
    assert len(builds) == 1              # built at most once
    assert results == ["the-exec"] * 4
    st = cache.stats()
    assert st.misses == 1 and st.hits == 3


def test_clear_during_build_does_not_resurrect_entry():
    # clear() while a build is in flight outside the lock: the orphaned
    # build must NOT re-insert into the freshly reset cache (size > 0
    # with misses == 0 would break the exact-telemetry invariant), but
    # its waiters still receive the built fn
    cache = ExecutorCache()
    started = threading.Event()
    release = threading.Event()

    def builder():
        started.set()
        assert release.wait(timeout=15)
        return "built"

    out = []
    t = threading.Thread(target=lambda: out.append(cache.get(_key(),
                                                             builder)))
    t.start()
    assert started.wait(timeout=15)
    cache.clear()
    release.set()
    t.join(timeout=30)
    assert out == ["built"]              # the builder's caller got its fn
    st = cache.stats()
    assert st.size == 0 and st.misses == 0 and not cache._pending
    # the key compiles fresh afterwards
    assert cache.get(_key(), lambda: "fresh") == "fresh"
    assert cache.stats().misses == 1


def test_failed_build_propagates_and_is_not_cached():
    cache = ExecutorCache()

    def boom():
        raise RuntimeError("compile failed")

    with pytest.raises(RuntimeError, match="compile failed"):
        cache.get(_key(), boom)
    assert len(cache) == 0 and not cache._pending
    # a failed build counts NO miss: misses is the count of compiles
    # that produced an executable, so under fault injection the sum of
    # successful requests' per-request misses still equals the lifetime
    # delta exactly (DESIGN.md §14)
    assert cache.stats().misses == 0
    # the key stays buildable (a later good builder compiles it)
    assert cache.get(_key(), lambda: "ok") == "ok"
    assert cache.stats().misses == 1


def test_batch_hits_counter():
    # batch_hits counts launches actually SERVED by a larger warm
    # executable (serve_poly), not mere best_batch lookups
    cache = ExecutorCache()
    cache.get(_key(batch=8), lambda: "b8")
    assert cache.stats().batch_hits == 0
    assert cache.best_batch(_key(batch=4)).batch == 8     # pure lookup
    assert cache.stats().batch_hits == 0
    fn, served = cache.serve_poly(_key(batch=4), lambda: "b4")
    assert fn == "b8" and served.batch == 8               # cross-batch
    assert cache.stats().batch_hits == 1
    fn, served = cache.serve_poly(_key(batch=8), lambda: "b8x")
    assert fn == "b8" and served.batch == 8               # exact: no event
    assert cache.stats().batch_hits == 1
    fn, served = cache.serve_poly(_key(batch=16), lambda: "b16")
    assert fn == "b16" and served.batch == 16             # growth compiles
    st = cache.stats()
    assert st.batch_hits == 1 and st.misses == 2
    from repro.core import CacheStats
    assert st.delta(CacheStats(0, 0, 0, 0)).batch_hits == 1
    assert st.to_json()["batch_hits"] == 1


def test_family_index_survives_eviction():
    # best_batch consults an index keyed by batch-stripped key; eviction
    # must remove the evicted batch from its family or the lookup would
    # hand out keys whose executable is gone
    cache = ExecutorCache(maxsize=2)
    cache.get(_key(0, batch=8), lambda: "a8")
    cache.get(_key(1, batch=4), lambda: "b4")
    cache.get(_key(2, batch=4), lambda: "c4")      # evicts a8 (LRU)
    assert cache.best_batch(_key(0, batch=4)) is None
    assert cache.best_batch(_key(1, batch=2)).batch == 4   # still indexed
    cache.clear()
    assert cache.best_batch(_key(1, batch=2)) is None


def test_eviction_then_best_batch_recompiles_exactly_once():
    # satellite: evict the larger-batch executable mid-suite; the next
    # launch must recompile EXACTLY once (no best_batch ghost, no double
    # compile) and stay bit-identical to a fresh exact-size launch
    # strides 2..5 (delta 8, count 32) share one bucket: footprints
    # 263..284 all pad to 512, idx_len 256
    pats = [make_pattern(f"UNIFORM:8:{s}", kind="gather", delta=8, count=32,
                         name=f"g{s}") for s in (2, 3, 4, 5)]
    plan4 = SuitePlan.build(pats)
    plan2 = SuitePlan.build(pats[:2])
    assert plan4.n_buckets == plan2.n_buckets == 1
    cache = ExecutorCache(maxsize=1)
    execute_bucket(plan4, plan4.buckets[0], backend="xla", cache=cache)
    # an unrelated executable evicts the warm batch-4 gather (maxsize=1)
    spl = SuitePlan.build([make_pattern("UNIFORM:4:1", kind="scatter",
                                        delta=4, count=16, name="s")])
    execute_bucket(spl, spl.buckets[0], backend="xla", cache=cache)
    m = cache.stats().misses
    outs = execute_bucket(plan2, plan2.buckets[0], backend="xla",
                          cache=cache)
    assert cache.stats().misses == m + 1           # exactly one recompile
    refs = execute_bucket(plan2, plan2.buckets[0], backend="xla",
                          cache=ExecutorCache())
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    # and the recompiled executable is warm for the repeat
    execute_bucket(plan2, plan2.buckets[0], backend="xla", cache=cache)
    assert cache.stats().misses == m + 1


# ---------------------------------------------------------------------------
# acceptance: 2-D placements, 8 fake devices, subprocess (own XLA_FLAGS)
# ---------------------------------------------------------------------------

ACCEPTANCE_2D = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, sys
    sys.path.insert(0, %(src)r)
    import jax, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import (ExecutorCache, GSEngine, Placement, SuitePlan,
                            load_suite, make_pattern, run_suite)

    # count caps per backend: bit-identity is count-independent, and the
    # suites' full counts are an xla regime here (CI smokes them via the
    # CLI) — onehot materializes an (N, F) one-hot per pattern, scalar is
    # a per-lane loop, and pallas runs every grid step through the
    # interpreter off-TPU, so those three run the same suite FILES at
    # small counts.  A lane-sharded pallas launch routes through the §16
    # manual shard_map (each device runs the kernel on its local shard —
    # the old GSPMD-replicated caveat is retired), so pallas exercises
    # lane axes here too.
    CAPS = {"xla": 4096, "pallas": 128, "scalar": 256, "onehot": 256}
    SHAPES = {"xla": ((4, 2), (2, 4)), "pallas": ((4, 2), (1, 8)),
              "scalar": ((4, 2),), "onehot": ((4, 2),)}

    def capped(path, cap):
        return [dataclasses.replace(p, count=min(p.count, cap))
                for p in load_suite(path)]

    for name in ("demo", "widelane"):
        path = %(suites)r + "/" + name + ".json"
        for backend, cap in CAPS.items():
            pats = capped(path, cap)
            ref = run_suite(pats, backend=backend, runs=1,
                            cache=ExecutorCache(), digest=True)
            d_ref = [r.out_digest for r in ref.results]
            cache = ExecutorCache()
            for shape in SHAPES[backend]:
                got = run_suite(pats, backend=backend, runs=1, cache=cache,
                                mesh=shape, digest=True)
                assert [r.out_digest for r in got.results] == d_ref, (
                    name, backend, shape)
            # warm repeat on the 2-D placement: zero compiles
            m = cache.stats().misses
            again = run_suite(pats, backend=backend, runs=1, cache=cache,
                              mesh=(4, 2), digest=True)
            assert cache.stats().misses == m, (name, backend)
            assert [r.out_digest for r in again.results] == d_ref
        print(name, "OK")

    # §16 launch census: the lane-sharded pallas executable carries its
    # single pallas_call INSIDE a shard_map over the lane mesh — the
    # kernel really runs on every device, nothing falls back to a
    # GSPMD-partitioned (or replicated) top-level launch
    import jax.numpy as jnp
    from repro.core.plan import SuitePlan, enumerate_executables
    from repro.core.tracing import (count_primitives, shard_map_meshes,
                                    shard_map_pallas_calls)
    pats = capped(%(suites)r + "/demo.json", 128)
    plan = SuitePlan.build(pats)
    for shape in ((1, 8), (4, 2)):
        pl = Placement.create(shape)
        for key, builder, avals in enumerate_executables(
                plan, backend="pallas", dtype=jnp.float32, mode="store",
                placement=pl):
            jx = jax.make_jaxpr(builder())(*avals)
            # exactly one launch, and it lives INSIDE the shard_map body
            # (count_primitives walks the whole jaxpr, so total == inside
            # means no top-level GSPMD-routed launch remains)
            assert count_primitives(jx).get("pallas_call", 0) == 1, shape
            assert shard_map_pallas_calls(jx) == 1, (shape, key)
            meshes = shard_map_meshes(jx)
            assert any(m.get("lane") == shape[1] for m in meshes), (
                shape, meshes)
    print("census OK")

    # §16 auto placement: per-bucket "auto" equals its hand-placed twins
    # — same ExecKeys (the twin run compiles nothing on the same cache),
    # same digests
    from repro.core.plan import auto_placements
    for backend in ("xla", "pallas"):
        pats = capped(%(suites)r + "/demo.json", CAPS[backend])
        plan = SuitePlan.build(pats)
        ref = run_suite(pats, backend=backend, runs=1,
                        cache=ExecutorCache(), digest=True)
        d_ref = [r.out_digest for r in ref.results]
        cache = ExecutorCache()
        got = run_suite(pats, backend=backend, runs=1, cache=cache,
                        mesh="auto", digest=True)
        assert [r.out_digest for r in got.results] == d_ref, backend
        twins = auto_placements(plan, "auto", backend=backend)
        assert len(twins) == plan.n_buckets
        m = cache.stats().misses
        again = run_suite(pats, backend=backend, runs=1, cache=cache,
                          mesh=twins, digest=True)
        assert cache.stats().misses == m, backend     # identical ExecKeys
        assert [r.out_digest for r in again.results] == d_ref, backend
    print("auto twin OK")

    # non-pow2 lane axis: pad_lanes pads the launched lane dim to a shard
    # multiple; results still bit-identical
    pats = capped(%(suites)r + "/widelane.json", 512)
    ref = run_suite(pats, backend="xla", runs=1, cache=ExecutorCache(),
                    digest=True)
    got = run_suite(pats, backend="xla", runs=1, cache=ExecutorCache(),
                    mesh=(2, 3), digest=True)
    assert ([r.out_digest for r in got.results]
            == [r.out_digest for r in ref.results])

    # every cached executable still holds exactly one trace (exact-compile
    # -count invariant across 2-D shapes)
    cache = ExecutorCache()
    for shape in ((4, 2), (2, 4), (1, 8), (8, 1)):
        run_suite(pats, backend="xla", runs=1, cache=cache, mesh=shape)
    for fn in cache._entries.values():
        assert fn._cache_size() == 1

    # GSEngine.sharded through a lane-only placement matches its build()
    p = make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=64,
                     name="lane")
    eng = GSEngine(p)
    fn, args = eng.sharded(Placement.create((1, 8)))
    ref_fn, ref_args = eng.build()
    np.testing.assert_array_equal(np.asarray(fn(*args)),
                                  np.asarray(ref_fn(*ref_args)))
    print("OK")
    """)


def test_acceptance_2d_placement_8dev_subprocess():
    code = ACCEPTANCE_2D % {"src": SRC, "suites": SUITES}
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout


LANE_WINDOWS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, %(src)r)
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import ExecutorCache, SuitePlan, make_pattern, run_suite
    from repro.kernels.gather_rows import ops as gather_ops

    # a 2^20-element table puts the pallas gather in its DMA regime; at
    # delta 32 each shard's steps fit their line windows (DESIGN.md §2.4)
    pat = make_pattern(list(range(16)), kind="gather", delta=32,
                       count=1 << 15, name="dense")
    ref = run_suite([pat], backend="xla", runs=1, cache=ExecutorCache(),
                    digest=True)
    got = run_suite([pat], backend="pallas", runs=1, cache=ExecutorCache(),
                    mesh=(1, 8), digest=True)
    assert got.results[0].out_digest == ref.results[0].out_digest
    spec = SuitePlan.build([pat]).buckets[0].spec
    shards = pat.absolute_indices().reshape(8, -1)
    hits, steps = gather_ops.window_steps(shards, spec.footprint + 1, 1)
    assert hits == steps > 0, (hits, steps)
    print("OK")
    """)


def test_lane_sharded_pallas_gather_windows_8dev_subprocess():
    """Each device of a ``[1, 8]`` lane placement runs the narrow DMA
    gather on its contiguous shard of lanes, window path included."""
    r = subprocess.run([sys.executable, "-c", LANE_WINDOWS % {"src": SRC}],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout
