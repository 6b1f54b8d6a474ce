"""Suite planner: bucketing, executor cache, and batched-vs-per-pattern
numerical equivalence (plan.py DESIGN NOTE)."""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BucketSpec, ExecutorCache, GSEngine, Pattern,
                        SuitePlan, execute_bucket, make_pattern, run_plan,
                        run_suite)
from repro.core import backends as B
from repro.core.engine import make_host_buffers
from repro.core.plan import next_pow2


def _suite(n_gather=4, n_scatter=4, count=32):
    pats = []
    for i in range(n_gather):
        pats.append(make_pattern(f"UNIFORM:8:{i + 1}", kind="gather",
                                 delta=8, count=count, name=f"g{i}"))
    for i in range(n_scatter):
        pats.append(make_pattern(f"UNIFORM:8:{i + 1}", kind="scatter",
                                 delta=8, count=count, name=f"s{i}"))
    return pats


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 255, 256, 257)] == \
        [1, 2, 4, 4, 8, 256, 256, 512]


def test_bucket_pad_amounts():
    # count*index_len = 32*8 = 256 (already pow2); footprint = 8*31+57 = 305
    p = make_pattern("UNIFORM:8:8", kind="gather", delta=8, count=32)
    spec = BucketSpec.of(p)
    assert spec.idx_len == 256
    assert p.footprint() == 305 and spec.footprint == 512


def test_same_bucket_shares_spec():
    # strides 2..8 with delta 8, count 32: footprints 9..105 all pad to <=128
    a = make_pattern("UNIFORM:8:2", kind="gather", delta=8, count=32)
    b = make_pattern("UNIFORM:8:5", kind="gather", delta=8, count=32)
    assert BucketSpec.of(a).idx_len == BucketSpec.of(b).idx_len
    # but kind always splits buckets
    c = make_pattern("UNIFORM:8:2", kind="scatter", delta=8, count=32)
    assert BucketSpec.of(a) != BucketSpec.of(c)


def test_plan_determinism_and_order():
    pats = _suite()
    p1, p2 = SuitePlan.build(pats), SuitePlan.build(pats)
    assert p1 == p2
    # member positions cover the suite exactly once, plan order is sorted
    members = [i for b in p1.buckets for i in b.members]
    assert sorted(members) == list(range(len(pats)))
    specs = [b.spec for b in p1.buckets]
    assert specs == sorted(specs, key=lambda s: (s.kind, s.idx_len,
                                                 s.footprint))
    # shuffling the suite changes member positions but not the bucket specs
    rng = random.Random(0)
    shuffled = pats[:]
    rng.shuffle(shuffled)
    p3 = SuitePlan.build(shuffled)
    assert [b.spec for b in p3.buckets] == specs


# ---------------------------------------------------------------------------
# cache behavior
# ---------------------------------------------------------------------------

def test_second_run_compiles_nothing():
    pats = _suite()
    cache = ExecutorCache()
    stats1 = run_suite(pats, backend="xla", runs=2, cache=cache)
    misses_after_first = cache.misses
    assert misses_after_first == stats1.plan.n_buckets
    stats2 = run_suite(pats, backend="xla", runs=2, cache=cache)
    assert cache.misses == misses_after_first        # zero new compiles
    assert cache.hits >= stats2.plan.n_buckets


def test_32_pattern_suite_compiles_at_most_buckets():
    # acceptance: 32 patterns on xla compile #buckets (< 32) executables
    pats = []
    for i in range(16):
        pats.append(make_pattern(f"UNIFORM:8:{(i % 8) + 1}", kind="gather",
                                 delta=8, count=32, name=f"g{i}"))
        pats.append(make_pattern(f"UNIFORM:8:{(i % 8) + 1}", kind="scatter",
                                 delta=8, count=32, name=f"s{i}"))
    assert len(pats) == 32
    cache = ExecutorCache()
    stats = run_suite(pats, backend="xla", runs=2, cache=cache)
    assert cache.misses == stats.plan.n_buckets
    assert cache.misses < 32
    # results come back in suite order with the paper's numerator
    for p, r in zip(pats, stats.results):
        assert r.pattern is p
        assert r.measured_gbs > 0 and r.time_s > 0


def test_cache_lru_eviction_recompiles():
    pats = _suite()
    cache = ExecutorCache(maxsize=1)          # every bucket evicts the last
    run_suite(pats, backend="xla", runs=1, cache=cache)
    first = cache.misses
    run_suite(pats, backend="xla", runs=1, cache=cache)
    assert cache.misses > first               # eviction forced recompiles


# ---------------------------------------------------------------------------
# numerical equivalence: batched == per-pattern, all backends, all modes
# ---------------------------------------------------------------------------

def test_batched_gather_matches_engine_exactly():
    pats = [make_pattern(f"UNIFORM:4:{s}", kind="gather", delta=4, count=16,
                         name=f"g{s}") for s in (1, 2, 3, 5)]
    plan = SuitePlan.build(pats)
    cache = ExecutorCache()
    for backend in B.BACKENDS:
        for bucket in plan.buckets:
            outs = execute_bucket(plan, bucket, backend=backend, cache=cache)
            for out, pos in zip(outs, bucket.members):
                fn, args = GSEngine(pats[pos], backend=backend).build()
                ref = np.asarray(fn(*args))
                np.testing.assert_array_equal(
                    out, ref, err_msg=f"{backend}/{pats[pos].name}")


def test_batched_scatter_matches_unbatched_both_modes():
    # delta 2 < index span -> duplicate writes exercise dedup + add order
    pats = [make_pattern(f"UNIFORM:4:{s}", kind="scatter", delta=2, count=16,
                         name=f"s{s}") for s in (1, 2, 3, 5)]
    plan = SuitePlan.build(pats)
    for backend in B.BACKENDS:
        for mode in ("store", "add"):
            cache = ExecutorCache()
            for bucket in plan.buckets:
                outs = execute_bucket(plan, bucket, backend=backend,
                                      mode=mode, cache=cache)
                for out, pos in zip(outs, bucket.members):
                    p = pats[pos]
                    _, abs_idx, vals, _ = make_host_buffers(p, 1)
                    dst = jnp.zeros((p.footprint(), 1), jnp.float32)
                    ref = np.asarray(B.scatter(
                        dst, jnp.asarray(abs_idx), jnp.asarray(vals),
                        mode=mode, backend=backend))
                    if backend == "onehot" and mode == "add":
                        # onehot-add is a matmul; vmap reassociates the
                        # contraction, so agreement is up to f32 rounding
                        np.testing.assert_allclose(
                            out, ref, rtol=1e-6, atol=1e-6,
                            err_msg=f"{backend}/{mode}/{p.name}")
                    else:
                        np.testing.assert_array_equal(
                            out, ref, err_msg=f"{backend}/{mode}/{p.name}")


def test_padded_lanes_stay_in_scratch():
    # footprint 11 pads to a 16-row bucket + scratch; count*idx_len 24 -> 32.
    # If any padded lane leaked into a real row the store result would
    # differ from the unbatched reference on untouched rows.
    p = Pattern("odd", "scatter", (0, 3, 10), delta=0, count=8)
    plan = SuitePlan.build([p])
    spec = plan.buckets[0].spec
    assert spec.idx_len == 32 and spec.footprint == 16
    outs = execute_bucket(plan, plan.buckets[0], backend="xla", mode="store")
    _, abs_idx, vals, _ = make_host_buffers(p, 1)
    dst = jnp.zeros((p.footprint(), 1), jnp.float32)
    ref = np.asarray(B.scatter(dst, jnp.asarray(abs_idx),
                               jnp.asarray(vals), mode="store",
                               backend="xla"))
    np.testing.assert_array_equal(outs[0], ref)
    untouched = [i for i in range(p.footprint()) if i not in p.index]
    assert np.all(outs[0][untouched] == 0)


def test_suite_stats_table_respects_metric():
    stats = run_suite(_suite(n_gather=2, n_scatter=0), backend="xla",
                      runs=1, cache=ExecutorCache())
    measured = stats.table("measured")
    modeled = stats.table("modeled")
    for row_m, row_v, r in zip(measured, modeled, stats.results):
        assert row_m["gbs"] == r.measured_gbs == row_m["measured_cpu_gbs"]
        assert row_v["gbs"] == r.modeled_gbs == row_v["modeled_v5e_gbs"]
    # full column names work as aliases; unknown metrics raise
    assert stats.table("modeled_v5e_gbs") == modeled
    try:
        stats.table("bogus")
    except ValueError:
        pass
    else:
        raise AssertionError("table() accepted an unknown metric")


def test_run_suite_rejects_unknown_metric():
    pats = _suite(n_gather=1, n_scatter=0)
    try:
        run_suite(pats, metric="measurd", runs=1, cache=ExecutorCache())
    except ValueError as e:
        assert "metric" in str(e)
    else:
        raise AssertionError("run_suite accepted a typo'd metric")
    # the modeled alias orders stats by the modeled column
    stats = run_suite(pats, metric="modeled", runs=1, cache=ExecutorCache())
    assert stats.min_gbs == stats.results[0].modeled_gbs


def test_run_plan_bandwidth_uses_useful_bytes_only():
    # pattern with heavy padding: numerator must still be count*index_len
    p = make_pattern("UNIFORM:5:1", kind="gather", delta=5, count=13)
    plan = SuitePlan.build([p])
    res = run_plan(plan, backend="xla", runs=2, cache=ExecutorCache())[0]
    useful = p.index_len * p.count * 4
    np.testing.assert_allclose(res.measured_gbs,
                               useful / res.time_s / 1e9, rtol=1e-9)


# ---------------------------------------------------------------------------
# run_suite mode= / stream_r / digest / to_json (PR 4 satellites)
# ---------------------------------------------------------------------------

def test_run_suite_mode_reaches_scatters():
    # duplicate-write pattern: add accumulates, store keeps the last write,
    # so the two modes must produce different outputs (witnessed by digest)
    dup = [Pattern("dup", "scatter", (0, 0, 1), delta=0, count=8)]
    st_store = run_suite(dup, runs=1, mode="store", cache=ExecutorCache(),
                         digest=True)
    st_add = run_suite(dup, runs=1, mode="add", cache=ExecutorCache(),
                       digest=True)
    assert st_store.results[0].out_digest != st_add.results[0].out_digest
    # the unbatched path takes mode= too and agrees with the planner
    st_nb = run_suite(dup, runs=1, mode="add", batch=False)
    assert st_nb.results[0].measured_gbs > 0


def test_run_suite_rejects_unknown_mode():
    pats = _suite(n_gather=1, n_scatter=0)
    try:
        run_suite(pats, mode="min", runs=1, cache=ExecutorCache())
    except ValueError as e:
        assert "mode" in str(e)
    else:
        raise AssertionError("run_suite accepted an unknown mode")


def test_run_suite_stream_r_wires_the_reference():
    # row_width 8: the v5e tile model separates stride 1 / 64 / MS1, so
    # both correlated columns have variance and R is defined
    pats = [make_pattern(f"UNIFORM:8:{s}", kind="gather", delta=8,
                         count=64, name=f"g{s}") for s in (1, 16, 64)]
    pats.append(make_pattern("MS1:8:4:64", kind="gather", delta=8,
                             count=64, name="ms1"))
    stats = run_suite(pats, runs=1, row_width=8, cache=ExecutorCache(),
                      stream_r=True, stream_n=1024)
    assert stats.stream_gbs is not None and stats.stream_gbs > 0
    # R is a correlation: defined and bounded
    assert -1.0 <= stats.stream_r <= 1.0
    # default: the reference never runs and the fields stay None
    stats2 = run_suite(pats, runs=1, cache=ExecutorCache())
    assert stats2.stream_gbs is None and stats2.stream_r is None


def test_suite_stats_to_json_is_strict_json():
    import json as _json
    pats = _suite(n_gather=2, n_scatter=1)
    stats = run_suite(pats, runs=1, cache=ExecutorCache(), digest=True)
    doc = stats.to_json("measured")
    _json.loads(_json.dumps(doc, allow_nan=False))    # strict JSON
    assert doc["n_patterns"] == 3 and doc["n_buckets"] == stats.plan.n_buckets
    assert [r["name"] for r in doc["table"]] == [p.name for p in pats]
    assert all(len(r["digest"]) == 64 for r in doc["table"])
    # NaN stream_r serializes as null
    one = run_suite(pats[:1], runs=1, cache=ExecutorCache(),
                    stream_r=True, stream_n=1024)
    assert np.isnan(one.stream_r)
    assert one.to_json()["stream_r"] is None


def test_run_plan_digest_deterministic_across_caches():
    pats = _suite(n_gather=2, n_scatter=2)
    plan = SuitePlan.build(pats)
    r1 = run_plan(plan, runs=1, cache=ExecutorCache(), digest=True)
    r2 = run_plan(plan, runs=1, cache=ExecutorCache(), digest=True)
    assert [r.out_digest for r in r1] == [r.out_digest for r in r2]
    assert all(r.out_digest for r in r1)
    # digest off by default: results carry None
    r3 = run_plan(plan, runs=1, cache=ExecutorCache())
    assert all(r.out_digest is None for r in r3)


@pytest.mark.parametrize("backend,kind,mode,placed,name", [
    ("xla", "gather", "", False, "spatter_gather_xla"),
    ("xla", "scatter", "store", False, "spatter_scatter_store_xla"),
    ("pallas", "gather", "", True, "spatter_gather_pallas"),
    ("pallas", "scatter", "add", True, "spatter_scatter_add_pallas"),
])
def test_bucket_program_is_named_after_what_it_runs(backend, kind, mode,
                                                    placed, name):
    """The bucket program's XLA module, which the profiler's "XLA Modules"
    line shows, reads ``jit_<kind>[_<mode>]_<backend>`` on the plain jit
    and on the shard_map path (a pallas placement) alike."""
    from repro.core.plan import (Placement, _lane_body, bucket_avals,
                                 bucket_builder)
    spec = BucketSpec(kind=kind, idx_len=128, footprint=256)
    placement = Placement.create(1) if placed else None
    fn = bucket_builder(backend, spec, mode, placement)()
    lowered = fn.lower(*bucket_avals(spec, 1, 128, jnp.float32, 1))
    assert lowered.as_text().startswith(f"module @jit_{name} ")
    if placed:
        assert _lane_body(kind, mode, "lane").__name__ == name
