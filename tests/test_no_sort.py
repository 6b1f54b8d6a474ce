"""Hot-path hygiene regression tests (ISSUE 3 tentpole, rewired to
spatterlint in ISSUE 6).

The last-write-wins keep mask for store-mode scatter is computed on the
host at build/plan time (``Pattern.keep_last_mask``: closed form from the
pattern, no sort; ``backends.keep_last_mask`` for bare index arrays) and
threaded through as an operand; nothing the engine or planner times may contain a
``sort`` primitive, and the pallas backend launches exactly ONE kernel
per bucket.  These invariants are now owned by the spatterlint rules
``no-sort-in-hot-path`` and ``single-pallas-call-per-bucket``
(repro.analysis.rules, DESIGN.md §12) — this file calls THOSE rules
rather than a private jaxpr walker, so the test and the lint can never
disagree about what "no sort in the hot path" means.
"""
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import run_rules, unit_for
from repro.core import GSEngine, Pattern, SuitePlan, make_pattern
from repro.core import backends as B
from repro.core.compat import make_mesh
from repro.core.engine import make_host_buffers
from repro.core.plan import ShardedExecutor, _assemble_bucket, \
    _build_executable

# delta 2 < span 15: every pattern writes rows more than once
DUP = make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=32,
                   name="dup")


def _assert_rules_clean(fn, args, backend, *, kind, mode="store",
                        placement="", cached=True, label=""):
    """Every executable-scope lint rule, via the real registry."""
    unit = unit_for(fn, args, backend=backend, kind=kind, mode=mode,
                    placement=placement, cached=cached)
    violations = run_rules(unit)
    assert not violations, \
        f"{label}: {[v.render() for v in violations]}"


# ---------------------------------------------------------------------------
# the host mask itself
# ---------------------------------------------------------------------------

def test_keep_last_mask_semantics():
    idx = np.asarray([3, 1, 3, 2, 1, 1], np.int32)
    keep = B.keep_last_mask(idx)
    assert keep.tolist() == [False, False, True, True, False, True]
    # no duplicates: everything keeps
    assert B.keep_last_mask(np.asarray([5, 1, 9])).all()
    # empty buffer: empty mask, no crash
    assert B.keep_last_mask(np.zeros((0,), np.int32)).shape == (0,)
    # all duplicates: only the last survives
    assert B.keep_last_mask(np.full(7, 4)).tolist() == [False] * 6 + [True]


def _lulesh_scatter(name):
    cfg = pathlib.Path(__file__).parent.parent / "bench/configs/lulesh.json"
    (d,) = [d for d in json.loads(cfg.read_text())["patterns"]
            if d["name"] == name]
    return [Pattern.from_json(d)]


def _sweep(seed, n=600):
    """n random triples: short buffers with repeats, small deltas/counts,
    so every overlap case (same op, later op, both, none) occurs."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        idx = rng.integers(0, 31, int(rng.integers(1, 13)))
        out.append(Pattern("r", "scatter", tuple(int(i) for i in idx),
                           int(rng.integers(0, 8)), int(rng.integers(1, 13))))
    return out


def _pat(spec, delta, count):
    return [make_pattern(spec, kind="scatter", delta=delta, count=count)]


_MASK_CASES = {
    **{f"lulesh-s{i}": (lambda i=i: _lulesh_scatter(f"LULESH-S{i}"))
       for i in range(4)},
    "dup": lambda: [DUP],
    "uniform": lambda: _pat("UNIFORM:8:3", 5, 40),
    "ms1": lambda: _pat("MS1:8:4:20", 3, 30),
    "laplacian": lambda: _pat("LAPLACIAN:2:2:10", 7, 50),
    "broadcast": lambda: _pat("BROADCAST:16:4", 1, 20),
    "custom-repeated-unsorted": lambda: _pat("CUSTOM:9,2,9,0,5,2,13,7", 3, 25),
    "delta-0": lambda: _pat("CUSTOM:3,1,3,2", 0, 9),
    "delta-above-span": lambda: _pat("UNIFORM:8:2", 100, 10),
    "delta-divides-no-difference": lambda: _pat("CUSTOM:0,3,7", 5, 12),
    "count-1": lambda: _pat("CUSTOM:4,1,4,0", 2, 1),
    "len-1": lambda: _pat("CUSTOM:5", 3, 7) + _pat("CUSTOM:5", 0, 7),
    **{f"sweep-{seed}": (lambda seed=seed: _sweep(seed)) for seed in range(4)},
}


@pytest.mark.parametrize("case", list(_MASK_CASES))
def test_pattern_keep_last_mask_matches_argsort(case):
    """The closed form equals the argsort mask over the pattern's own
    absolute indices, bit for bit."""
    for p in _MASK_CASES[case]():
        keep = p.keep_last_mask()
        ref = B.keep_last_mask(p.absolute_indices().reshape(-1))
        assert keep.dtype == bool and keep.shape == ref.shape, p
        assert np.array_equal(keep, ref), p


def test_pattern_paths_never_sort(monkeypatch):
    """Store-mode keep operands from the planner and the engine are the
    argsort mask's bytes, with the argsort patched to raise: no Pattern
    path sorts.  Gathers and add mode compute no mask at all."""
    argsort_mask = B.keep_last_mask

    def boom(idx):
        raise AssertionError("a Pattern path called backends.keep_last_mask")

    pats = [DUP, Pattern("odd", "scatter", (0, 3, 10, 3), delta=0, count=7),
            make_pattern("LAPLACIAN:2:2:10", kind="scatter", delta=7,
                         count=9)]
    monkeypatch.setattr(B, "keep_last_mask", boom)
    plan = SuitePlan.build(pats)
    for bucket in plan.buckets:
        # padded lanes and scratch batch rows all point at the scratch row:
        # the argsort over each whole padded row is the operand expected
        (_, idx_b, _, keep_b), _ = _assemble_bucket(
            plan, bucket, jnp.float32, 1, 0, batch=len(bucket.members) + 1,
            lanes=bucket.spec.idx_len + 8)
        idx_b, keep_b = np.asarray(idx_b), np.asarray(keep_b)
        for b in range(idx_b.shape[0]):
            np.testing.assert_array_equal(keep_b[b], argsort_mask(idx_b[b]))
    for p in pats:
        _, abs_idx, _, keep = make_host_buffers(p, 1)
        np.testing.assert_array_equal(keep, argsort_mask(abs_idx))

    monkeypatch.setattr(Pattern, "keep_last_mask", boom)
    for bucket in plan.buckets:
        args, _ = _assemble_bucket(plan, bucket, jnp.float32, 1, 0,
                                   mode="add")
        assert not np.asarray(args[3]).any()
    g = make_pattern("UNIFORM:8:2", kind="gather", delta=2, count=32)
    gplan = SuitePlan.build([g])
    assert len(_assemble_bucket(gplan, gplan.buckets[0], jnp.float32, 1,
                                0)[0]) == 2
    assert make_host_buffers(g, 1)[3] is None


def test_make_host_buffers_carries_keep():
    _, abs_idx, vals, keep = make_host_buffers(DUP, 2)
    assert keep is not None and keep.dtype == bool
    assert keep.shape == abs_idx.shape
    np.testing.assert_array_equal(keep, B.keep_last_mask(abs_idx))
    # gathers carry no mask
    g = make_pattern("UNIFORM:8:2", kind="gather", delta=2, count=32)
    assert make_host_buffers(g, 2)[3] is None


# ---------------------------------------------------------------------------
# per-pattern executables (GSEngine.build) — every lint rule must pass.
# cached=False: engine executables are rebuilt per GSEngine and may
# legitimately donate their dst (fresh buffer every call), unlike
# ExecutorCache entries (the donation-honored rule's subject).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", B.BACKENDS)
def test_engine_store_executable_passes_lint(backend):
    fn, args = GSEngine(DUP, backend=backend).build()
    _assert_rules_clean(fn, args, backend, kind="scatter", mode="store",
                        cached=False, label=f"engine/{backend}")


# ---------------------------------------------------------------------------
# batched bucket executables (plan._build_executable), store mode —
# these DO live in the ExecutorCache, so cached=True adds the
# donation-honored check on top of no-sort / single-pallas / host
# boundary / f64.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", B.BACKENDS)
def test_bucket_store_executable_passes_lint(backend):
    plan = SuitePlan.build([DUP])
    bucket = plan.buckets[0]
    args, _ = _assemble_bucket(plan, bucket, jnp.float32, 1, 0)
    fn = _build_executable(backend, "scatter", "store")
    _assert_rules_clean(fn, args, backend, kind="scatter", mode="store",
                        label=f"bucket/{backend}")


@pytest.mark.parametrize("backend", B.BACKENDS)
def test_sharded_bucket_store_executable_passes_lint(backend):
    mesh = make_mesh((1,), ("data",))
    plan = SuitePlan.build([DUP])
    bucket = plan.buckets[0]
    args, _ = _assemble_bucket(plan, bucket, jnp.float32, 1, 0)
    sharder = ShardedExecutor(mesh, "data")
    fn = sharder.build(backend, "scatter", "store")
    args = sharder.place("scatter", args)
    _assert_rules_clean(fn, args, backend, kind="scatter", mode="store",
                        placement=sharder.placement,
                        label=f"sharded/{backend}")


def test_sharded_engine_store_passes_lint():
    mesh = make_mesh((1,), ("data",))
    fn, args = GSEngine(DUP, backend="xla").sharded(mesh, "data")
    _assert_rules_clean(fn, args, "xla", kind="scatter", mode="store",
                        cached=False, label="engine-sharded/xla")


# ---------------------------------------------------------------------------
# one-launch property: the single-pallas-call-per-bucket rule (expects
# exactly one pallas_call for backend="pallas") passes on every pallas
# execution path — store bucket, store engine, gather bucket
# ---------------------------------------------------------------------------

def test_pallas_store_bucket_is_single_launch():
    plan = SuitePlan.build([DUP])
    args, _ = _assemble_bucket(plan, plan.buckets[0], jnp.float32, 1, 0)
    fn = _build_executable("pallas", "scatter", "store")
    unit = unit_for(fn, args, backend="pallas", kind="scatter",
                    mode="store")
    assert run_rules(unit, ["single-pallas-call-per-bucket"]) == []
    assert unit.counts.get("pallas_call", 0) == 1, unit.counts


def test_pallas_store_engine_is_single_launch():
    fn, args = GSEngine(DUP, backend="pallas").build()
    unit = unit_for(fn, args, backend="pallas", kind="scatter",
                    mode="store", cached=False)
    assert run_rules(unit, ["single-pallas-call-per-bucket"]) == []
    assert unit.counts.get("pallas_call", 0) == 1, unit.counts


def test_pallas_gather_bucket_is_single_launch():
    g = make_pattern("UNIFORM:8:2", kind="gather", delta=2, count=32)
    plan = SuitePlan.build([g])
    args, _ = _assemble_bucket(plan, plan.buckets[0], jnp.float32, 1, 0)
    fn = _build_executable("pallas", "gather", "")
    unit = unit_for(fn, args, backend="pallas", kind="gather")
    assert run_rules(unit, ["single-pallas-call-per-bucket"]) == []
    assert unit.counts.get("pallas_call", 0) == 1, unit.counts
