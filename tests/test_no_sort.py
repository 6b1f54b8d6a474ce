"""Hot-path hygiene regression tests (ISSUE 3 tentpole, rewired to
spatterlint in ISSUE 6).

The last-write-wins keep mask for store-mode scatter is computed once on
the host at build/plan time (backends.keep_last_mask) and threaded
through as an operand; nothing the engine or planner times may contain a
``sort`` primitive, and the pallas backend launches exactly ONE kernel
per bucket.  These invariants are now owned by the spatterlint rules
``no-sort-in-hot-path`` and ``single-pallas-call-per-bucket``
(repro.analysis.rules, DESIGN.md §12) — this file calls THOSE rules
rather than a private jaxpr walker, so the test and the lint can never
disagree about what "no sort in the hot path" means.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import run_rules, unit_for
from repro.core import GSEngine, SuitePlan, make_pattern
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
