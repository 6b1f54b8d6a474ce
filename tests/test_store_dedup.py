"""Duplicate-index store scatter: all four backends bit-identical on
patterns that write the same row twice, on every execution path
(per-pattern GSEngine, batched bucket, sharded bucket).

The sequential-scalar backend is the semantic oracle: a fori_loop of
writes IS last-write-wins by construction, with no mask involved.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ExecutorCache, GSEngine, Pattern, SuitePlan,
                        execute_bucket, make_pattern)
from repro.core import backends as B
from repro.core.compat import make_mesh
from repro.core.engine import make_host_buffers

# delta < span: neighbouring gathers/scatters overlap -> duplicate writes.
# BROADCAST repeats indices inside one op; delta 0 stacks every op on the
# same base (the LULESH-S3 regime).
DUP_PATTERNS = [
    make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=16,
                 name="overlap"),
    make_pattern("BROADCAST:8:4", kind="scatter", delta=1, count=12,
                 name="bcast"),
    Pattern("delta0", "scatter", (0, 3, 3, 7), delta=0, count=8),
    Pattern("same-row", "scatter", (5,), delta=0, count=32),
]


def _lww_ref(p: Pattern) -> np.ndarray:
    """Sequential last-write-wins oracle on the engine's own buffers."""
    _, abs_idx, vals, _ = make_host_buffers(p, 1)
    ref = np.zeros((p.footprint(), 1), np.float32)
    for i, j in enumerate(abs_idx):
        ref[j] = vals[i]
    return ref


@pytest.mark.parametrize("p", DUP_PATTERNS, ids=lambda p: p.name)
@pytest.mark.parametrize("backend", B.BACKENDS)
def test_per_pattern_store_bit_identical(p, backend):
    fn, args = GSEngine(p, backend=backend).build()
    np.testing.assert_array_equal(np.asarray(fn(*args)), _lww_ref(p))


@pytest.mark.parametrize("backend", B.BACKENDS)
def test_batched_bucket_store_bit_identical(backend):
    plan = SuitePlan.build(DUP_PATTERNS)
    for bucket in plan.buckets:
        outs = execute_bucket(plan, bucket, backend=backend, mode="store",
                              cache=ExecutorCache())
        for out, pos in zip(outs, bucket.members):
            np.testing.assert_array_equal(
                out, _lww_ref(plan.patterns[pos]),
                err_msg=f"{backend}/{plan.patterns[pos].name}")


@pytest.mark.parametrize("backend", B.BACKENDS)
def test_sharded_bucket_store_bit_identical(backend):
    mesh = make_mesh((1,), ("data",))
    plan = SuitePlan.build(DUP_PATTERNS)
    for bucket in plan.buckets:
        outs = execute_bucket(plan, bucket, backend=backend, mode="store",
                              cache=ExecutorCache(), mesh=mesh)
        for out, pos in zip(outs, bucket.members):
            np.testing.assert_array_equal(
                out, _lww_ref(plan.patterns[pos]),
                err_msg=f"{backend}/{plan.patterns[pos].name}")


def test_all_backends_agree_with_each_other_batched():
    """Cross-check the batched path across backends directly (not just
    against the oracle) so a shared-oracle bug can't mask a divergence."""
    plan = SuitePlan.build(DUP_PATTERNS)
    for bucket in plan.buckets:
        ref = execute_bucket(plan, bucket, backend="scalar", mode="store",
                             cache=ExecutorCache())
        for backend in ("xla", "onehot", "pallas"):
            outs = execute_bucket(plan, bucket, backend=backend,
                                  mode="store", cache=ExecutorCache())
            for o, r_ in zip(outs, ref):
                np.testing.assert_array_equal(o, r_, err_msg=backend)
