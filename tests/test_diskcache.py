"""core/diskcache — the crash-safe persistent executor tier (DESIGN.md §14).

The warm-restart contract, counter-proven: a fresh ExecutorCache on a
populated cache directory serves the whole suite with ``misses == 0``
(zero compiles) and bit-identical digests; corrupt or stale entries are
quarantined and recompiled, never loaded, never fatal; degraded
(fallback-built) executables are NOT persisted.
"""
import glob
import os

import jax
import pytest

from repro.core import DiskTier, ExecutorCache, SuitePlan, make_pattern
from repro.core.diskcache import QUAR_SUFFIX, SUFFIX, exec_key_str
from repro.core.plan import bucket_builder, enumerate_executables, run_plan

PLAN = SuitePlan.build([
    make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=16),
    make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=16),
])
N_BUCKETS = PLAN.n_buckets


def _digests(cache):
    return [r.out_digest
            for r in run_plan(PLAN, runs=1, cache=cache, digest=True)]


def _entries(root):
    return sorted(glob.glob(os.path.join(root, "*" + SUFFIX)))


def _quarantined(root):
    return sorted(glob.glob(os.path.join(root, "*" + QUAR_SUFFIX)))


def test_round_trip_zero_compiles_bit_identical(tmp_path):
    root = str(tmp_path)
    cold = ExecutorCache(disk=DiskTier(root))
    ref = _digests(cold)
    assert cold.stats().misses == N_BUCKETS          # genuinely cold
    assert cold.disk.stats()["stores"] == N_BUCKETS  # all persisted
    assert len(_entries(root)) == N_BUCKETS

    # "restart": a brand-new process-level cache over the same directory
    warm = ExecutorCache()
    assert warm.attach_disk(DiskTier(root), preload=True) == N_BUCKETS
    assert _digests(warm) == ref                     # bit-identical
    s = warm.stats()
    assert s.misses == 0                             # ZERO compiles
    assert s.disk_hits == N_BUCKETS


def test_lazy_restore_without_preload(tmp_path):
    root = str(tmp_path)
    cold = ExecutorCache(disk=DiskTier(root))
    ref = _digests(cold)

    # no preload: each executable restores on first demand instead
    warm = ExecutorCache()
    assert warm.attach_disk(DiskTier(root), preload=False) == 0
    assert len(warm) == 0
    assert _digests(warm) == ref
    assert warm.stats().misses == 0
    assert warm.stats().disk_hits == N_BUCKETS
    assert warm.disk.stats()["loads"] == N_BUCKETS


def test_corrupt_entry_quarantined_and_recompiled(tmp_path):
    root = str(tmp_path)
    ref = _digests(ExecutorCache(disk=DiskTier(root)))
    victim = _entries(root)[0]
    raw = bytearray(open(victim, "rb").read())
    raw[-10] ^= 0xFF                                 # bit rot in payload
    with open(victim, "wb") as f:
        f.write(raw)

    warm = ExecutorCache()
    tier = DiskTier(root)
    assert warm.attach_disk(tier, preload=True) == N_BUCKETS - 1
    assert tier.stats()["quarantined"] == 1
    assert len(_quarantined(root)) == 1              # set aside, not deleted
    # serving still works: ONE recompile (the quarantined entry), and it
    # re-persists so the NEXT restart is fully warm again
    assert _digests(warm) == ref
    assert warm.stats().misses == 1
    assert len(_entries(root)) == N_BUCKETS
    warm2 = ExecutorCache()
    assert warm2.attach_disk(DiskTier(root), preload=True) == N_BUCKETS
    assert _digests(warm2) == ref
    assert warm2.stats().misses == 0


def test_stale_toolchain_entry_quarantined(tmp_path):
    root = str(tmp_path)
    _digests(ExecutorCache(disk=DiskTier(root)))
    victim = _entries(root)[0]
    raw = open(victim, "rb").read()
    head, _, payload = raw.partition(b"\n")          # MAGIC line
    header, _, payload = payload.partition(b"\n")
    header = header.replace(jax.__version__.encode(), b"0.0.0-stale", 1)
    with open(victim, "wb") as f:
        f.write(head + b"\n" + header + b"\n" + payload)

    tier = DiskTier(root)
    warm = ExecutorCache()
    assert warm.attach_disk(tier, preload=True) == N_BUCKETS - 1
    assert tier.stats()["quarantined"] == 1


def test_byte_budget_evicts_oldest(tmp_path):
    root = str(tmp_path)
    # a budget smaller than one entry: every store immediately evicts
    tier = DiskTier(root, budget_bytes=1)
    _digests(ExecutorCache(disk=tier))
    assert tier.stats()["stores"] == N_BUCKETS
    assert tier.stats()["evicted"] == N_BUCKETS
    assert _entries(root) == []


def test_degraded_fallback_flagged_and_not_persisted(tmp_path):
    tier = DiskTier(str(tmp_path))
    cache = ExecutorCache(disk=tier)
    key, builder, _ = enumerate_executables(PLAN)[0]

    def bad_builder():
        raise RuntimeError("injected: primary backend refused")

    fn, served, compiled, degraded = cache.serve_poly_info(
        key, bad_builder, fallback=builder)
    assert compiled and degraded
    assert fn is not None and served == key
    s = cache.stats()
    assert s.misses == 1 and s.degraded == 1
    # a degraded executable must NOT poison the persistent tier: the
    # healthy backend gets its chance again on the next restart
    assert tier.stats()["stores"] == 0 and _entries(str(tmp_path)) == []
    # warm hits on a degraded key stay flagged (every launch it serves
    # reports degraded, not just the first)
    _, _, compiled2, degraded2 = cache.serve_poly_info(key, builder)
    assert not compiled2 and degraded2


def test_restored_entries_are_marked_and_not_restored_again(tmp_path):
    root = str(tmp_path)
    _digests(ExecutorCache(disk=DiskTier(root)))
    warm = ExecutorCache()
    warm.attach_disk(DiskTier(root), preload=True)
    for _, fn in warm.entries():
        assert getattr(fn, "restored", False)
    # store() refuses a round-trip of a restored fn: it came FROM disk
    key, fn = warm.entries()[0]
    assert warm.disk.store(key, fn, None) is False
    assert warm.disk.stats()["store_failures"] == 0  # refusal, not failure


def test_key_str_covers_every_field():
    key, _, _ = enumerate_executables(PLAN)[0]
    s = exec_key_str(key)
    for field in ("backend", "kind", "idx_len", "batch", "dtype",
                  "placement"):
        assert f"{field}=" in s


def test_restored_program_keeps_its_name(tmp_path):
    """A restored executable runs as an exported call; its XLA module
    still reads as the stored program's, not ``jit_call``."""
    from repro.core.plan import key_avals
    tier = DiskTier(str(tmp_path))
    units = enumerate_executables(PLAN)
    for key, builder, _ in units:
        avals = key_avals(key)
        assert tier.store(key, builder(), avals)
        restored = tier.load(key)
        text = restored._fn.lower(*avals).as_text()
        want = ("spatter_gather_xla" if key.kind == "gather"
                else f"spatter_scatter_{key.mode}_xla")
        assert text.startswith(f"module @jit_{want} "), text[:80]
