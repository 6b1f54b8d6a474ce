"""Suite execution planner: plan -> compile -> execute for pattern suites.

DESIGN NOTE (referenced from suite.py)
======================================

Problem.  ``run_suite`` used to build one ``GSEngine`` per pattern, so an
N-pattern suite paid N XLA compiles — compile time dwarfed execute time for
the paper's JSON suites (§3.3) and made streamed/repeated suite runs (the
"many scenarios per process" regime) unusable.

Plan.  ``SuitePlan.build`` groups patterns into **shape buckets**: the two
shape-bearing dims of a pattern's executable — the flattened index length
``count * index_len`` and the table ``footprint`` — are padded up to the
next power of two, and patterns whose ``(kind, padded_idx_len,
padded_footprint)`` agree share one bucket.  Pow-2 padding trades at most
2x wasted lanes for an O(log) number of distinct executable shapes.

Compile.  One executable per bucket shape: a ``jax.jit``-wrapped ``vmap``
of the single-pattern backend op (backends.gather_batched /
scatter_batched), with the pattern-batch as the mapped dim.  Executables
live in an ``ExecutorCache`` — an LRU keyed on ``(backend, kind, idx_len,
footprint, dtype, row_width, mode, batch, placement)`` — so repeated or
streamed suite runs reuse warm executables across ``run_suite`` calls.
The cache's ``misses`` counter is the compile counter: a 32-pattern suite
compiles ``n_buckets`` (< 32) executables, and a second identical run
compiles zero.

Batch polymorphism.  The pattern-batch dim itself is padded to the next
power of two (``pad_batch``), exactly like the lane dims: a bucket whose
member count drifts between streamed suite runs (31 patterns today, 29
tomorrow) keeps hitting the same padded batch, the same ``ExecKey``, and
the same traced executable — zero re-traces, where the unpadded batch dim
used to make jax silently re-trace on every membership change.  Lookup is
additionally batch-polymorphic across pow-2 brackets
(``ExecutorCache.best_batch``): a bucket whose membership *shrank* below
its old bracket reuses the smallest warm executable with a larger batch,
padding with more scratch patterns, so only genuine shape growth ever
compiles.  Because the padded batch is part of the ``ExecKey``,
``ExecutorCache.misses`` is an *exact* compile count: one cached
executable is only ever called with one input signature (each jitted
entry holds exactly one trace — asserted by tests).

Padded batch rows are scratch *patterns*: their index lanes all point at
the scratch table row, their tables/payloads are zeros, and their vmap
outputs are dropped before results are attributed — the same
can't-touch-real-data / never-in-the-numerator semantics as padded lanes.

Sharded launches.  ``run_plan(..., mesh=...)`` places every bucket launch
on a ``Placement`` — a device mesh of shape ``(batch, lane)`` with either
axis degenerate (DESIGN.md §11).  ``mesh=`` accepts an int ``N`` (batch-
only, the PR 2 behavior), a ``(b, l)`` tuple, a raw ``Mesh`` (batch-only
over ``mesh_axis``), or a ``Placement``.  The batch axis splits the
pattern-batch dim — each device runs whole patterns, the multi-device
form of the paper's §3.4 thread scaling — and the lane axis splits the
flattened lane dim *within* each pattern, the same split
``GSEngine.sharded`` applies to a single pattern, so buckets with few
members but huge lanes still fill the mesh.  Axis semantics live in ONE
rule table (``runtime.sharding.gs_specs``) shared by every sharded path.
``pad_batch`` rounds the batch up to a shard-multiple of the batch axis
and ``pad_lanes`` rounds the launched lane dim up to a shard-multiple of
the lane axis, so both splits are always even; results stay bit-identical
to the single-device launch (store-mode scatter dedup is decided by the
host keep mask *before* the lane split, so at most one write per row
survives globally).  The canonical placement string is part of the
``ExecKey`` (differently-placed executables never collide).

Execute.  Same-bucket patterns are stacked: indices into a (B_pad, N_pad)
int32 array, tables into (B_pad, F_pad + 1, R).  Row ``F_pad`` of every
table is a scratch row; padded lanes (both the lane tail up to N_pad and,
for scatters, their payload) point at it, so they can never touch real
rows, and they never enter the bandwidth numerator — ``measured_gbs`` /
``modeled_gbs`` keep exactly the paper's §3.5 useful-bytes formula.
Per-pattern buffers come from ``engine.make_host_buffers`` — the same
function ``GSEngine`` uses — so batched results are bit-identical to
per-pattern execution (asserted by tests/test_suite_plan.py on all four
backends, and by tests/test_sharded_plan.py for the sharded path).

Hot-path hygiene.  Store-mode scatter needs last-write-wins dedup; its
keep mask is a pure function of the (static) padded index buffer, so
``_assemble_bucket`` computes it once on the host (backends.keep_last_mask)
and passes it to the executable as a fourth operand — no sort or dedup
primitive ever appears in a timed executable's jaxpr (asserted by
tests/test_no_sort.py).  On the pallas backend the batched ops are
batch-NATIVE kernels (a real grid over pattern-batch x tiles with the
index buffers scalar-prefetched once) rather than jax.vmap of per-pattern
pallas_calls, and store mode is one single-pass kernel launch per bucket.

Timing attribution.  A bucket launch is timed like GSEngine.run (min over
K runs, §3.5); each member pattern is attributed wall time proportional to
its share of the bucket's *launched* pattern lanes — scratch batch rows
count in the denominator (their share belongs to padding, not to any
member), so a member's reported bandwidth is invariant to how much batch
padding the serving executable carried, and every pattern in a bucket
reports the bandwidth the launch achieved.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import threading
import time
from collections import OrderedDict
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import backends as B
from . import bandwidth as bw
from .engine import SCATTER_MODES, RunResult, make_host_buffers
from .pattern import Pattern


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    return 1 << (n - 1).bit_length()


def _bracket_multiple(n: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` >= ``next_pow2(n)`` — the ONE
    padding contract both mesh axes share (``pad_batch``/``pad_lanes``),
    so the bracket-stability rule can never drift between them."""
    if n_shards < 1:
        raise ValueError(f"need n_shards >= 1, got {n_shards}")
    return math.ceil(next_pow2(n) / n_shards) * n_shards


def pad_batch(nb: int, n_shards: int = 1) -> int:
    """Padded pattern-batch dim: the smallest multiple of ``n_shards`` that
    is >= ``next_pow2(nb)`` (with ``n_shards=1`` that is exactly the next
    pow2).

    Pow-2 bracketing makes bucket executables batch-polymorphic in practice
    (member-count drift between suite runs lands on the same padded batch);
    the shard-count multiple keeps a sharded launch's batch split even.
    The shard round-up is applied ON TOP of the pow-2 bracket — never
    instead of it — so every member count in a bracket maps to ONE padded
    batch per shard count.  (The old behavior rounded ``ceil(nb/n_shards)``
    to a pow2 and could land *below* the bracket: nb=5, n_shards=3 gave 6
    while nb=7 gave 12, fragmenting the ``ExecKey.batch`` values that
    ``ExecutorCache.best_batch`` assumes are bracket-stable.)
    """
    return _bracket_multiple(nb, n_shards)


def pad_lanes(n: int, n_shards: int = 1) -> int:
    """Padded flattened-lane dim: the lane-axis twin of ``pad_batch``,
    sharing the same bracket-multiple contract (``_bracket_multiple``).

    ``BucketSpec.idx_len`` is already a pow2, so with a pow-2 lane-shard
    count this is the identity; non-pow2 lane axes (e.g. ``--mesh 2x3``)
    pad the launched lane dim up to the next shard multiple, and the
    extra lanes are ordinary padding lanes (they point at the scratch
    row and never enter the bandwidth numerator).
    """
    return _bracket_multiple(n, n_shards)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Shape signature shared by every pattern in a bucket."""
    kind: str           # "gather" | "scatter"
    idx_len: int        # count * index_len, padded to pow2
    footprint: int      # table footprint, padded to pow2

    @staticmethod
    def of(p: Pattern) -> "BucketSpec":
        return BucketSpec(kind=p.kind,
                          idx_len=next_pow2(p.count * p.index_len),
                          footprint=next_pow2(p.footprint()))


@dataclasses.dataclass(frozen=True)
class Bucket:
    spec: BucketSpec
    members: tuple[int, ...]      # positions into the suite's pattern list


@dataclasses.dataclass(frozen=True)
class SuitePlan:
    patterns: tuple[Pattern, ...]
    buckets: tuple[Bucket, ...]

    @staticmethod
    def build(patterns: Sequence[Pattern]) -> "SuitePlan":
        groups: dict[BucketSpec, list[int]] = {}
        for i, p in enumerate(patterns):
            groups.setdefault(BucketSpec.of(p), []).append(i)
        buckets = tuple(
            Bucket(spec=spec, members=tuple(groups[spec]))
            for spec in sorted(groups,
                               key=lambda s: (s.kind, s.idx_len, s.footprint)))
        return SuitePlan(patterns=tuple(patterns), buckets=buckets)

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def pad_waste(self, n_shards: int = 1, lane_shards: int = 1) -> float:
        """Fraction of launched lanes that are padding (0 = no waste).

        Counts lane padding (pow-2 bracket plus the ``lane_shards``
        multiple on the lane axis) and the scratch patterns added by
        batch-dim padding (``pad_batch``, including the ``n_shards``
        multiple on the batch axis).
        """
        real = sum(p.count * p.index_len for p in self.patterns)
        launched = sum(pad_lanes(b.spec.idx_len, lane_shards)
                       * pad_batch(len(b.members), n_shards)
                       for b in self.buckets)
        return 1.0 - real / max(1, launched)

    def pad_waste_for(self, placements) -> float:
        """``pad_waste`` under a per-bucket placement list (the
        ``mesh="auto"`` resolution) — each bucket pads to its own
        placement's shard multiples; ``None`` entries are unsharded."""
        real = sum(p.count * p.index_len for p in self.patterns)
        launched = 0
        for b, pl in zip(self.buckets, placements):
            bs, ls = pl.grid if pl is not None else (1, 1)
            launched += (pad_lanes(b.spec.idx_len, ls)
                         * pad_batch(len(b.members), bs))
        return 1.0 - real / max(1, launched)


# ---------------------------------------------------------------------------
# Executor cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecKey:
    backend: str
    kind: str
    idx_len: int
    footprint: int
    dtype: str
    row_width: int
    mode: str           # "store" | "add" for scatter, "" for gather
    batch: int          # padded pattern-batch dim (pad_batch)
    placement: str      # Placement.placement, "" = single-device


@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Point-in-time ``ExecutorCache`` counters (one consistent snapshot).

    ``misses`` is the exact compile count (see ExecutorCache).  The
    serving layer brackets each request with two snapshots and reports
    ``after.delta(before)`` — the request's own hits/misses — so a warm
    repeat request can *prove* it compiled nothing.

    ``batch_hits`` counts cross-batch (polymorphic) hits: launches served
    by a warm executable with a *larger* pattern-batch via ``best_batch``
    instead of compiling an exact-size one.  They are a subset of
    ``hits`` — each one also counts as a plain hit on the larger key.

    ``disk_hits`` counts serves satisfied by the persistent tier
    (restored AOT executables — no compile ran, so they are NOT misses:
    a warm restart proves itself with ``misses == 0``).  ``degraded``
    counts fallback compiles: a requested builder failed and the key was
    served by the ``xla`` fallback instead (DESIGN.md §14) — a subset of
    ``misses`` (the fallback did compile).
    """
    hits: int
    misses: int
    size: int
    batch_hits: int = 0
    disk_hits: int = 0
    degraded: int = 0

    def delta(self, before: "CacheStats") -> "CacheStats":
        """Elementwise difference — every field of the result is a delta
        (``size`` is net entry growth, which eviction can make negative);
        report absolute occupancy from the *after* snapshot instead."""
        return CacheStats(hits=self.hits - before.hits,
                          misses=self.misses - before.misses,
                          size=self.size - before.size,
                          batch_hits=self.batch_hits - before.batch_hits,
                          disk_hits=self.disk_hits - before.disk_hits,
                          degraded=self.degraded - before.degraded)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class _BuildFuture:
    """In-flight compile slot: the owning thread publishes the built
    executable (or the builder's exception) and every racing thread on
    the same key waits instead of building a duplicate.  ``degraded`` is
    set (before ``done``) when the owner served the fallback builder."""
    __slots__ = ("done", "fn", "exc", "degraded")

    def __init__(self):
        self.done = threading.Event()
        self.fn = None
        self.exc = None
        self.degraded = False


class ExecutorCache:
    """LRU of compiled bucket executables; ``misses`` counts compiles.

    Keys carry the full input signature (bucket shape, padded batch, and
    mesh placement), so one entry is only ever invoked with one trace:
    ``misses`` equals the number of XLA compiles performed through the
    cache, exactly.

    Thread safety: all structure mutation (the LRU order, eviction, the
    hit/miss counters) happens under one internal lock, because the
    serving daemon's request handlers share the process-wide cache from
    multiple threads.  ``builder()`` itself runs *outside* the lock with
    per-key build futures and double-checked locking: distinct keys
    compile concurrently (holding the global lock across a builder used
    to serialize every compile in the process behind a mutex meant for
    bookkeeping), while threads racing on the SAME key wait on the one
    in-flight future — a key is still built at most once and ``misses``
    never double-counts a race (the waiters count as hits: they compiled
    nothing).

    ``best_batch`` is indexed: entries are grouped by their batch-
    stripped key (``_family``), so the polymorphic lookup scans only that
    family's candidate batches instead of every cached entry under the
    lock on each bucket launch.

    ``disk`` is the optional persistent tier (core/diskcache.DiskTier,
    DESIGN.md §14): a build owner probes it before compiling (restores
    count ``disk_hits``, not ``misses``) and persists fresh non-degraded
    builds after publishing them.  ``fault_hook`` is the fault-injection
    seam: when set it is called with ``"compile"`` immediately before a
    builder runs and may raise (serve/faults.py).
    """

    def __init__(self, maxsize: int = 128, *, disk=None, fault_hook=None):
        self.maxsize = maxsize
        self.disk = disk
        self.fault_hook = fault_hook
        self._entries: OrderedDict[ExecKey, Callable] = OrderedDict()
        self._pending: dict[ExecKey, _BuildFuture] = {}
        self._families: dict[ExecKey, set[int]] = {}   # family -> batches
        self._degraded_keys: set[ExecKey] = set()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.batch_hits = 0
        self.disk_hits = 0
        self.degraded = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @staticmethod
    def _family(key: ExecKey) -> ExecKey:
        """Batch-stripped index key (real batches are >= 1, 0 is free)."""
        return dataclasses.replace(key, batch=0)

    def _insert(self, key: ExecKey, fn: Callable) -> None:
        # caller holds self._lock
        self._entries[key] = fn
        self._families.setdefault(self._family(key), set()).add(key.batch)
        while len(self._entries) > self.maxsize:
            old, _ = self._entries.popitem(last=False)
            fam = self._family(old)
            batches = self._families.get(fam)
            if batches is not None:
                batches.discard(old.batch)
                if not batches:
                    del self._families[fam]

    def _hit_locked(self, key: ExecKey) -> Callable | None:
        # caller holds self._lock
        fn = self._entries.get(key)
        if fn is not None:
            self._entries.move_to_end(key)
            self.hits += 1
        return fn

    def _claim_locked(self, key: ExecKey) -> tuple[_BuildFuture, bool]:
        # caller holds self._lock; returns (future, this thread owns build).
        # The owner is NOT counted as a miss here: whether the claim
        # becomes a miss (builder ran) or a disk_hit (restored from the
        # persistent tier) is only known when the build resolves
        # (_await_or_build) — misses must stay the exact compile count.
        fut = self._pending.get(key)
        if fut is None:
            fut = _BuildFuture()
            self._pending[key] = fut
            return fut, True
        self.hits += 1                 # raced: that build is in flight
        return fut, False

    def _fail_build(self, key: ExecKey, fut: _BuildFuture,
                    exc: BaseException) -> None:
        fut.exc = exc
        with self._lock:
            if self._pending.get(key) is fut:
                del self._pending[key]
        fut.done.set()

    def _await_or_build(self, key: ExecKey, fut: _BuildFuture, owner: bool,
                        builder: Callable[[], Callable],
                        fallback: Callable[[], Callable] | None = None
                        ) -> tuple[Callable, bool, bool]:
        """Resolve a claimed build; returns ``(fn, compiled, degraded)``.

        Runs OUTSIDE the lock: distinct keys compile concurrently.  The
        owner first probes the disk tier (a restore compiles nothing —
        ``disk_hits``, not ``misses``), then runs ``builder`` — and on
        builder failure, ``fallback`` when given (the pallas→xla
        degradation path; the key is marked degraded so telemetry can
        flag every launch it serves).  ``compiled`` is True only when a
        builder actually ran, which is what keeps ``misses`` exact.
        """
        if not owner:
            fut.done.wait()
            if fut.exc is not None:
                raise fut.exc
            return fut.fn, False, fut.degraded
        fn = None
        degraded = False
        disk = self.disk
        if disk is not None:
            try:
                fn = disk.load(key)
            except Exception:
                fn = None
        from_disk = fn is not None
        if fn is None:
            hook = self.fault_hook
            try:
                if hook is not None:
                    hook("compile")
                fn = builder()
            except BaseException as e:
                if fallback is None:
                    self._fail_build(key, fut, e)
                    raise
                try:
                    if hook is not None:
                        hook("compile")
                    fn = fallback()
                    degraded = True
                except BaseException:
                    self._fail_build(key, fut, e)   # report the root cause
                    raise e
        with self._lock:
            # insert only if this build's claim is still current — a
            # clear() while we compiled outside the lock emptied _pending,
            # and re-inserting would desync the freshly reset counters
            # (size > 0 with misses == 0).  Counters move under the same
            # hold as the insert so a stats() snapshot never sees one
            # without the other.
            if self._pending.get(key) is fut:
                del self._pending[key]
                self._insert(key, fn)
                if from_disk:
                    self.disk_hits += 1
                else:
                    self.misses += 1   # exactly one thread ran the builder
                if degraded:
                    self.degraded += 1
                    self._degraded_keys.add(key)
        fut.degraded = degraded
        fut.fn = fn
        fut.done.set()
        # persist fresh, non-degraded builds (degradation must not become
        # sticky across restarts — §14); store failures are counted by
        # the tier and never surface here
        if disk is not None and not from_disk and not degraded:
            try:
                disk.store(key, fn, key_avals(key))
            except Exception:
                pass
        return fn, not from_disk, degraded

    def attach_disk(self, tier, preload: bool = True) -> int:
        """Adopt a persistent tier; optionally preload every verifiable
        entry into memory (a restarted daemon starts warm).  Returns the
        number of executables restored.  Deserialization runs outside
        the lock; only the inserts are serialized."""
        self.disk = tier
        if not preload:
            return 0
        restored = tier.load_all()
        n = 0
        with self._lock:
            for key, fn in restored:
                if key not in self._entries:
                    self._insert(key, fn)
                    self.disk_hits += 1
                    n += 1
        return n

    def get(self, key: ExecKey, builder: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._hit_locked(key)
            if fn is not None:
                return fn
            fut, owner = self._claim_locked(key)
        return self._await_or_build(key, fut, owner, builder)[0]

    def serve_poly(self, key: ExecKey, builder: Callable[[], Callable]
                   ) -> tuple[Callable, ExecKey]:
        """Batch-polymorphic fetch: ``(fn, served_key)`` where served_key
        is ``key`` or its smallest warm larger-batch sibling.

        Lookup and serve happen under ONE lock hold, so the
        ``batch_hits`` counter records only launches actually served by a
        larger warm executable (a lookup/serve race with eviction can
        neither count a phantom cross-batch hit nor compile at a stale
        larger batch), and ``misses`` stays the exact compile count.
        """
        fn, served, _, _ = self.serve_poly_info(key, builder)
        return fn, served

    def serve_poly_info(self, key: ExecKey, builder: Callable[[], Callable],
                        fallback: Callable[[], Callable] | None = None
                        ) -> tuple[Callable, ExecKey, bool, bool]:
        """``serve_poly`` plus compile attribution: ``(fn, served_key,
        compiled, degraded)`` where ``compiled`` is True iff THIS call
        claimed the key's ``_BuildFuture`` and ran a builder (a restore
        from the disk tier compiles nothing and reports False).

        Exactly one caller per compile sees ``compiled=True`` (racers on
        the same key wait on the in-flight future and see False), so a
        caller-side sum of ``compiled`` equals the cache's ``misses``
        delta exactly — the serving scheduler uses this to attribute each
        compile to the one request that claimed it, keeping per-request
        ``misses`` exact without bracketing global counters.

        ``degraded`` is True when the serve is backed by the fallback
        builder — either this call degraded, or it hit a key an earlier
        call degraded (``_degraded_keys`` remembers) — so every launch
        on a degraded executable is flagged, not just the first.
        """
        with self._lock:
            best = self._best_batch_locked(key)
            if best is not None:
                # the family index only tracks inserted entries, so under
                # this same lock hold the hit cannot fail
                fn = self._hit_locked(best)
                if fn is not None:
                    if best.batch > key.batch:
                        self.batch_hits += 1
                    return fn, best, False, best in self._degraded_keys
            fut, owner = self._claim_locked(key)
        fn, compiled, degraded = self._await_or_build(key, fut, owner,
                                                      builder, fallback)
        return fn, key, compiled, degraded

    def _best_batch_locked(self, key: ExecKey) -> ExecKey | None:
        # caller holds self._lock
        batches = self._families.get(self._family(key))
        if not batches:
            return None
        cands = [b for b in batches if b >= key.batch]
        if not cands:
            return None
        return dataclasses.replace(key, batch=min(cands))

    def best_batch(self, key: ExecKey) -> ExecKey | None:
        """Smallest cached key differing from ``key`` only by a >= batch.

        The batch-polymorphic lookup: a warm executable compiled for a
        larger pattern-batch serves a smaller bucket by padding with more
        scratch patterns, so bucket-membership shrink never compiles.
        O(candidate batches) via the family index — not O(cache size).
        Pure lookup; the serving path (``serve_poly``) counts
        ``batch_hits`` at actual serve time.
        """
        with self._lock:
            return self._best_batch_locked(key)

    def stats(self) -> CacheStats:
        """Consistent counter snapshot (one lock hold)."""
        with self._lock:
            return CacheStats(hits=self.hits, misses=self.misses,
                              size=len(self._entries),
                              batch_hits=self.batch_hits,
                              disk_hits=self.disk_hits,
                              degraded=self.degraded)

    def entries(self) -> list[tuple[ExecKey, Callable]]:
        """Read-only snapshot of ``(key, executable)`` pairs, LRU order.

        For auditors (the daemon's ``GET /lint`` walks the live cache):
        touches neither the LRU order nor the hit/miss counters, so an
        audit can never perturb the telemetry the serving layer reports.
        """
        with self._lock:
            return list(self._entries.items())

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._families.clear()
            # orphan in-flight builds: their completion sees its claim is
            # gone and skips the insert (waiters still receive the fn)
            self._pending.clear()
            self._degraded_keys.clear()
            self.hits = 0
            self.misses = 0
            self.batch_hits = 0
            self.disk_hits = 0
            self.degraded = 0


_DEFAULT_CACHE = ExecutorCache()


def default_cache() -> ExecutorCache:
    """Process-wide cache: repeated run_suite calls share warm executables."""
    return _DEFAULT_CACHE


def _raw_batched_fn(backend: str, kind: str, mode: str) -> Callable:
    """The (unjitted) bucket op — single source of truth for the bucket
    executable's signature, shared by the single-device and sharded
    builders so their operand lists can never drift apart."""
    if kind == "gather":
        def fn(src_b, idx_b):
            return B.gather_batched(src_b, idx_b, backend=backend)
    else:
        # keep is the host-precomputed last-write-wins mask over the padded
        # index buffer (unused in add mode); the traced body never sorts
        def fn(dst_b, idx_b, vals_b, keep_b):
            return B.scatter_batched(dst_b, idx_b, vals_b, mode=mode,
                                     backend=backend, keep=keep_b)
    return fn


def _build_executable(backend: str, kind: str, mode: str) -> Callable:
    return jax.jit(_raw_batched_fn(backend, kind, mode))


def _lane_body(kind: str, mode: str, lane_axis: str) -> Callable:
    """Per-device body of a lane-sharded pallas launch (DESIGN.md §16).

    Runs the batch-native Pallas kernel on the LOCAL lane shard — so
    every device executes the real kernel instead of falling back around
    an opaque ``pallas_call`` — and combines across the lane axis:

      * gather: no combine; each shard produces its own output lanes.
      * scatter-add: shards hold disjoint lanes of the same pattern, so
        partial sums ``psum`` into the full result (floating-point adds
        reassociate across the shard boundary — add mode's documented
        ~1-ulp tolerance).
      * scatter-store: the host keep mask deduped writes BEFORE the lane
        split, so globally at most one shard writes each row.  The store
        kernel's ``with_covered`` output says which elements this shard
        wrote; psum of disjoint contributions is an exact select, and
        uncovered elements keep ``dst`` — bit-identical to the
        single-device launch.

    The signature mirrors ``_raw_batched_fn`` exactly, so the lane path
    launches with the same operand list as every other placement.
    """
    from repro.kernels.gather_rows import ops as gather_ops
    from repro.kernels.scatter_rows import ops as scatter_ops

    if kind == "gather":
        def fn(src_b, idx_b):
            return gather_ops.gather_rows_batched(src_b, idx_b)
    elif mode == "add":
        def fn(dst_b, idx_b, vals_b, keep_b):
            del keep_b                       # add mode never dedups
            part = scatter_ops.scatter_add_rows_batched(
                idx_b, vals_b, dst_b.shape[1])
            return dst_b + jax.lax.psum(part, lane_axis)
    else:
        def fn(dst_b, idx_b, vals_b, keep_b):
            safe = jnp.where(keep_b, idx_b, jnp.iinfo(jnp.int32).max)
            out_l, cov_l = scatter_ops.scatter_store_rows_batched(
                jnp.zeros_like(dst_b), safe, vals_b, with_covered=True)
            covered = jax.lax.psum(cov_l, lane_axis)
            return jnp.where(covered > 0, jax.lax.psum(out_l, lane_axis),
                             dst_b)
    return fn


# ---------------------------------------------------------------------------
# Placement: the 2-D (pattern-batch x lane) distribution layer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Placement:
    """A device placement of shape ``(batch, lane)`` for G/S executables.

    One abstraction serves every distributed path (DESIGN.md §11): the
    batch axis splits a bucket launch's pattern-batch dim (whole patterns
    per device — the PR 2 ``ShardedExecutor``), the lane axis splits the
    flattened lane dim *within* a pattern (the paper's OpenMP-thread dim
    — ``GSEngine.sharded``), and a 2-D placement composes both.  Either
    axis may be degenerate (``None``); the axis *rules* live in
    ``runtime.sharding.gs_specs``, so no sharding policy is duplicated
    across paths.

    ``placement`` is the canonical string that feeds ``ExecKey`` —
    batch-only placements keep the PR 2 format (``data=8/8dev``) so warm
    caches stay warm; 2-D shapes read ``data=4xlane=2/8dev``.
    """
    mesh: Mesh
    batch_axis: str | None = "data"
    lane_axis: str | None = None

    def __post_init__(self):
        if self.batch_axis is None and self.lane_axis is None:
            raise ValueError("placement needs at least one mesh axis")
        if self.batch_axis == self.lane_axis:
            raise ValueError(f"batch and lane axes must differ, both "
                             f"{self.batch_axis!r}")
        for ax in (self.batch_axis, self.lane_axis):
            if ax is not None and ax not in self.mesh.axis_names:
                raise ValueError(f"mesh has no axis {ax!r} "
                                 f"(axes: {self.mesh.axis_names})")

    # -- construction --------------------------------------------------------
    @staticmethod
    def create(shape, *, batch_axis: str = "data",
               lane_axis: str = "lane") -> "Placement":
        """Build a placement (and its mesh) from a shape: an int ``N``
        (batch-only over N devices) or a ``(b, l)`` tuple.  Degenerate
        tuple dims collapse to 1-D meshes, so ``(8, 1)`` and ``8`` give
        the SAME canonical placement (and hence the same ``ExecKey``
        executables), and ``(1, 8)`` is lane-only over 8 devices.
        """
        if isinstance(shape, int):
            shape = (shape,)
        shape = tuple(int(s) for s in shape)
        if not 1 <= len(shape) <= 2 or any(s < 1 for s in shape):
            raise ValueError(f"placement shape must be N or (b, l) with "
                             f"b, l >= 1, got {shape}")
        b, l = shape[0], shape[1] if len(shape) == 2 else 1
        from .compat import make_mesh
        n_dev = len(jax.devices())
        if b * l > n_dev:
            raise ValueError(
                f"placement {b}x{l} needs {b * l} devices, have {n_dev} "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{b * l} to fake devices on CPU)")
        if l == 1:
            return Placement(make_mesh((b,), (batch_axis,)),
                             batch_axis=batch_axis, lane_axis=None)
        if b == 1:
            return Placement(make_mesh((l,), (lane_axis,)),
                             batch_axis=None, lane_axis=lane_axis)
        return Placement(make_mesh((b, l), (batch_axis, lane_axis)),
                         batch_axis=batch_axis, lane_axis=lane_axis)

    @staticmethod
    def from_mesh(mesh: Mesh, *, batch_axis: str | None = None,
                  lane_axis: str | None = None) -> "Placement":
        """Wrap an existing mesh; name which axes play which role."""
        return Placement(mesh, batch_axis=batch_axis, lane_axis=lane_axis)

    # -- geometry ------------------------------------------------------------
    @property
    def batch_shards(self) -> int:
        return self.mesh.shape[self.batch_axis] if self.batch_axis else 1

    @property
    def lane_shards(self) -> int:
        return self.mesh.shape[self.lane_axis] if self.lane_axis else 1

    @property
    def grid(self) -> tuple[int, int]:
        """(batch_shards, lane_shards) — feeds pad_batch/pad_lanes."""
        return self.batch_shards, self.lane_shards

    @property
    def placement(self) -> str:
        """Canonical ``ExecKey`` string; 1-D batch keeps the PR 2 form."""
        ndev = len(self.mesh.devices.flat)
        if self.lane_axis is None:
            return f"{self.batch_axis}={self.batch_shards}/{ndev}dev"
        if self.batch_axis is None:
            return f"lane:{self.lane_axis}={self.lane_shards}/{ndev}dev"
        return (f"{self.batch_axis}={self.batch_shards}"
                f"x{self.lane_axis}={self.lane_shards}/{ndev}dev")

    # -- executables ---------------------------------------------------------
    def shardings(self, kind: str, *, batched: bool = True):
        """(in_shardings, out_sharding) on this placement's mesh."""
        from repro.runtime.sharding import gs_specs, named_shardings
        in_specs, out_spec = gs_specs(kind, batched=batched,
                                      batch_axis=self.batch_axis,
                                      lane_axis=self.lane_axis)
        in_sh = named_shardings(self.mesh, *in_specs)
        (out_sh,) = named_shardings(self.mesh, out_spec)
        return in_sh, out_sh

    def build(self, backend: str, kind: str, mode: str) -> Callable:
        """Jit the batched bucket op with this placement's shardings.

        Every pallas placement routes through
        ``compat.shard_map_unchecked`` (DESIGN.md §16): GSPMD has no
        partitioning rule for ``pallas_call``, so under a plain jit the
        kernel would run replicated behind all-gathers of its operands.
        The manual body runs the kernel on each device's own shard — its
        patterns (batch axis) and its lanes (lane axis) — and, with a
        lane split, combines across the lane axis explicitly
        (``_lane_body``).  The other backends keep the plain GSPMD jit:
        XLA partitions their ops natively.
        """
        in_sh, out_sh = self.shardings(kind)
        if backend != "pallas":
            return jax.jit(_raw_batched_fn(backend, kind, mode),
                           in_shardings=in_sh, out_shardings=out_sh)
        from repro.runtime.sharding import gs_specs

        from . import compat
        in_specs, out_spec = gs_specs(kind, batched=True,
                                      batch_axis=self.batch_axis,
                                      lane_axis=self.lane_axis)
        body = (_lane_body(kind, mode, self.lane_axis)
                if self.lane_shards > 1
                else _raw_batched_fn(backend, kind, mode))
        body = compat.shard_map_unchecked(
            body, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=out_spec)
        return jax.jit(body, in_shardings=in_sh, out_shardings=out_sh)

    def place(self, kind: str, args: tuple) -> tuple:
        """Commit assembled host buffers to their launch shardings.

        Keeps the device layout transfer out of the timed region (the jit
        would otherwise reshard uncommitted arrays inside every call).
        """
        in_sh, _ = self.shardings(kind)
        return tuple(jax.device_put(a, s) for a, s in zip(args, in_sh))


def ShardedExecutor(mesh: Mesh, axis: str = "data") -> Placement:
    """Legacy PR 2 constructor: a batch-only (1-D) placement over ``axis``.

    Kept as a shim so existing callers/tests keep working; the placement
    layer (``Placement``) is the real implementation.
    """
    return Placement(mesh, batch_axis=axis, lane_axis=None)


def as_placement(mesh, mesh_axis: str = "data") -> Placement | None:
    """Normalize every accepted ``mesh=`` form to a Placement (or None).

    ``None``/``0``/empty -> None; a ``Placement`` passes through; a raw
    ``Mesh`` becomes batch-only over ``mesh_axis`` (the pre-placement
    behavior); an int ``N`` or a ``(b, l)`` tuple goes through
    ``Placement.create`` (which validates the device count).
    """
    if mesh is None or isinstance(mesh, Placement):
        return mesh
    if isinstance(mesh, Mesh):
        return Placement(mesh, batch_axis=mesh_axis, lane_axis=None)
    if isinstance(mesh, int):
        return Placement.create(mesh, batch_axis=mesh_axis) if mesh else None
    shape = tuple(mesh)
    if not shape:
        return None
    return Placement.create(shape, batch_axis=mesh_axis)


def auto_placements(plan: SuitePlan, mesh: str, *, mesh_axis: str = "data",
                    backend: str = "xla", dtype=None, row_width: int = 1):
    """Resolve ``mesh="auto"``/``"auto-suite"`` against the cost model.

    ``"auto"`` (the default auto mode) picks a placement PER BUCKET:
    each bucket's members form a single-bucket sub-plan (they re-bucket
    to the identical spec) and ``analysis.cost.select_shape`` scores the
    candidate shapes on that sub-plan alone, so a lane-heavy bucket can
    take a lane split while a member-heavy sibling in the same suite
    shards its batch dim.  Returns a per-bucket list for ``make_work``.

    ``"auto-suite"`` is the pre-PR-10 escape hatch: ONE shape for the
    whole suite (``analysis.cost.auto_placement``), returned as a single
    Placement (or None for a 1x1 choice).

    Both paths hand the cost model the launch backend: lane-sharded
    pallas placements are not charged the GSPMD all-gather replication
    bytes the shard_map path no longer moves (analysis/cost.key_cost).
    Equal shapes share one Placement object, and because the canonical
    placement string is the only placement input to ``ExecKey``, a
    bucket auto-placed at shape (b, l) hits exactly the warm cache
    entries a hand-placed ``mesh=(b, l)`` run of that bucket built.
    """
    from repro.analysis import cost as _cost
    if mesh == "auto-suite":
        shape = _cost.auto_placement(plan, dtype=dtype, row_width=row_width,
                                     backend=backend)
        return as_placement(shape, mesh_axis)
    if mesh != "auto":
        raise ValueError(f"unknown auto mesh mode {mesh!r}; "
                         f"expected 'auto' or 'auto-suite'")
    memo: dict = {}
    out = []
    for bucket in plan.buckets:
        sub = SuitePlan(
            patterns=tuple(plan.patterns[p] for p in bucket.members),
            buckets=(Bucket(spec=bucket.spec,
                            members=tuple(range(len(bucket.members)))),))
        shape = _cost.auto_placement(sub, dtype=dtype, row_width=row_width,
                                     backend=backend)
        if shape not in memo:
            memo[shape] = as_placement(shape, mesh_axis)
        out.append(memo[shape])
    return out


def placement_grid(placement: str) -> tuple[int, int, int]:
    """Parse a canonical ``ExecKey.placement`` string back to
    ``(batch_shards, lane_shards, n_devices)``; ``""`` is ``(1, 1, 1)``.

    The inverse of ``Placement.placement`` for the three canonical forms
    (``data=8/8dev``, ``lane:lane=8/8dev``, ``data=4xlane=2/8dev``),
    used by auditors that only hold an ``ExecKey`` — the live-cache lint
    reconstructs launch avals from it, and the sharding-spec-consistency
    rule checks the lowered module against exactly this grid.  A
    drift-guard test round-trips it against ``Placement`` (the canonical
    batch axis name contains no ``x``, which the 2-D split relies on).
    """
    if not placement:
        return (1, 1, 1)
    body, sep, dev = placement.rpartition("/")
    if not sep or not dev.endswith("dev"):
        raise ValueError(f"not a canonical placement string: {placement!r}")
    ndev = int(dev[:-len("dev")])
    if body.startswith("lane:"):
        return (1, int(body.split("=", 1)[1]), ndev)
    if "x" in body:
        b_part, l_part = body.split("x", 1)
        return (int(b_part.split("=", 1)[1]),
                int(l_part.split("=", 1)[1]), ndev)
    return (int(body.split("=", 1)[1]), 1, ndev)


def placement_axes(placement: str) -> dict[str, int]:
    """Parse a canonical ``ExecKey.placement`` string to its named
    mesh axes, e.g. ``"data=4xlane=2/8dev"`` -> ``{"data": 4,
    "lane": 2}``; ``""`` -> ``{}``.

    The named companion of ``placement_grid``: ``Placement.create``
    builds its Mesh with exactly the non-degenerate axes, so this is
    what a lowered shard_map's ``mesh.shape`` must equal — the
    sharding-spec-consistency rule compares the two (DESIGN.md §16).
    """
    if not placement:
        return {}
    body, sep, dev = placement.rpartition("/")
    if not sep or not dev.endswith("dev"):
        raise ValueError(f"not a canonical placement string: {placement!r}")
    if body.startswith("lane:"):
        body = body[len("lane:"):]
        parts = [body]
    elif "x" in body:
        parts = body.split("x", 1)
    else:
        parts = [body]
    out = {}
    for part in parts:
        name, _, size = part.partition("=")
        out[name] = int(size)
    return out


def bucket_key(backend: str, spec: BucketSpec, dtype, row_width: int,
               mode: str, n_members: int,
               placement: Placement | None) -> ExecKey:
    """The ``ExecKey`` a bucket launch compiles/serves under.

    Single source of truth shared by the hot path
    (``_bucket_executable``) and the static auditor
    (``enumerate_executables``): what spatterlint checks is by
    construction what the cache would build.
    """
    b_shards = placement.batch_shards if placement else 1
    return ExecKey(backend=backend, kind=spec.kind, idx_len=spec.idx_len,
                   footprint=spec.footprint, dtype=jnp.dtype(dtype).name,
                   row_width=row_width,
                   mode=mode if spec.kind == "scatter" else "",
                   batch=pad_batch(n_members, b_shards),
                   placement=placement.placement if placement else "")


def bucket_builder(backend: str, spec: BucketSpec, mode: str,
                   placement: Placement | None) -> Callable[[], Callable]:
    """Zero-arg builder for a bucket executable (what a cache miss runs).

    ``mode`` is the key's mode — already ``""`` for gathers.
    """
    if placement is not None:
        return lambda: placement.build(backend, spec.kind, mode)
    return lambda: _build_executable(backend, spec.kind, mode)


def bucket_avals(spec: BucketSpec, batch: int, lanes: int, dtype,
                 row_width: int) -> tuple:
    """Abstract launch operands for a bucket executable —
    ``jax.ShapeDtypeStruct``s mirroring ``_assemble_bucket``'s concrete
    buffers exactly (gather: table, idx; scatter: dst, idx, vals, keep),
    so an executable can be traced/lowered without materializing host
    buffers or touching devices.
    """
    dtype = jnp.dtype(dtype)
    f_pad, r = spec.footprint, row_width
    idx = jax.ShapeDtypeStruct((batch, lanes), jnp.int32)
    table = jax.ShapeDtypeStruct((batch, f_pad + 1, r), dtype)
    if spec.kind == "gather":
        return (table, idx)
    vals = jax.ShapeDtypeStruct((batch, lanes, r), dtype)
    keep = jax.ShapeDtypeStruct((batch, lanes), jnp.bool_)
    return (table, idx, vals, keep)


def key_avals(key: ExecKey) -> tuple:
    """Abstract launch operands reconstructed from an ``ExecKey`` alone.

    Every field the avals need rides in the key (that is the cache's
    one-entry-one-trace contract), so auditors and the persistence layer
    can trace/serialize an executable without its originating bucket:
    the live-cache lint (analysis/lint.py), ``DiskTier.store``, and the
    daemon's ``POST /warm`` zero-buffer calls all reconstruct from here.
    """
    _, l_shards, _ = placement_grid(key.placement)
    spec = BucketSpec(kind=key.kind, idx_len=key.idx_len,
                      footprint=key.footprint)
    return bucket_avals(spec, key.batch, pad_lanes(key.idx_len, l_shards),
                        jnp.dtype(key.dtype), key.row_width)


def enumerate_executables(plan: SuitePlan, *, backend: str = "xla",
                          dtype=jnp.float32, row_width: int = 1,
                          mode: str = "store", placement=None,
                          mesh_axis: str = "data"
                          ) -> list[tuple[ExecKey, Callable, tuple]]:
    """Every executable ``run_plan`` would ask the cache for, statically.

    Returns ``[(key, builder, avals), ...]`` — one per bucket — without
    compiling or running anything: the enumeration spatterlint audits.
    ``key``/``builder`` come from the same ``bucket_key``/
    ``bucket_builder`` the hot path uses; ``avals`` are the launch
    operands at the key's exact batch (``pad_batch`` of the member
    count — ``best_batch`` polymorphic serving can only substitute a
    *larger* warm batch of the same family, which changes no invariant a
    rule checks).  ``placement`` accepts any ``as_placement`` form, the
    auto strings (``"auto"``/``"auto-suite"``, resolved through
    ``auto_placements`` exactly as ``run_plan`` resolves them), or a
    per-bucket placement list matching ``plan.buckets`` in order.
    """
    if backend not in B.BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    if mode not in SCATTER_MODES:
        raise ValueError(f"unknown mode {mode!r}; "
                         f"expected one of {SCATTER_MODES}")
    if isinstance(placement, str):
        placement = auto_placements(plan, placement, mesh_axis=mesh_axis,
                                    backend=backend, dtype=dtype,
                                    row_width=row_width)
    if isinstance(placement, list):
        if len(placement) != len(plan.buckets):
            raise ValueError(f"{len(placement)} placements for "
                             f"{len(plan.buckets)} buckets")
        placements = [as_placement(p, mesh_axis) for p in placement]
    else:
        placements = [as_placement(placement, mesh_axis)] * len(plan.buckets)
    out = []
    for bucket, pl_b in zip(plan.buckets, placements):
        spec = bucket.spec
        key = bucket_key(backend, spec, dtype, row_width, mode,
                         len(bucket.members), pl_b)
        _, l_shards = pl_b.grid if pl_b else (1, 1)
        lanes = pad_lanes(spec.idx_len, l_shards)
        out.append((key, bucket_builder(backend, spec, key.mode, pl_b),
                    bucket_avals(spec, key.batch, lanes, dtype, row_width)))
    return out


def _bucket_executable(cache: ExecutorCache, backend: str, spec: BucketSpec,
                       dtype, row_width: int, mode: str, n_members: int,
                       placement: Placement | None
                       ) -> tuple[Callable, int, int]:
    """Fetch (or compile) a bucket executable; returns (fn, batch, lanes).

    ``batch`` is the pattern-batch dim the executable was traced for —
    ``pad_batch`` of the member count, or the smallest warm executable's
    larger batch when one exists (``ExecutorCache.serve_poly``); callers
    must assemble the bucket at exactly that batch.  ``lanes`` is the
    launched lane dim — ``pad_lanes`` of the bucket's padded idx_len over
    the placement's lane axis.  Both are pure functions of the ``ExecKey``
    fields (``lanes`` of idx_len + placement), so one cached executable
    still holds exactly one trace and ``misses`` stays an exact compile
    count.
    """
    _, l_shards = placement.grid if placement else (1, 1)
    key = bucket_key(backend, spec, dtype, row_width, mode, n_members,
                     placement)
    builder = bucket_builder(backend, spec, key.mode, placement)
    fn, served = cache.serve_poly(key, builder)
    return fn, served.batch, pad_lanes(spec.idx_len, l_shards)


# ---------------------------------------------------------------------------
# Bucket assembly + execution
# ---------------------------------------------------------------------------

def _assemble_members(spec: BucketSpec, patterns: Sequence[Pattern],
                      dtype, row_width: int, seeds: Sequence[int],
                      batch: int | None = None, mode: str = "store",
                      lanes: int | None = None):
    """Stack member patterns (of one bucket shape) into batched buffers.

    Returns (args, real_lanes) where args feeds the bucket executable and
    real_lanes[b] is member b's un-padded lane count.  Table row F_pad is
    the scratch row every padded lane points at.  ``batch`` (>= member
    count; default ``pad_batch``) sets the padded pattern-batch dim: rows
    past the member count are scratch patterns — all-scratch indices, zero
    tables/payloads — whose outputs the callers drop.  ``lanes`` (>= the
    bucket's idx_len; default exactly it) sets the launched lane dim —
    ``pad_lanes`` hands a lane-sharded launch a shard-multiple here, and
    the extra columns are ordinary padding lanes (scratch-row indices,
    zero payloads).  ``seeds`` gives member b its host-buffer RNG seed —
    per member, because a COALESCED launch (serve/scheduler) stacks
    members from different requests whose seeds may differ; each member's
    buffers are exactly what its own serial run would assemble, which is
    why coalescing preserves bit-identity row by row.

    Scatter buckets also carry the (B_pad, N_pad) last-write-wins keep
    mask for store mode: real lanes reuse the per-pattern mask
    ``make_host_buffers`` already computed (real indices never reach the
    scratch row F_pad, so padding can't change their dedup), and of the
    padding lanes — which ALL point at F_pad — only each row's final lane
    keeps, so the single-pass store kernel's at-most-one-write-per-row
    contract holds for every row including scratch.  In add mode (and in
    gather buckets) no mask is computed; the add executable's keep
    operand is an all-False placeholder it never reads.
    """
    nb = len(patterns)
    if len(seeds) != nb:
        raise ValueError(f"{len(seeds)} seeds for {nb} members")
    b_pad = pad_batch(nb) if batch is None else batch
    if b_pad < nb:
        raise ValueError(f"batch {b_pad} < member count {nb}")
    n_pad = spec.idx_len if lanes is None else lanes
    if n_pad < spec.idx_len:
        raise ValueError(f"lanes {n_pad} < bucket idx_len {spec.idx_len}")
    f_pad, r = spec.footprint, row_width
    idx_b = np.full((b_pad, n_pad), f_pad, np.int32)       # pad -> scratch
    table_b = (np.zeros((b_pad, f_pad + 1, r), np.float32)
               if spec.kind == "gather" else None)
    vals_b = (np.zeros((b_pad, n_pad, r), np.float32)
              if spec.kind == "scatter" else None)
    keep_b = (np.zeros((b_pad, n_pad), bool)
              if spec.kind == "scatter" else None)
    store = spec.kind == "scatter" and mode == "store"
    if store:
        keep_b[:, -1] = True       # scratch row's single write (pad lanes)
    real_lanes = []
    for b, p in enumerate(patterns):
        src, abs_idx, vals, keep = make_host_buffers(p, r, seed=seeds[b])
        n = abs_idx.shape[0]
        real_lanes.append(n)
        idx_b[b, :n] = abs_idx
        if spec.kind == "gather":
            table_b[b, :src.shape[0]] = src
        else:
            vals_b[b, :n] = vals
            if store:
                keep_b[b, :n] = keep      # n == n_pad overwrites the True
    idx = jnp.asarray(idx_b)
    if spec.kind == "gather":
        return (jnp.asarray(table_b, dtype), idx), real_lanes
    dst = jnp.zeros((b_pad, f_pad + 1, r), dtype)
    return (dst, idx, jnp.asarray(vals_b, dtype),
            jnp.asarray(keep_b)), real_lanes


def _assemble_bucket(plan: SuitePlan, bucket: Bucket, dtype, row_width: int,
                     seed: int, batch: int | None = None,
                     mode: str = "store", lanes: int | None = None):
    """One-plan wrapper over ``_assemble_members`` (one seed for all
    members — the serial ``run_plan`` regime)."""
    patterns = [plan.patterns[pos] for pos in bucket.members]
    return _assemble_members(bucket.spec, patterns, dtype, row_width,
                             [seed] * len(patterns), batch=batch,
                             mode=mode, lanes=lanes)


def execute_bucket(plan: SuitePlan, bucket: Bucket, *, backend: str = "xla",
                   dtype=jnp.float32, row_width: int = 1,
                   mode: str = "store", seed: int = 0,
                   cache: ExecutorCache | None = None,
                   mesh=None,
                   mesh_axis: str = "data") -> list[np.ndarray]:
    """Run one bucket once and return per-member un-padded outputs.

    Gathers give member i its (count*index_len, R) rows; scatters give the
    (footprint, R) result table (scratch row trimmed).  ``mesh`` accepts
    any ``as_placement`` form (int, ``(b, l)`` tuple, Mesh, Placement):
    the batch axis splits the launch's pattern-batch dim, the lane axis
    the lane dim.
    """
    if mode not in SCATTER_MODES:
        raise ValueError(f"unknown mode {mode!r}; "
                         f"expected one of {SCATTER_MODES}")
    cache = cache if cache is not None else default_cache()
    placement = as_placement(mesh, mesh_axis)
    spec = bucket.spec
    fn, batch, lanes = _bucket_executable(cache, backend, spec, dtype,
                                          row_width, mode,
                                          len(bucket.members), placement)
    args, real_lanes = _assemble_bucket(plan, bucket, dtype, row_width, seed,
                                        batch=batch, mode=mode, lanes=lanes)
    if placement is not None:
        args = placement.place(spec.kind, args)
    out = np.asarray(jax.block_until_ready(fn(*args)))
    trimmed = []
    for b, pos in enumerate(bucket.members):
        if spec.kind == "gather":
            trimmed.append(out[b, :real_lanes[b]])
        else:
            trimmed.append(out[b, :plan.patterns[pos].footprint()])
    return trimmed


# ---------------------------------------------------------------------------
# Work units: the addressable request-path decomposition
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketWork:
    """One bucket's worth of a suite request: the addressable work unit.

    A suite decomposes into one ``BucketWork`` per bucket (``make_work``);
    each carries everything a ``launch`` needs — the member patterns, the
    execution options, the placement — with NO reference back to the
    originating plan, so work units from *different* requests can be
    stacked into one coalesced launch (serve/scheduler.py).  ``family``
    is the coalescing identity: the batch-stripped ``ExecKey`` — two work
    units with equal families (and equal ``runs``, the timing contract)
    launch the same executable family and may share a launch.

    ``dtype`` is the dtype NAME (a str) so the unit is plain data; the
    launch path re-parses it.  ``seed`` is per work unit — a coalesced
    assembly seeds each member segment with its own work's seed, so every
    member's buffers are exactly what its serial run would build.
    """
    spec: BucketSpec
    patterns: tuple[Pattern, ...]     # member patterns, bucket order
    positions: tuple[int, ...]        # members' positions in their suite
    backend: str
    dtype: str
    row_width: int
    mode: str                         # request scatter mode (store | add)
    runs: int
    seed: int
    digest: bool
    placement: Placement | None

    def __post_init__(self):
        if len(self.patterns) != len(self.positions):
            raise ValueError(f"{len(self.patterns)} patterns vs "
                             f"{len(self.positions)} positions")
        if not self.patterns:
            raise ValueError("work unit needs at least one member")

    @property
    def n_members(self) -> int:
        return len(self.patterns)

    @property
    def family(self) -> ExecKey:
        """Batch-stripped ExecKey — the coalescing identity.  Real keys
        have batch >= 1, so batch=0 can never collide with one."""
        key = bucket_key(self.backend, self.spec, jnp.dtype(self.dtype),
                         self.row_width, self.mode, self.n_members,
                         self.placement)
        return dataclasses.replace(key, batch=0)

    @property
    def real_lanes_total(self) -> int:
        """Un-padded lanes this unit contributes to a launch — what the
        scheduler budgets coalesced assembly size with."""
        return sum(p.count * p.index_len for p in self.patterns)


@dataclasses.dataclass(frozen=True)
class LaunchResult:
    """What one (possibly coalesced) bucket launch produced.

    ``real_lanes``/``out`` rows are in launch order: work unit i of the
    launch owns rows ``[offset_i, offset_i + n_members_i)`` where
    ``offset_i`` is the member count of the units before it — ``demux``
    slices a unit's rows back out with that offset.  ``compiled`` is True
    iff THIS launch claimed the executable's build
    (``ExecutorCache.serve_poly_info``): summed over launches it equals
    the cache's ``misses`` delta exactly, which is how the scheduler
    attributes each compile to one request.  ``degraded`` marks a launch
    served by the xla fallback after the requested backend's builder
    failed (DESIGN.md §14) — set on EVERY launch of a degraded
    executable, not only the one that fell back.
    """
    key: ExecKey                      # the key actually served (best_batch)
    t_bucket: float                   # min over runs (paper §3.5)
    batch: int                        # launched pattern-batch dim
    lanes: int                        # launched lane dim (pad_lanes)
    n_members: int                    # real members across all units
    real_lanes: tuple[int, ...]       # per member, launch order
    out: np.ndarray | None            # batched output (digest launches)
    compiled: bool
    degraded: bool = False


def make_work(plan: SuitePlan, *, backend: str = "xla", dtype=None,
              row_width: int = 1, runs: int = 10, mode: str = "store",
              seed: int = 0, placement=None,
              digest: bool = False) -> list[BucketWork]:
    """Decompose a suite plan into one ``BucketWork`` per bucket.

    Validates the options once (the same checks ``run_plan`` applies), so
    a work unit is always launchable as-is.  ``placement`` is one
    ``Placement | None`` for every bucket, or a per-bucket sequence of
    them (``auto_placements``'s per-bucket mode) matching
    ``plan.buckets`` in order — each work unit carries its own placement
    either way, so nothing downstream changes.
    """
    if backend not in B.BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    if mode not in SCATTER_MODES:
        raise ValueError(f"unknown mode {mode!r}; "
                         f"expected one of {SCATTER_MODES}")
    dtype = jnp.dtype(dtype or jnp.float32)
    if isinstance(placement, (list, tuple)):
        if len(placement) != len(plan.buckets):
            raise ValueError(f"{len(placement)} placements for "
                             f"{len(plan.buckets)} buckets")
        placements = list(placement)
    else:
        placements = [placement] * len(plan.buckets)
    return [
        BucketWork(spec=bucket.spec,
                   patterns=tuple(plan.patterns[pos]
                                  for pos in bucket.members),
                   positions=bucket.members, backend=backend,
                   dtype=dtype.name, row_width=row_width, mode=mode,
                   runs=runs, seed=seed, digest=digest,
                   placement=placements[i])
        for i, bucket in enumerate(plan.buckets)
    ]


def launch(works: Sequence[BucketWork],
           cache: ExecutorCache | None = None) -> LaunchResult:
    """Execute one (possibly coalesced) bucket launch: the pure step.

    ``works`` is one or more work units sharing a ``family`` and ``runs``
    (validated here — the scheduler's coalescing contract); their member
    patterns are stacked in order into ONE padded launch whose batch is
    ``pad_batch`` of the combined member count (or a larger warm
    executable via ``serve_poly``).  With a single work unit this is
    byte-identical to the serial ``run_plan`` bucket step.

    The timed region is exactly the serial path's: one warm-up call,
    then ``runs`` timed executions (fresh zeroed dst per run for
    scatters), min-over-K.  The batched output is pulled to the host
    only when some unit wants digests.
    """
    if not works:
        raise ValueError("launch needs at least one work unit")
    w0 = works[0]
    fam, runs = w0.family, w0.runs
    for w in works[1:]:
        if w.family != fam or w.runs != runs:
            raise ValueError(
                f"cannot coalesce work units with different families/runs: "
                f"{fam}/r{runs} vs {w.family}/r{w.runs}")
    cache = cache if cache is not None else default_cache()
    spec, placement = w0.spec, w0.placement
    dtype = jnp.dtype(w0.dtype)
    _, l_shards = placement.grid if placement else (1, 1)
    n_members = sum(w.n_members for w in works)
    key = bucket_key(w0.backend, spec, dtype, w0.row_width, w0.mode,
                     n_members, placement)
    builder = bucket_builder(w0.backend, spec, key.mode, placement)
    # graceful degradation: a non-xla builder that fails to compile is
    # served by the xla builder for the SAME key, flagged degraded —
    # availability over backend fidelity (DESIGN.md §14)
    fb = (bucket_builder("xla", spec, key.mode, placement)
          if w0.backend != "xla" else None)
    fn, served, compiled, degraded = cache.serve_poly_info(key, builder, fb)
    batch, lanes = served.batch, pad_lanes(spec.idx_len, l_shards)
    patterns = [p for w in works for p in w.patterns]
    seeds = [w.seed for w in works for _ in w.patterns]
    args, real_lanes = _assemble_members(spec, patterns, dtype,
                                         w0.row_width, seeds, batch=batch,
                                         mode=w0.mode, lanes=lanes)
    if placement is not None:
        args = placement.place(spec.kind, args)
    if spec.kind == "scatter":
        dst, idx, vals, keep = args
        jax.block_until_ready(fn(dst, idx, vals, keep))    # compile & warm
        times = []
        for _ in range(runs):
            d = jnp.zeros_like(dst)
            if placement is not None:
                d = placement.place(spec.kind, (d,))[0]
            jax.block_until_ready(d)
            t0 = time.perf_counter()
            out = fn(d, idx, vals, keep)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
    else:
        jax.block_until_ready(fn(*args))                   # compile & warm
        times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
    want_out = any(w.digest for w in works)
    return LaunchResult(key=served, t_bucket=min(times),   # paper §3.5
                        batch=batch, lanes=lanes, n_members=n_members,
                        real_lanes=tuple(real_lanes),
                        out=np.asarray(out) if want_out else None,
                        compiled=compiled, degraded=degraded)


def demux(result: LaunchResult, work: BucketWork,
          offset: int = 0) -> list[tuple[int, RunResult]]:
    """Slice one work unit's per-pattern results back out of a launch.

    ``offset`` is the unit's first row in the launch (sum of member
    counts of the units stacked before it; 0 for a solo launch).
    Returns ``(position, RunResult)`` pairs in the unit's member order.

    Wall time is attributed proportionally to each member's real
    (un-padded) lanes over the launch's TOTAL lanes — scratch batch rows
    count in the denominator at the launched lane width, so a member's
    reported bandwidth is invariant to batch padding, best_batch reuse,
    AND how many foreign members a coalesced launch carried: every
    member reports the bandwidth the launch achieved on its share.
    Digests hash the member's trimmed rows only, so they are a pure
    function of (pattern, seed, mode, dtype) — bit-identical between
    solo and coalesced launches.
    """
    spec = work.spec
    dtype = jnp.dtype(work.dtype)
    elem_bytes = dtype.itemsize * work.row_width
    total_lanes = (sum(result.real_lanes)
                   + (result.batch - result.n_members) * result.lanes)
    out: list[tuple[int, RunResult]] = []
    for i, pos in enumerate(work.positions):
        b = offset + i
        p = work.patterns[i]
        t_i = result.t_bucket * result.real_lanes[b] / total_lanes
        tm = bw.tpu_tile_model(p, elem_bytes)
        dg = None
        if work.digest:
            trim = (result.out[b, :result.real_lanes[b]]
                    if spec.kind == "gather"
                    else result.out[b, :p.footprint()])
            dg = hashlib.sha256(
                np.ascontiguousarray(trim).tobytes()).hexdigest()
        out.append((pos, RunResult(
            pattern=p, backend=work.backend, elem_bytes=elem_bytes,
            row_width=work.row_width, runs=work.runs, time_s=t_i,
            measured_gbs=bw.paper_bandwidth(p, t_i, elem_bytes) / 1e9,
            modeled_gbs=tm.modeled_gbs,
            tile_efficiency=tm.tile_efficiency,
            out_digest=dg,
        )))
    return out


def run_plan(plan: SuitePlan, *, backend: str = "xla", dtype=None,
             row_width: int = 1, runs: int = 10, mode: str = "store",
             seed: int = 0,
             cache: ExecutorCache | None = None,
             mesh=None,
             mesh_axis: str = "data",
             digest: bool = False) -> list[RunResult]:
    """Execute a SuitePlan with paper-style timing (min over ``runs``).

    Returns one RunResult per pattern, in the suite's original order.
    Wall time of a bucket launch is attributed to members proportionally
    to their real (un-padded) lanes.

    A thin serial driver over the work-unit pipeline: the suite
    decomposes into one ``BucketWork`` per bucket (``make_work``), each
    launches solo (``launch``), and per-pattern results demultiplex back
    out (``demux``) — the same three steps the serving scheduler runs
    concurrently with cross-request coalescing (serve/scheduler.py,
    DESIGN.md §13), so the serial and scheduled paths can never drift.

    With ``mesh`` — any ``as_placement`` form: an int N (batch-only), a
    ``(b, l)`` tuple, a raw Mesh (batch-only over ``mesh_axis``), or a
    ``Placement`` — every bucket launch is placed on the 2-D
    (pattern-batch x lane) mesh: the multi-device suite regime.
    Reported bandwidth stays the paper's useful-bytes formula over the
    *aggregate* launch: divide by the device count for per-device numbers.

    With ``digest``, each RunResult carries the sha256 of its trimmed
    computed output (``out_digest``).  The output is a pure function of
    (pattern, seed, mode, dtype) — batch padding and best_batch reuse
    never reach real rows — so equal digests across runs/processes mean
    bit-identical results; the serving layer uses this as its warm-repeat
    identity proof.

    ``mesh="auto"`` places each bucket on the shape the §15 cost model
    prefers for it (``auto_placements`` per-bucket mode);
    ``mesh="auto-suite"`` keeps the old one-shape-per-suite choice.
    """
    cache = cache if cache is not None else default_cache()
    if isinstance(mesh, str):
        placement = auto_placements(plan, mesh, mesh_axis=mesh_axis,
                                    backend=backend, dtype=dtype,
                                    row_width=row_width)
    elif isinstance(mesh, list):
        # explicit per-bucket placements — the hand-placed twin of "auto"
        placement = [as_placement(m, mesh_axis) for m in mesh]
    else:
        placement = as_placement(mesh, mesh_axis)
    works = make_work(plan, backend=backend, dtype=dtype,
                      row_width=row_width, runs=runs, mode=mode, seed=seed,
                      placement=placement, digest=digest)
    results: list[RunResult | None] = [None] * len(plan.patterns)
    for work in works:
        res = launch((work,), cache)
        for pos, r in demux(res, work):
            results[pos] = r
    return results  # type: ignore[return-value]
