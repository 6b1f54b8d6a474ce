"""serve/scheduler — the concurrent, coalescing work-unit executor
(DESIGN.md §13).

The ISSUE 7 acceptance contract, telemetry-proven:

* N concurrent identical requests cause exactly ONE compile and fewer
  launches than requests (bucket-affinity coalescing);
* digests under concurrency are bit-identical to the serial ``run_plan``
  path, shared-suite and mixed-suite alike;
* a full queue rejects at ``submit`` — before any JAX work runs — and a
  request is queued whole or not at all;
* ``stop(drain=True)`` completes queued + in-flight work before the
  workers exit; ``stop(drain=False)`` fails queued tickets instead.

Determinism recipe: ``pause()`` stages every request in the queue, then
``resume()`` releases the workers — the first worker to wake sweeps the
whole same-key queue into one launch, so "coalesced" stops being a race
and becomes an assertion.
"""
import time

import pytest

from repro.core import ExecutorCache, SuitePlan, make_pattern
from repro.core.plan import make_work, run_plan
from repro.serve.scheduler import (QUARANTINE_AFTER, DeadlineExceeded,
                                   FamilyQuarantined, QueueFull,
                                   RequestCancelled, Scheduler,
                                   SchedulerStopped)

# one bucket: the sharpest coalescing target (N requests -> 1 launch)
SINGLE = SuitePlan.build(
    [make_pattern("UNIFORM:8:2", kind="gather", delta=2, count=32)])

# three buckets across kinds/shapes, same shape as test_serve's SUITE
MIXED = SuitePlan.build([
    make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=16),
    make_pattern("UNIFORM:8:4", kind="gather", delta=4, count=64),
    make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=16),
])


def _digests(results):
    return [r.out_digest for r in results]


def _ticket_digests(ticket, n):
    assert sorted(ticket.results) == list(range(n))
    return [ticket.results[i].out_digest for i in range(n)]


def _serial_reference(plan, runs):
    return _digests(run_plan(plan, runs=runs, cache=ExecutorCache(),
                             digest=True))


# ---------------------------------------------------------------------------
# coalescing: exactly one compile, fewer launches than requests
# ---------------------------------------------------------------------------

def test_identical_concurrent_requests_one_compile_fewer_launches():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=2)
    n = 8
    try:
        sched.pause()
        tickets = [sched.submit(make_work(SINGLE, runs=2, digest=True))
                   for _ in range(n)]
        assert sched.snapshot()["queue_depth"] == n
        sched.resume()
        for t in tickets:
            t.wait(timeout=300)
    finally:
        sched.stop()

    # staged queue -> ONE coalesced launch serves all n requests
    snap = sched.snapshot()
    assert snap["total_launches"] == 1
    assert snap["coalesced_launches"] == 1
    assert snap["total_launches"] < n
    assert snap["submitted"] == n and snap["completed"] == n

    # exactly one compile, attributed to exactly one ticket; everyone
    # else rode the launch warm — and the sum matches the cache's own
    # exact compile count
    assert sum(t.misses for t in tickets) == 1
    assert cache.stats().misses == 1
    assert sum(1 for t in tickets if t.misses == 1) == 1
    assert all(t.launches == 1 for t in tickets)
    assert all(t.coalesced_launches == 1 for t in tickets)
    assert all(t.queued_ms >= 0.0 for t in tickets)

    # every request's digests are bit-identical to the serial path
    ref = _serial_reference(SINGLE, runs=2)
    assert all(d is not None for d in ref)
    for t in tickets:
        assert _ticket_digests(t, len(ref)) == ref


def test_coalesced_launch_shares_its_phases_and_names_its_requests(
        tmp_path):
    """Both tickets of one coalesced launch carry its whole phases; the
    launch's spans carry ``launch=`` and both request ids."""
    import glob

    import jax
    from jax.profiler import ProfileData
    sched = Scheduler(ExecutorCache(), workers=1)
    try:
        sched.pause()
        tickets = [sched.submit(make_work(SINGLE, runs=2, digest=True),
                                request_id=rid) for rid in (41, 42)]
        with jax.profiler.trace(str(tmp_path)):
            sched.resume()
            for t in tickets:
                t.wait(timeout=300)
    finally:
        sched.stop()
    assert sched.snapshot()["total_launches"] == 1
    a, b = (t.telemetry()["phases_ms"] for t in tickets)
    assert a == b
    assert set(a) == {"plan.draw", "plan.stack", "plan.to_device",
                      "plan.warmup", "plan.runs", "plan.fetch",
                      "plan.digest"}
    assert all(ms > 0 for ms in a.values())
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
    stats = [dict(e.stats) for plane in ProfileData.from_file(xplane).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("plan.")]
    assert stats and all(s["launch"] == stats[0]["launch"] for s in stats)
    assert {s["reqs"] for s in stats} == {"41;42"}


def test_mixed_suite_concurrency_matches_serial_digests():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=2)
    try:
        sched.pause()
        # interleave two different suites so the queue mixes families
        mixed = [sched.submit(make_work(MIXED, runs=1, digest=True))
                 for _ in range(3)]
        single = [sched.submit(make_work(SINGLE, runs=1, digest=True))
                  for _ in range(3)]
        sched.resume()
        for t in mixed + single:
            t.wait(timeout=300)
    finally:
        sched.stop()

    ref_mixed = _serial_reference(MIXED, runs=1)
    ref_single = _serial_reference(SINGLE, runs=1)
    for t in mixed:
        assert _ticket_digests(t, len(ref_mixed)) == ref_mixed
    for t in single:
        assert _ticket_digests(t, len(ref_single)) == ref_single

    # exactness survives bracket proliferation: a coalesced launch may
    # land in a larger pow-2 bracket (extra compile per family), but the
    # summed per-ticket misses still equal the cache's compile count
    assert (sum(t.misses for t in mixed + single)
            == cache.stats().misses)
    assert sched.snapshot()["total_launches"] < 6 * 2  # fewer than items


def test_coalesce_member_cap_splits_launches():
    cache = ExecutorCache()
    # cap so small that two single-bucket requests cannot share a launch
    sched = Scheduler(cache, workers=1, max_coalesce_members=1)
    try:
        sched.pause()
        tickets = [sched.submit(make_work(SINGLE, runs=1, digest=True))
                   for _ in range(3)]
        sched.resume()
        for t in tickets:
            t.wait(timeout=300)
    finally:
        sched.stop()
    snap = sched.snapshot()
    assert snap["total_launches"] == 3           # no coalescing possible
    assert snap["coalesced_launches"] == 0
    assert all(t.coalesced_launches == 0 for t in tickets)
    # still exactly one compile total: the cache serves warm repeats
    assert cache.stats().misses == 1
    assert sum(t.misses for t in tickets) == 1


# ---------------------------------------------------------------------------
# backpressure: reject at submit, before any JAX work
# ---------------------------------------------------------------------------

def test_queue_full_rejects_before_any_launch():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=1, max_queue=2)
    try:
        sched.pause()
        t1 = sched.submit(make_work(SINGLE, runs=1))
        t2 = sched.submit(make_work(SINGLE, runs=1))
        with pytest.raises(QueueFull) as ei:
            sched.submit(make_work(SINGLE, runs=1))
        assert ei.value.depth == 2 and ei.value.limit == 2
        # the rejection happened BEFORE the run: nothing compiled,
        # nothing launched
        assert cache.stats().misses == 0
        assert sched.snapshot()["total_launches"] == 0
        sched.resume()
        t1.wait(timeout=300)
        t2.wait(timeout=300)
    finally:
        sched.stop()
    assert sched.snapshot()["completed"] == 2


def test_submit_is_all_or_nothing():
    sched = Scheduler(ExecutorCache(), workers=1, max_queue=4)
    try:
        sched.pause()
        sched.submit(make_work(MIXED, runs=1))       # 3 of 4 slots
        with pytest.raises(QueueFull):
            sched.submit(make_work(MIXED, runs=1))   # 3 more won't fit
        # the failed submit left NO partial items behind
        assert sched.snapshot()["queue_depth"] == 3
        assert sched.snapshot()["submitted"] == 1
        sched.resume()
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# shutdown: drain vs fail-fast
# ---------------------------------------------------------------------------

def test_stop_drains_queued_work():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=2)
    sched.pause()
    tickets = [sched.submit(make_work(MIXED, runs=1, digest=True))
               for _ in range(3)]
    # stop() un-pauses, lets the workers drain the queue, and only then
    # joins them — every ticket must resolve with full results
    sched.stop(drain=True)
    ref = _serial_reference(MIXED, runs=1)
    for t in tickets:
        assert t.done.is_set()
        t.wait(timeout=0.1)                      # no error to re-raise
        assert _ticket_digests(t, len(ref)) == ref
    snap = sched.snapshot()
    assert snap["queue_depth"] == 0
    assert snap["completed"] == 3 and snap["failed"] == 0
    assert snap["stopping"] is True
    with pytest.raises(SchedulerStopped):
        sched.submit(make_work(SINGLE, runs=1))


def test_stop_without_drain_fails_queued_tickets():
    sched = Scheduler(ExecutorCache(), workers=1)
    sched.pause()
    tickets = [sched.submit(make_work(SINGLE, runs=1)) for _ in range(2)]
    sched.stop(drain=False)
    for t in tickets:
        assert t.done.is_set()
        with pytest.raises(SchedulerStopped):
            t.wait(timeout=0.1)
    assert sched.snapshot()["failed"] == 2


# ---------------------------------------------------------------------------
# failure isolation: one bad request cannot poison its neighbors
# ---------------------------------------------------------------------------

def test_launch_failure_fails_only_its_ticket():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=1)

    # a ticket that fails mid-suite (here: injected, as if an earlier
    # bucket launch raised) must have its still-queued items retired
    # dead — no launch, no results — while neighbors run untouched
    sched.pause()
    good = sched.submit(make_work(SINGLE, runs=1, digest=True))
    victim = sched.submit(make_work(MIXED, runs=1, digest=True))
    with sched._cv:
        victim.error = RuntimeError("injected: earlier bucket failed")
        victim.done.set()
    sched.resume()
    good.wait(timeout=300)
    sched.stop()
    # the good ticket completed untouched; the victim's dead items were
    # retired without running (3 items retired, only SINGLE launched +
    # however the sweep batched — crucially, results stay empty)
    assert good.error is None and len(good.results) == 1
    assert victim.results == {}
    assert sched.snapshot()["queue_depth"] == 0


# ---------------------------------------------------------------------------
# deadlines, cancellation, quarantine (ISSUE 8 fault tolerance)
# ---------------------------------------------------------------------------

def test_deadline_expired_in_queue_never_launches():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=1)
    try:
        # pause -> the item sits queued past its deadline -> resume: the
        # worker must retire it dead, not launch it
        sched.pause()
        doomed = sched.submit(make_work(SINGLE, runs=1), deadline_s=0.05)
        fine = sched.submit(make_work(SINGLE, runs=1, digest=True))
        time.sleep(0.2)
        sched.resume()
        with pytest.raises(DeadlineExceeded):
            doomed.wait(timeout=300)
        fine.wait(timeout=300)
    finally:
        sched.stop()
    snap = sched.snapshot()
    # the expired request launched NOTHING and compiled NOTHING of its
    # own: the only launch/compile belongs to the healthy neighbor
    assert snap["deadline_expired"] == 1
    assert snap["failed"] == 1 and snap["completed"] == 1
    assert doomed.results == {} and doomed.launches == 0
    assert snap["total_launches"] == fine.launches == 1
    assert cache.stats().misses == 1 and fine.misses == 1


def test_unexpired_deadline_is_harmless():
    sched = Scheduler(ExecutorCache(), workers=1)
    try:
        t = sched.submit(make_work(SINGLE, runs=1, digest=True),
                         deadline_s=300.0)
        t.wait(timeout=300)
    finally:
        sched.stop()
    assert t.error is None and len(t.results) == 1
    assert sched.snapshot()["deadline_expired"] == 0


def test_cancel_removes_queued_items_before_launch():
    cache = ExecutorCache()
    sched = Scheduler(cache, workers=1)
    try:
        sched.pause()
        victim = sched.submit(make_work(MIXED, runs=1))
        survivor = sched.submit(make_work(SINGLE, runs=1, digest=True))
        removed = sched.cancel(victim)
        assert removed == 3                      # all queued items pulled
        assert sched.snapshot()["queue_depth"] == 1
        sched.resume()
        with pytest.raises(RequestCancelled):
            victim.wait(timeout=300)
        survivor.wait(timeout=300)
    finally:
        sched.stop()
    snap = sched.snapshot()
    assert snap["cancelled"] == 1 and snap["failed"] == 1
    # cancelled items never launched: only the survivor's launch ran
    assert victim.results == {} and victim.launches == 0
    assert snap["total_launches"] == 1
    assert cache.stats().misses == 1
    # cancelling a completed ticket is a no-op
    assert sched.cancel(survivor) == 0
    assert sched.snapshot()["cancelled"] == 1


def test_quarantine_after_consecutive_launch_failures():
    from repro.serve.faults import FaultInjector
    n_fail = QUARANTINE_AFTER
    faults = FaultInjector.from_spec(f"launch:fail:{n_fail}")
    sched = Scheduler(ExecutorCache(), workers=1, max_coalesce_members=1,
                      faults=faults)
    try:
        # each submit is its own launch (coalescing capped off), so the
        # streak builds one failure at a time
        for _ in range(n_fail):
            t = sched.submit(make_work(SINGLE, runs=1))
            with pytest.raises(Exception):
                t.wait(timeout=300)
        assert sched.snapshot()["quarantined_families"] == 1
        # the family now fails FAST: no launch, injector exhausted
        t = sched.submit(make_work(SINGLE, runs=1))
        with pytest.raises(FamilyQuarantined):
            t.wait(timeout=300)
        assert sched.snapshot()["total_launches"] == n_fail
        # operator reset: the family launches (and succeeds) again
        assert sched.clear_quarantine() == 1
        t = sched.submit(make_work(SINGLE, runs=1, digest=True))
        t.wait(timeout=300)
        assert len(t.results) == 1
    finally:
        sched.stop()
    assert sched.snapshot()["quarantined_families"] == 0
