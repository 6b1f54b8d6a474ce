"""spatterlint (repro/analysis, DESIGN.md §12).

Three layers of coverage:

* seeded-violation fixtures — one per rule — proving each rule actually
  FIRES on the defect it encodes (a lint that can't fail is decoration);
* clean-path audits: the shipped suites, the live cache, and the current
  serving layer all lint clean;
* schema/infrastructure: the jax-free report import (mirroring
  test_serve's client drift guard), placement-string parsing, and the
  exit codes of the CLI front-ends (8-dev matrix in a subprocess, like
  test_sharded_plan).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
ROOT = os.path.dirname(SRC)

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

from repro.analysis.ast_lint import lint_source       # noqa: E402
from repro.analysis.lint import (lint_cache, lint_plan, lint_serve,
                                 lint_suite_file, run_rules,
                                 unit_for)            # noqa: E402
from repro.analysis.report import LintReport, Violation   # noqa: E402
from repro.analysis.rules import (RULES, PAD_WASTE_BUDGET,
                                  PlanUnit)           # noqa: E402
from repro.core import ExecutorCache, SuitePlan, make_pattern  # noqa: E402
from repro.core.plan import (ExecKey, _raw_batched_fn,
                             enumerate_executables, placement_grid,
                             run_plan)                # noqa: E402

X = jnp.arange(8.0)


def _fired(violations, rule):
    hits = [v for v in violations if v.rule == rule]
    assert hits, f"rule {rule} did not fire: {violations}"
    return hits


# ---------------------------------------------------------------------------
# seeded-violation fixtures: every rule must fire on its defect
# ---------------------------------------------------------------------------

def test_rule_fires_no_sort_in_hot_path():
    unit = unit_for(jax.jit(jnp.sort), (X,), backend="xla", kind="gather")
    hits = _fired(run_rules(unit, ["no-sort-in-hot-path"]),
                  "no-sort-in-hot-path")
    assert "sort" in hits[0].location        # the offending equation


def test_rule_fires_single_pallas_call_per_bucket():
    # a pallas-keyed executable with ZERO kernel launches (and implicitly
    # the >1 case: want != got)
    unit = unit_for(jax.jit(lambda x: x + 1), (X,), backend="pallas",
                    kind="gather")
    hits = _fired(run_rules(unit, ["single-pallas-call-per-bucket"]),
                  "single-pallas-call-per-bucket")
    assert "expected 1" in hits[0].message


def test_rule_fires_lane_pallas_launch_outside_shard_map():
    # exactly one launch, but at TOP level of a lane-sharded key: the
    # count census passes and the §16 shard_map census must catch the
    # GSPMD-routed fallback (no real devices needed — the rule reads
    # the jaxpr, not the mesh)
    fn = jax.jit(_raw_batched_fn("pallas", "gather", ""))
    table = jnp.zeros((2, 9, 1))
    idx = jnp.zeros((2, 8), jnp.int32)
    unit = unit_for(fn, (table, idx), backend="pallas", kind="gather",
                    placement="lane:lane=2/2dev")
    hits = _fired(run_rules(unit, ["single-pallas-call-per-bucket"]),
                  "single-pallas-call-per-bucket")
    assert "GSPMD-routed" in hits[0].message
    # the same executable under an honest single-device key: clean
    ok = unit_for(fn, (table, idx), backend="pallas", kind="gather")
    assert run_rules(ok, ["single-pallas-call-per-bucket"]) == []


def test_rule_fires_no_host_callback():
    def cb(x):
        return np.asarray(x)

    fn = jax.jit(lambda x: jax.pure_callback(
        cb, jax.ShapeDtypeStruct(x.shape, x.dtype), x))
    unit = unit_for(fn, (X,), backend="xla", kind="gather")
    _fired(run_rules(
        unit, ["no-host-callback-or-device-put-in-timed-region"]),
        "no-host-callback-or-device-put-in-timed-region")


def test_rule_fires_on_device_put_in_timed_region():
    fn = jax.jit(lambda x: jax.device_put(x) * 2)
    unit = unit_for(fn, (X,), backend="xla", kind="gather")
    _fired(run_rules(
        unit, ["no-host-callback-or-device-put-in-timed-region"]),
        "no-host-callback-or-device-put-in-timed-region")


def test_rule_fires_donation_honored():
    # the PR 4 crash class, statically: a CACHED executable that donates
    # its dst would raise 'buffer deleted or donated' on the second call
    fn = jax.jit(_raw_batched_fn("xla", "scatter", "store"),
                 donate_argnums=(0,))
    dst = jnp.zeros((2, 9, 1))
    idx = jnp.zeros((2, 8), jnp.int32)
    vals = jnp.ones((2, 8, 1))
    keep = jnp.ones((2, 8), bool)
    args = (dst, idx, vals, keep)
    unit = unit_for(fn, args, backend="xla", kind="scatter", mode="store")
    _fired(run_rules(unit, ["donation-honored"]), "donation-honored")
    # the same executable is FINE outside the cache (engine semantics)
    free = unit_for(fn, args, backend="xla", kind="scatter", mode="store",
                    cached=False)
    assert run_rules(free, ["donation-honored"]) == []


def test_rule_fires_no_f64_promotion_drift():
    with jax.enable_x64():
        jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.arange(8, dtype=jnp.float64))
    unit = unit_for(None, (jax.ShapeDtypeStruct((8,), np.float64),),
                    backend="xla", kind="gather", dtype="float32",
                    jaxpr=jaxpr)
    hits = _fired(run_rules(unit, ["no-f64-promotion-drift"]),
                  "no-f64-promotion-drift")
    assert "float64" in hits[0].message
    # declared f64 is allowed — the rule checks drift, not the dtype
    ok = unit_for(None, (jax.ShapeDtypeStruct((8,), np.float64),),
                  backend="xla", kind="gather", dtype="float64",
                  jaxpr=jaxpr)
    assert run_rules(ok, ["no-f64-promotion-drift"]) == []


def test_rule_fires_pad_waste_threshold():
    # one 33-lane pattern, batch-padded 8 wide: 33 real lanes of
    # 64 * 8 launched = ~94% waste, over budget
    skinny = make_pattern("UNIFORM:33:1", kind="gather", delta=1, count=1)
    plan = SuitePlan.build([skinny])
    assert plan.pad_waste(8, 1) > PAD_WASTE_BUDGET
    unit = PlanUnit(plan=plan, grid=(8, 1), label="fixture @ 8x1")
    hits = _fired(RULES["pad-waste-threshold"].check(unit),
                  "pad-waste-threshold")
    assert "budget" in hits[0].message
    # and within budget at its natural single-device placement
    assert RULES["pad-waste-threshold"].check(
        PlanUnit(plan=plan, grid=(1, 1), label="fixture @ 1x1")) == []


def test_rule_fires_sharding_spec_consistency():
    # key promises a 4x2 placement over 8 devices; the executable was
    # built unplaced — the lowered module has no partitions at all
    fn = jax.jit(_raw_batched_fn("xla", "gather", ""))
    table = jnp.zeros((4, 9, 1))
    idx = jnp.zeros((4, 8), jnp.int32)
    unit = unit_for(fn, (table, idx), backend="xla", kind="gather",
                    placement="data=4xlane=2/8dev")
    hits = _fired(run_rules(unit, ["sharding-spec-consistency"]),
                  "sharding-spec-consistency")
    assert "num_partitions" in hits[0].message
    # honest single-device key on the same executable: clean
    ok = unit_for(fn, (table, idx), backend="xla", kind="gather")
    assert run_rules(ok, ["sharding-spec-consistency"]) == []


def test_rule_fires_cache_key_purity():
    base = ExecKey(backend="xla", kind="gather", idx_len=8, footprint=8,
                   dtype="float32", row_width=1, mode="", batch=1,
                   placement="")
    plan = SuitePlan.build(
        [make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=1)])

    def impure_enumerate():
        # an object identity leaking into the key: different every call
        key = dataclasses.replace(base,
                                  placement=f"mesh@{hex(id(object()))}")
        return [(key, None, ())]

    unit = PlanUnit(plan=plan, grid=(1, 1), label="fixture",
                    enumerate=impure_enumerate)
    hits = _fired(RULES["cache-key-purity"].check(unit),
                  "cache-key-purity")
    assert len(hits) >= 1


BAD_SERVE_SRC = textwrap.dedent("""\
    import threading
    import time


    class BadDaemon:
        def __init__(self):
            self._run_lock = threading.Lock()
            self.stats = {}
            self.n_requests = 0

        def record(self, key):
            with self._run_lock:
                self.stats[key] = 1
                self.n_requests += 1

        def evict(self, key):
            self.stats.pop(key, None)

        def bump(self):
            self.n_requests += 1

        def slow(self):
            with self._run_lock:
                time.sleep(5)
    """)


def test_rule_fires_serve_lock_discipline():
    violations = lint_source(BAD_SERVE_SRC, "bad_daemon.py")
    lock = _fired(violations, "serve-lock-discipline")
    # both unlocked mutations of guarded state are caught, with lines
    assert len(lock) == 2
    assert {v.location.split(":")[-1] for v in lock} == {"17", "20"}


def test_rule_fires_serve_blocking_under_lock():
    violations = lint_source(BAD_SERVE_SRC, "bad_daemon.py")
    hits = _fired(violations, "serve-blocking-under-lock")
    assert "sleep" in hits[0].message


BAD_SCHEDULER_SRC = textwrap.dedent("""\
    import threading
    import time


    class BadScheduler:
        def __init__(self):
            self._cv = threading.Condition()
            self.queue = []
            self.busy = 0

        def submit(self, item):
            with self._cv:
                self.queue.append(item)
                self._cv.notify_all()

        def steal(self, item):
            self.queue.remove(item)

        def tick(self):
            self.busy += 1

        def _drain_locked(self):
            self.busy -= 1
            time.sleep(1)

        def park(self, ev):
            with self._cv:
                self._cv.wait()
                self._cv.wait_for(lambda: self.queue)
                ev.wait()
    """)


def test_ast_lint_condition_variable_counts_as_lock():
    # the scheduler's idiom: with self._cv: acquires the Condition's
    # lock, so a mutation inside seeds guarded-attr inference and the
    # unguarded mutations elsewhere fire — extending the PR 6 lint to
    # cover serve/scheduler.py without annotations
    violations = lint_source(BAD_SCHEDULER_SRC, "bad_scheduler.py")
    lock = _fired(violations, "serve-lock-discipline")
    assert {v.location.split(":")[-1] for v in lock} == {"17", "20"}
    assert all("queue" in v.message or "busy" in v.message for v in lock)


def test_ast_lint_locked_suffix_method_convention():
    # *_locked methods assert caller-held locks: their mutations count
    # as guarded (busy -= 1 on line 23 must NOT fire) while blocking
    # calls inside them DO fire, same as a lexical with-block
    violations = lint_source(BAD_SCHEDULER_SRC, "bad_scheduler.py")
    lines = {v.location.split(":")[-1]
             for v in violations if v.rule == "serve-lock-discipline"}
    assert "23" not in lines
    blocking = _fired(violations, "serve-blocking-under-lock")
    assert any("sleep" in v.message and v.location.endswith(":24")
               for v in blocking)


def test_ast_lint_sanctions_wait_on_held_cv_only():
    # Condition.wait / wait_for on the HELD cv atomically release the
    # lock — the one blocking call cv code cannot exist without — but
    # ev.wait() under the cv is a genuine deadlock shape and stays
    # flagged
    violations = lint_source(BAD_SCHEDULER_SRC, "bad_scheduler.py")
    blocking = [v for v in violations
                if v.rule == "serve-blocking-under-lock"]
    flagged_lines = {v.location.split(":")[-1] for v in blocking}
    assert "28" not in flagged_lines          # self._cv.wait()
    assert "29" not in flagged_lines          # self._cv.wait_for(...)
    assert "30" in flagged_lines              # ev.wait() under the cv


def test_ast_lint_lock_token_matching_is_word_based():
    # "_recv" must not read as a cv; "state_cond" must — token-wise
    # matching, not substring soup
    from repro.analysis.ast_lint import _is_lock_expr
    import ast as _ast

    def expr(s):
        return _ast.parse(s, mode="eval").body

    assert not _is_lock_expr(expr("self._recv"))
    assert _is_lock_expr(expr("self._cv"))
    assert _is_lock_expr(expr("self.state_cond"))
    assert _is_lock_expr(expr("self._memo_lock"))
    assert not _is_lock_expr(expr("self.blocked"))


def test_rule_fires_canonical_exec_key():
    # a key a coalescing bug could mint: un-padded combined batch,
    # non-pow2 geometry, dtype alias, unparseable placement spelling
    from repro.core.plan import ExecKey
    from repro.analysis.rules import ExecUnit
    bad = ExecKey(backend="xla", kind="gather", idx_len=24, footprint=48,
                  dtype="f32", row_width=1, mode="", batch=6,
                  placement="mesh(4,2)")
    unit = ExecUnit(key=bad, builder=None, avals=())
    hits = _fired(run_rules(unit, ["canonical-exec-key"]),
                  "canonical-exec-key")
    msgs = " | ".join(v.message for v in hits)
    assert "bracket-stable" in msgs           # batch=6 not padded
    assert "pow-2" in msgs                    # idx_len=24 / footprint=48
    assert "canonical dtype" in msgs          # "f32" alias
    assert "placement" in msgs                # placement_grid can't parse


def test_canonical_exec_key_accepts_planner_keys_and_adhoc_units():
    from repro.core.plan import BucketSpec, bucket_key
    from repro.analysis.rules import ExecUnit
    # exactly what the hot path and a coalesced launch both mint
    good = bucket_key("xla", BucketSpec("scatter", 8, 16), jnp.float32,
                      1, "store", 6, None)     # batch 6 -> bracket 8
    assert good.batch == 8
    unit = ExecUnit(key=good, builder=None, avals=())
    assert run_rules(unit, ["canonical-exec-key"]) == []
    # unit_for's zeroed ad-hoc keys are out of scope, not violations
    adhoc = unit_for(jax.jit(lambda x: x + 1), (X,), backend="xla",
                     kind="gather")
    assert run_rules(adhoc, ["canonical-exec-key"]) == []


def test_ast_lint_allows_unguarded_by_design_state():
    # attributes never mutated under ANY lock are handler-local by
    # design (the daemon's server-thread handle): no false positive
    src = textwrap.dedent("""\
        class Daemon:
            def __init__(self):
                import threading
                self._memo_lock = threading.Lock()
                self.memo = {}
                self._thread = None

            def put(self, k, v):
                with self._memo_lock:
                    bounded_put(self.memo, k, v)

            def start(self):
                self._thread = object()

            def stop(self):
                self._thread = None
        """)
    assert lint_source(src, "good.py") == []


# ---------------------------------------------------------------------------
# clean paths: shipped code must lint clean
# ---------------------------------------------------------------------------

def test_current_serve_layer_passes_ast_lint():
    report = lint_serve()
    assert report.n_units >= 3                # daemon, client, schema, ...
    assert report.ok and report.violations == [], report.summary()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_demo_suite_lints_clean(backend):
    report = lint_suite_file(os.path.join(ROOT, "suites", "demo.json"),
                             backends=(backend,))
    assert report.ok and report.n_violations == 0, report.summary()
    assert report.n_units > 1                 # buckets + the plan unit


def test_live_cache_lints_clean_and_readonly():
    cache = ExecutorCache()
    pats = [make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=16),
            make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=16,
                         name="s")]
    run_plan(SuitePlan.build(pats), backend="xla", runs=1, cache=cache)
    before = cache.stats()
    report = lint_cache(cache)
    assert report.ok and report.n_units == before.size > 0
    # the audit must not perturb serving telemetry
    assert cache.stats() == before


def test_live_cache_lint_catches_poisoned_entry():
    # seed the cache with a donating executable under a planner-shaped
    # key: GET /lint's audit path must catch the PR 4 crash class
    cache = ExecutorCache()
    key = ExecKey(backend="xla", kind="scatter", idx_len=8, footprint=8,
                  dtype="float32", row_width=1, mode="store", batch=1,
                  placement="")
    bad = jax.jit(_raw_batched_fn("xla", "scatter", "store"),
                  donate_argnums=(0,))
    cache.get(key, lambda: bad)
    report = lint_cache(cache)
    assert not report.ok
    assert [v.rule for v in report.violations] == ["donation-honored"]


def test_enumeration_matches_live_cache_keys():
    # the static enumeration IS what the hot path compiles: same keys
    pats = [make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=16),
            make_pattern("UNIFORM:8:2", kind="scatter", delta=2, count=16,
                         name="s")]
    plan = SuitePlan.build(pats)
    cache = ExecutorCache()
    run_plan(plan, backend="xla", runs=1, cache=cache)
    static = {k for k, _, _ in enumerate_executables(plan, backend="xla")}
    live = {k for k, _ in cache.entries()}
    assert static == live


def test_lint_plan_counts_units():
    pats = [make_pattern("UNIFORM:8:1", kind="gather", delta=8, count=16)]
    report = lint_plan(pats, backend="xla", label="inline")
    assert report.ok and report.n_units == 2          # 1 bucket + plan
    assert "no-sort-in-hot-path" in report.rules
    assert "pad-waste-threshold" in report.rules


# ---------------------------------------------------------------------------
# report schema: shared, jax-free, round-trippable
# ---------------------------------------------------------------------------

def test_report_schema_roundtrip_and_merge():
    v = Violation(rule="r", message="m", exec_key="k", location="l")
    r1 = LintReport(violations=[v], n_units=3, rules=("r",),
                    meta={"cells": [{"cell": "a"}]})
    r2 = LintReport(n_units=2, rules=("r", "s"),
                    meta={"cells": [{"cell": "b"}]})
    merged = r1.merge(r2)
    assert (merged.n_units, merged.n_violations) == (5, 1)
    assert merged.rules == ("r", "s")
    assert [c["cell"] for c in merged.meta["cells"]] == ["a", "b"]
    doc = json.loads(json.dumps(merged.to_json()))
    back = LintReport.from_json(doc)
    assert back.to_json() == merged.to_json()
    assert not back.ok and back.violations[0] == v
    # warnings don't fail the audit; unknown fields are rejected
    assert LintReport(violations=[Violation(
        rule="r", message="m", severity="warning")]).ok
    with pytest.raises(ValueError, match="unknown"):
        Violation.from_json({"rule": "r", "message": "m", "oops": 1})
    with pytest.raises(ValueError, match="severity"):
        Violation(rule="r", message="m", severity="fatal")


def test_report_and_ast_lint_import_jax_free():
    # the report schema is the wire format CI and dashboards parse; like
    # the serve client, parsing a lint report must not pay the jax import
    code = ("import sys; sys.path.insert(0, %r); "
            "import repro.analysis.report, repro.analysis.ast_lint; "
            "assert 'jax' not in sys.modules, 'analysis.report pulls jax'; "
            "r = repro.analysis.report.LintReport.from_json("
            "{'violations': [], 'n_units': 0}); "
            "assert r.ok; print('OK')" % SRC)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_placement_grid_parses_canonical_strings():
    assert placement_grid("") == (1, 1, 1)
    assert placement_grid("data=8/8dev") == (8, 1, 8)
    assert placement_grid("lane:lane=8/8dev") == (1, 8, 8)
    assert placement_grid("data=4xlane=2/8dev") == (4, 2, 8)
    with pytest.raises(ValueError, match="placement"):
        placement_grid("not-a-placement")
    # round-trip against the writer on the one mesh tier-1 can build
    from repro.core.plan import Placement
    p = Placement.create(1)
    assert placement_grid(p.placement) == (*p.grid, 1)


# ---------------------------------------------------------------------------
# front-end exit codes (single-device paths)
# ---------------------------------------------------------------------------

def test_matrix_runner_unbuildable_cell_is_exit_2():
    from repro.analysis.__main__ import main
    rc = main(["--suite", os.path.join(ROOT, "suites", "demo.json"),
               "--mesh", "4096x1", "--backend", "xla"])
    assert rc == 2


def test_matrix_runner_clean_run_is_exit_0(tmp_path):
    from repro.analysis.__main__ import main
    out = str(tmp_path / "LINT_report.json")
    rc = main(["--suite", os.path.join(ROOT, "suites", "demo.json"),
               "--backend", "xla", "--out", out])
    assert rc == 0
    doc = json.load(open(out))
    assert doc["ok"] is True and doc["n_units"] > 0
    # serve lint rides along by default
    assert "serve-lock-discipline" in doc["rules"]


# ---------------------------------------------------------------------------
# acceptance: 8-device matrix + seeded pad-waste violation through the
# real front-ends, in a subprocess (tier-1 sees one device)
# ---------------------------------------------------------------------------

MATRIX_8DEV = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, %(src)r)
    import json, tempfile
    import jax
    assert len(jax.devices()) == 8, jax.devices()

    from repro.core.plan import Placement, placement_grid

    # placement_grid round-trips every canonical placement form
    for shape in (8, (8, 1), (4, 2), (1, 8)):
        p = Placement.create(shape)
        b, l, nd = placement_grid(p.placement)
        assert (b, l) == p.grid and nd == len(p.mesh.devices.flat), \\
            (shape, p.placement)

    # real placed executables lint clean (positive half of
    # sharding-spec-consistency: the lowered modules DO carry the tile)
    from repro.analysis.lint import lint_suite_file
    for mesh in ((8, 1), (4, 2), (1, 8)):
        r = lint_suite_file(%(demo)r, mesh=mesh)
        assert r.ok and r.n_violations == 0, r.summary()

    # seeded §16 violations: the rule halves that walk INTO shard_map
    # bodies, which need a real lane mesh to trace
    import jax.numpy as jnp
    from repro.analysis.lint import run_rules, unit_for
    from repro.core import make_pattern
    from repro.core.plan import SuitePlan, enumerate_executables

    plan = SuitePlan.build([make_pattern("UNIFORM:8:1", kind="gather",
                                         delta=8, count=64, name="g")])
    key, builder, avals = next(iter(enumerate_executables(
        plan, backend="pallas", dtype=jnp.float32, mode="store",
        placement=Placement.create((1, 8)))))
    lane_fn = builder()

    # (1) double launch: two shard_map'd kernels per bucket — the count
    # census sees both because the walk descends into shard_map bodies
    double = jax.jit(lambda *a: lane_fn(*a) + lane_fn(*a))
    unit = unit_for(double, avals, backend="pallas", kind="gather",
                    placement=key.placement)
    viol = run_rules(unit, ["single-pallas-call-per-bucket"])
    assert any("2 pallas_call" in v.message for v in viol), viol

    # (2) mesh drift: the executable shard_maps over {lane: 8} but the
    # key placement promises a 4x2 split — same device count, so only
    # the shard_map-mesh census can tell them apart
    lying = unit_for(lane_fn, avals, backend="pallas", kind="gather",
                     placement="data=4xlane=2/8dev")
    viol = run_rules(lying, ["sharding-spec-consistency"])
    assert any("shard_map splits axes" in v.message for v in viol), viol
    # honest key on the same executable: the shard_map census is clean
    honest = unit_for(lane_fn, avals, backend="pallas", kind="gather",
                      placement=key.placement)
    assert not any("shard_map" in v.message
                   for v in run_rules(honest,
                                      ["sharding-spec-consistency"]))

    # seeded pad-waste violation through both CLI front-ends: exit 1
    bad = [{"name": "skinny", "kernel": "Gather",
            "pattern": "UNIFORM:33:1", "delta": 1, "count": 1}]
    with tempfile.TemporaryDirectory() as td:
        suite = os.path.join(td, "bad.json")
        out = os.path.join(td, "report.json")
        json.dump(bad, open(suite, "w"))

        from repro.analysis.__main__ import main
        rc = main(["--suite", suite, "--mesh", "8x1",
                   "--backend", "xla", "--out", out])
        assert rc == 1, rc
        doc = json.load(open(out))
        assert doc["ok"] is False
        assert any(v["rule"] == "pad-waste-threshold"
                   for v in doc["violations"]), doc

        sys.path.insert(0, %(examples)r)
        import spatter_cli
        sys.argv = ["spatter_cli.py", "--lint", suite, "--mesh", "8x1",
                    "--backend", "xla"]
        try:
            spatter_cli.main()
            raise AssertionError("expected SystemExit(1)")
        except SystemExit as e:
            assert e.code == 1, e.code
    print("OK")
    """)


def test_acceptance_lint_matrix_8dev_subprocess():
    code = MATRIX_8DEV % {
        "src": SRC,
        "demo": os.path.join(ROOT, "suites", "demo.json"),
        "examples": os.path.join(ROOT, "examples"),
    }
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "OK" in r.stdout
