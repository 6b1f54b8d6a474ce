"""spatterlint rules — the invariants PRs 1–5 established, as code
(DESIGN.md §12).

Three rule scopes, one registry:

``executable`` rules see an ``ExecUnit`` — one (ExecKey, executable,
abstract launch operands) triple, with its closed jaxpr and lowered
StableHLO text computed lazily.  ``plan`` rules see a ``PlanUnit`` — a
whole-suite view (the SuitePlan, the placement grid, and a re-runnable
enumeration).  ``serve`` rules see a ``ServeUnit`` — source files of the
serving layer (the Python-``ast`` front-end, ``ast_lint``).

Rules return ``list[Violation]`` (empty = clean) and must be pure: an
audit can run against a live daemon's cache and must neither execute nor
mutate anything.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from .report import Violation

# thresholds -----------------------------------------------------------------

# pad_waste budget: the worst shipped suite x placement cell today is
# widelane @ 8x1 at ~83% (few huge-lane patterns, batch-padded 8-wide);
# 90% leaves headroom for membership drift while still catching the
# pathological cells the ROADMAP auto-placement item exists to fix.
PAD_WASTE_BUDGET = 0.90

# host-boundary primitives that must never appear in a timed executable:
# each one is a device<->host round trip inside the §3.5 timed region
HOST_BOUNDARY_PRIMS = (
    "pure_callback", "io_callback", "callback", "debug_callback",
    "host_callback", "outside_call", "device_put", "infeed", "outfeed",
)


# units ----------------------------------------------------------------------

@dataclasses.dataclass
class ExecUnit:
    """One executable under audit: the ExecKey plus lazy views of it.

    ``builder`` compiles nothing — jit wrapping is lazy — and the jaxpr/
    lowered text are traced from abstract ``avals``, so auditing a unit
    never touches a device.  ``fn`` may be pre-set (live-cache audits
    hand the cached executable over directly).  ``cached=True`` marks
    executables that live in the ExecutorCache across calls — what the
    donation rule keys on.
    """
    key: object                       # plan.ExecKey
    builder: Callable[[], Callable] | None
    avals: tuple
    fn: Callable | None = None
    cached: bool = True
    _jaxpr: object = None
    _counts: dict | None = None
    _lowered: str | None = None

    @property
    def label(self) -> str:
        k = self.key
        place = k.placement or "single"
        mode = f" {k.mode}" if k.mode else ""
        return (f"{k.backend}/{k.kind} idx={k.idx_len} fp={k.footprint} "
                f"{k.dtype} r{k.row_width}{mode} b{k.batch} @{place}")

    @property
    def executable(self) -> Callable:
        if self.fn is None:
            self.fn = self.builder()
        return self.fn

    @property
    def jaxpr(self):
        if self._jaxpr is None:
            import jax
            self._jaxpr = jax.make_jaxpr(self.executable)(*self.avals)
        return self._jaxpr

    @property
    def counts(self) -> dict:
        if self._counts is None:
            from repro.core.tracing import count_primitives
            self._counts = count_primitives(self.jaxpr)
        return self._counts

    @property
    def lowered_text(self) -> str:
        if self._lowered is None:
            self._lowered = self.executable.lower(*self.avals).as_text()
        return self._lowered


@dataclasses.dataclass
class PlanUnit:
    """A suite-level audit unit: the plan, the placement grid it would
    launch on, and a zero-arg re-enumeration of its executables.

    A ``mesh="auto"`` cell launches on per-bucket placements rather
    than one grid; ``placements`` then carries the resolved
    ``Placement | None`` list (bucket order) and ``grid`` is vestigial.
    """
    plan: object                      # plan.SuitePlan
    grid: tuple[int, int]             # (batch_shards, lane_shards)
    label: str                        # e.g. "suites/demo.json @ 4x2"
    enumerate: Callable[[], list] | None = None   # -> [(key, builder, avals)]
    placements: list | None = None    # per-bucket [Placement | None]


@dataclasses.dataclass
class ServeUnit:
    """The serving layer's source files: [(path, source), ...]."""
    files: list


# registry -------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    scope: str                        # "executable" | "plan" | "serve"
    doc: str
    fn: Callable

    def check(self, unit) -> list[Violation]:
        return self.fn(unit)


RULES: dict[str, Rule] = {}


def rule(name: str, scope: str):
    def deco(fn):
        if name in RULES:
            raise ValueError(f"duplicate rule {name!r}")
        RULES[name] = Rule(name=name, scope=scope,
                           doc=(fn.__doc__ or "").strip(), fn=fn)
        return fn
    return deco


def rules_for(scope: str, names=None) -> list[Rule]:
    picked = [r for r in RULES.values() if r.scope == scope]
    if names is not None:
        names = set(names)
        unknown = names - set(RULES)
        if unknown:
            raise ValueError(f"unknown rule(s): {sorted(unknown)}")
        picked = [r for r in picked if r.name in names]
    return picked


# executable-scope rules -----------------------------------------------------

@rule("no-sort-in-hot-path", scope="executable")
def _no_sort(unit: ExecUnit) -> list[Violation]:
    """No ``sort`` primitive in a timed executable (PR 3: store-mode
    dedup is a host-precomputed keep mask, never an on-device sort)."""
    n = unit.counts.get("sort", 0)
    if not n:
        return []
    from repro.core.tracing import find_primitive_eqns
    eqns = find_primitive_eqns(unit.jaxpr, ("sort",))
    return [Violation(
        rule="no-sort-in-hot-path", exec_key=unit.label,
        location=eqns[0][1] if eqns else "",
        message=(f"{n} sort primitive(s) in a timed executable — "
                 f"index preprocessing belongs on the host (§4: the "
                 f"bandwidth number times only the gather/scatter)"))]


@rule("single-pallas-call-per-bucket", scope="executable")
def _single_pallas(unit: ExecUnit) -> list[Violation]:
    """The pallas backend launches exactly ONE kernel per bucket PER
    DEVICE (PR 3's single-pass store kernel); other backends launch
    zero.  The census walks into shard_map bodies (core.tracing
    descends every sub-jaxpr), where one ``pallas_call`` equation IS
    one launch on each mesh device — so on the lane-sharded path the
    same ``count == 1`` is the per-device launch census.  Lane-sharded
    pallas keys must additionally run that launch INSIDE the shard_map
    body: a pallas_call left outside is GSPMD-routed, the replicated
    fallback the §16 manual path exists to avoid."""
    from repro.core.plan import placement_grid
    n = unit.counts.get("pallas_call", 0)
    want = 1 if unit.key.backend == "pallas" else 0
    if n != want:
        return [Violation(
            rule="single-pallas-call-per-bucket", exec_key=unit.label,
            message=(f"{n} pallas_call(s) in the jaxpr, expected {want} "
                     f"per device for backend={unit.key.backend!r}"
                     + (" — multi-launch buckets re-pay kernel dispatch "
                        "per tile pass (the pre-PR 3 masked-add + count "
                        "+ blend split)" if want == 1 else "")))]
    if want == 1 and placement_grid(unit.key.placement)[1] > 1:
        from repro.core.tracing import shard_map_pallas_calls
        inside = shard_map_pallas_calls(unit.jaxpr)
        if inside != 1:
            return [Violation(
                rule="single-pallas-call-per-bucket", exec_key=unit.label,
                message=(f"lane-sharded pallas key "
                         f"{unit.key.placement!r} but {inside} "
                         f"pallas_call(s) inside shard_map bodies "
                         f"(expected 1): the launch is GSPMD-routed, "
                         f"not the §16 manual lane split"))]
    return []


@rule("no-host-callback-or-device-put-in-timed-region", scope="executable")
def _no_host_boundary(unit: ExecUnit) -> list[Violation]:
    """No host callback / device_put / infeed inside a timed executable:
    placement transfers happen before timing (``Placement.place``),
    never inside the jitted body (PR 5)."""
    hits = [(p, unit.counts[p]) for p in HOST_BOUNDARY_PRIMS
            if unit.counts.get(p, 0)]
    if not hits:
        return []
    from repro.core.tracing import find_primitive_eqns
    eqns = find_primitive_eqns(unit.jaxpr, [p for p, _ in hits])
    return [Violation(
        rule="no-host-callback-or-device-put-in-timed-region",
        exec_key=unit.label,
        location=eqns[0][1] if eqns else "",
        message=("host-boundary primitive(s) in a timed executable: "
                 + ", ".join(f"{p} x{n}" for p, n in hits)
                 + " — each is a device<->host round trip inside the "
                   "timed region"))]


@rule("donation-honored", scope="executable")
def _donation(unit: ExecUnit) -> list[Violation]:
    """Cached executables never donate operands.  ``GSEngine.build``
    may donate its dst (fresh buffer every call), but an ExecutorCache
    entry is invoked repeatedly with held arrays — donation there is the
    PR 4 'buffer deleted or donated' crash, caught here statically."""
    if not unit.cached:
        return []
    from repro.core.tracing import hlo_stats
    n = hlo_stats(unit.lowered_text)["aliased_params"]
    if not n:
        return []
    return [Violation(
        rule="donation-honored", exec_key=unit.label,
        location=f"{n} aliased/donated operand marker(s) in lowered HLO",
        message=("cached executable donates input buffer(s): the second "
                 "call on the held operands raises 'buffer deleted or "
                 "donated' (the PR 4 repeated-run crash class)"))]


@rule("no-f64-promotion-drift", scope="executable")
def _no_f64(unit: ExecUnit) -> list[Violation]:
    """No float64 aval appears unless the ExecKey says float64: a silent
    x64 promotion doubles bytes moved and falsifies the §3.5 bandwidth
    arithmetic keyed on the declared dtype."""
    if unit.key.dtype == "float64":
        return []
    from repro.core.tracing import find_dtype_eqns
    eqns = find_dtype_eqns(unit.jaxpr, "float64")
    if not eqns:
        return []
    return [Violation(
        rule="no-f64-promotion-drift", exec_key=unit.label,
        location=eqns[0],
        message=(f"{len(eqns)} equation(s) touch float64 in an executable "
                 f"keyed dtype={unit.key.dtype} — promotion drift breaks "
                 f"the useful-bytes bandwidth formula"))]


@rule("sharding-spec-consistency", scope="executable")
def _sharding_consistency(unit: ExecUnit) -> list[Violation]:
    """The ExecKey placement string matches the lowered module: the
    partition count equals the placement's device count and some operand
    carries the ``devices=[b,l]`` tile the grid promises (PR 5's 2-D
    placement layer; a mismatch means the key lies about where the
    executable runs)."""
    from repro.core.plan import placement_grid
    from repro.core.tracing import hlo_stats
    b, l, ndev = placement_grid(unit.key.placement)
    stats = hlo_stats(unit.lowered_text)
    out = []
    if ndev == 1:
        if stats["num_partitions"] > 1:
            out.append(Violation(
                rule="sharding-spec-consistency", exec_key=unit.label,
                message=(f"single-device key but lowered module has "
                         f"num_partitions={stats['num_partitions']}")))
        return out
    if stats["num_partitions"] != ndev:
        out.append(Violation(
            rule="sharding-spec-consistency", exec_key=unit.label,
            message=(f"placement {unit.key.placement!r} promises {ndev} "
                     f"devices but the lowered module has "
                     f"num_partitions={stats['num_partitions']}")))
        return out
    tile = f"devices=[{b},{l}]"
    if not any(tile in s for s in stats["shardings"]):
        out.append(Violation(
            rule="sharding-spec-consistency", exec_key=unit.label,
            location=f"shardings seen: {sorted(stats['shardings'])[:4]}",
            message=(f"placement {unit.key.placement!r} promises tile "
                     f"{tile} but no lowered operand sharding carries it")))
    # the §16 manual path: a placed pallas executable splits its axes
    # with shard_map, and every shard_map's mesh must be exactly the
    # named axes the placement string promises — a drifted mesh (wrong
    # split, renamed axis, stale grid) would still lower and run, just
    # on the wrong decomposition
    if unit.key.backend == "pallas":
        from repro.core.plan import placement_axes
        from repro.core.tracing import shard_map_meshes
        want = placement_axes(unit.key.placement)
        meshes = shard_map_meshes(unit.jaxpr)
        if not meshes:
            out.append(Violation(
                rule="sharding-spec-consistency", exec_key=unit.label,
                message=(f"placed pallas key "
                         f"{unit.key.placement!r} but the jaxpr has no "
                         f"shard_map — the launch relies on GSPMD "
                         f"replication, not the §16 manual split")))
        for got in meshes:
            live = {k: v for k, v in got.items() if v > 1}
            if live != want:
                out.append(Violation(
                    rule="sharding-spec-consistency", exec_key=unit.label,
                    location=f"shard_map mesh: {got}",
                    message=(f"shard_map splits axes {live} but the key "
                             f"placement {unit.key.placement!r} promises "
                             f"{want}")))
    return out


@rule("canonical-exec-key", scope="executable")
def _canonical_key(unit: ExecUnit) -> list[Violation]:
    """Every cached ExecKey is in the canonical ``bucket_key`` format:
    pow-2 bucketed geometry, bracket-stable batch, parseable placement
    string, canonical dtype name, kind-consistent mode.  The coalescing
    scheduler (serve/scheduler.py) stacks concurrent requests' work
    units and re-derives the launch key from the COMBINED member count —
    this rule is the backstop proving those launches reuse the same key
    grammar as solo launches: a raw un-padded batch, a novel placement
    spelling, or a non-canonical dtype alias leaking into the cache
    would fragment the family index ``best_batch`` coalesces through
    and silently break the exact-compile-count telemetry.

    Ad-hoc units (``lint.unit_for`` wraps executables that never came
    from the planner, with zeroed geometry) are out of scope.
    """
    from repro.core.backends import BACKENDS
    from repro.core.engine import SCATTER_MODES
    from repro.core.plan import next_pow2, pad_batch, placement_grid
    k = unit.key
    if k.idx_len == 0 and k.footprint == 0 and k.batch == 0:
        return []                     # unit_for ad-hoc wrapper
    probs = []
    if k.backend not in BACKENDS:
        probs.append(f"backend {k.backend!r} not in {sorted(BACKENDS)}")
    if k.kind not in ("gather", "scatter"):
        probs.append(f"kind {k.kind!r} not gather|scatter")
    try:
        b_shards, _, _ = placement_grid(k.placement)
    except (ValueError, IndexError):
        probs.append(f"placement {k.placement!r} is not a canonical "
                     f"placement string (placement_grid cannot parse it)")
        b_shards = 1
    for name in ("idx_len", "footprint"):
        v = getattr(k, name)
        if v < 1 or next_pow2(v) != v:
            probs.append(f"{name}={v} is not pow-2 bucketed")
    if k.batch < 1 or pad_batch(k.batch, b_shards) != k.batch:
        probs.append(f"batch={k.batch} is not bracket-stable for "
                     f"{b_shards} batch shard(s) (expected "
                     f"pad_batch(batch)==batch; a coalesced launch must "
                     f"pad its combined member count)")
    import jax.numpy as jnp
    try:
        canon = jnp.dtype(k.dtype).name
    except TypeError:
        canon = None
    if canon != k.dtype:
        probs.append(f"dtype {k.dtype!r} is not the canonical dtype name"
                     + (f" ({canon!r})" if canon else ""))
    want_modes = SCATTER_MODES if k.kind == "scatter" else ("",)
    if k.kind in ("gather", "scatter") and k.mode not in want_modes:
        probs.append(f"mode {k.mode!r} invalid for kind={k.kind} "
                     f"(expected one of {want_modes})")
    return [Violation(rule="canonical-exec-key", exec_key=unit.label,
                      location=p.split(" ", 1)[0], message=p)
            for p in probs]


# plan-scope rules -----------------------------------------------------------

@rule("pad-waste-threshold", scope="plan")
def _pad_waste(unit: PlanUnit) -> list[Violation]:
    """``pad_waste(b, l)`` of a suite x placement cell stays within
    budget: pathological padding (one huge-lane pattern batch-padded
    8-wide) silently launches >90% scratch lanes — the signal the
    ROADMAP per-bucket auto-placement item needs surfaced, not buried."""
    if unit.placements is not None:           # mesh="auto": per-bucket
        b, l = "auto", "auto"
        waste = unit.plan.pad_waste_for(unit.placements)
    else:
        b, l = unit.grid
        waste = unit.plan.pad_waste(b, l)
    if waste <= PAD_WASTE_BUDGET:
        return []
    return [Violation(
        rule="pad-waste-threshold", exec_key=unit.label,
        severity="error",
        message=(f"pad_waste({b}, {l}) = {waste:.1%} exceeds the "
                 f"{PAD_WASTE_BUDGET:.0%} budget — "
                 f"{unit.plan.n_buckets} bucket(s), "
                 f"{len(unit.plan.patterns)} pattern(s); pick a smaller "
                 f"batch axis or lane-shard this suite"))]


@rule("cache-key-purity", scope="plan")
def _key_purity(unit: PlanUnit) -> list[Violation]:
    """ExecKeys are a pure function of pattern geometry + placement:
    re-enumerating the same suite yields the identical key sequence, and
    every key field is a plain str/int (an object identity — a Mesh
    repr, an id() — leaking into a key would split the cache and break
    the exact-compile-count telemetry)."""
    if unit.enumerate is None:
        return []
    out = []
    keys1 = [k for k, _, _ in unit.enumerate()]
    keys2 = [k for k, _, _ in unit.enumerate()]
    if keys1 != keys2:
        drift = next((i for i, (a, b) in enumerate(zip(keys1, keys2))
                      if a != b), min(len(keys1), len(keys2)))
        out.append(Violation(
            rule="cache-key-purity", exec_key=unit.label,
            location=f"first drift at bucket {drift}",
            message=("re-enumerating the suite produced different "
                     "ExecKeys — keys are not a pure function of "
                     "geometry + placement, so warm lookups will miss "
                     "and 'misses' stops being an exact compile count")))
    for k in keys1:
        for f in dataclasses.fields(k):
            v = getattr(k, f.name)
            if not isinstance(v, (str, int)):
                out.append(Violation(
                    rule="cache-key-purity", exec_key=unit.label,
                    location=f"{f.name}={v!r}",
                    message=(f"ExecKey.{f.name} is {type(v).__name__}, "
                             f"not str/int — unhashable or "
                             f"identity-keyed fields fragment the cache")))
            elif isinstance(v, str) and "0x" in v:
                out.append(Violation(
                    rule="cache-key-purity", exec_key=unit.label,
                    location=f"{f.name}={v!r}",
                    message=(f"ExecKey.{f.name} embeds what looks like "
                             f"an object address — keys must not depend "
                             f"on object identity")))
    return out


# cost rules (DESIGN.md §15) -------------------------------------------------

@rule("traffic-conservation", scope="executable")
def _traffic_conservation(unit: ExecUnit) -> list[Violation]:
    """Every byte in the lowered StableHLO signature is accounted for by
    the key's traffic model (useful + pad + index + table + keep), and
    vice versa: excess means redundant materialization the planner does
    not know about; deficit means the key lies about its geometry."""
    k = unit.key
    if k.idx_len == 0 and k.footprint == 0 and k.batch == 0:
        return []                      # ad-hoc unit, no planner geometry
    from repro.analysis import cost as C
    from repro.core import hlo
    uc = C.key_cost(k)
    lowered = hlo.main_io_bytes(unit.lowered_text)["total"]
    # backends that dedup without the keep operand (scalar's serial
    # writes) legitimately prune it from the lowered signature — the
    # one allowed deficit; anything else is a geometry lie
    floor = uc.io_bytes - uc.keep_bytes
    tol = max(C.TRAFFIC_TOL * uc.io_bytes, C.TRAFFIC_TOL_FLOOR)
    if lowered > uc.io_bytes + tol or lowered < floor - tol:
        kind = "unaccounted lowered traffic (redundant " \
            "materialization?)" if lowered > uc.io_bytes else \
            "key geometry overstates the lowered module"
        return [Violation(
            rule="traffic-conservation", exec_key=unit.label,
            location=f"lowered={lowered}B predicted={uc.io_bytes}B",
            message=(f"lowered I/O is {lowered} B but the key's traffic "
                     f"model predicts {uc.io_bytes} B "
                     f"(allowed deficit: the {uc.keep_bytes} B keep "
                     f"mask; tolerance {tol:.0f} B): {kind}"))]
    return []


@rule("auto-placement-sane", scope="plan")
def _auto_placement_sane(unit: PlanUnit) -> list[Violation]:
    """On suites with a recorded mesh sweep, the placement ``mesh="auto"``
    would pick must not be dominated by a recorded cell — no cell may
    beat it on *both* measured pad waste and measured GB/s beyond
    tolerance (the cost model may trade the axes, but not lose both)."""
    from repro.analysis import cost as C
    cal = C.Calibration.from_bench()
    cells = cal.sweep.get(C.suite_stem(unit.label))
    if not cells:
        return []                      # no recorded sweep: nothing to audit
    shape = C.select_shape(unit.plan, n_devices=cal.n_dev)
    name = "single" if shape == (1, 1) else f"{shape[0]}x{shape[1]}"
    chosen = cells.get(name)
    if chosen is None:
        return []                      # auto chose an unrecorded cell
    out = []
    for other_name, other in cells.items():
        if other_name == name:
            continue
        if (other["pad_waste"] < chosen["pad_waste"] - C.PAD_WASTE_TOL
                and other["hmean_gbs"] > chosen["hmean_gbs"]
                * (1 + C.GBS_TOL)):
            out.append(Violation(
                rule="auto-placement-sane", exec_key=unit.label,
                location=f"auto={name} dominated-by={other_name}",
                message=(f"auto placement {name} (pad waste "
                         f"{chosen['pad_waste']:.3f}, "
                         f"{chosen['hmean_gbs']:.4g} GB/s) is dominated "
                         f"by recorded cell {other_name} "
                         f"({other['pad_waste']:.3f}, "
                         f"{other['hmean_gbs']:.4g} GB/s) — the cost "
                         f"model disagrees with the measured sweep")))
    return out


@rule("cost-regression", scope="executable")
def _cost_regression(unit: ExecUnit) -> list[Violation]:
    """Predicted I/O bytes per executable may not grow versus the
    committed ``COST_baseline.json`` without updating it (regenerate via
    ``python -m repro.analysis --cost --write-baseline``).  Key-geometry
    only, so it also audits restored DiskTier entries."""
    k = unit.key
    if k.idx_len == 0 and k.footprint == 0 and k.batch == 0:
        return []                      # ad-hoc unit, no planner geometry
    from repro.analysis import cost as C
    baseline = C.load_baseline()
    committed = baseline.get(C.key_id(k))
    if committed is None:
        return []                      # nothing committed for this key
    predicted = C.key_cost(k).io_bytes
    if predicted > committed:
        return [Violation(
            rule="cost-regression", exec_key=unit.label,
            location=f"baseline={committed}B predicted={predicted}B",
            message=(f"predicted I/O bytes grew {committed} -> "
                     f"{predicted} vs the committed baseline — update "
                     f"COST_baseline.json (--write-baseline) if the "
                     f"growth is intended"))]
    return []


# serve-scope rules ----------------------------------------------------------

@rule("serve-lock-discipline", scope="serve")
def _serve_locks(unit: ServeUnit) -> list[Violation]:
    """Shared daemon state is mutated only under its lock (mostly-locked
    inference over repro/serve; PR 4's thread-safety contract)."""
    import ast as _ast

    from .ast_lint import check_lock_discipline
    out = []
    for path, src in unit.files:
        out.extend(check_lock_discipline(_ast.parse(src, filename=path),
                                         path))
    return out


@rule("serve-blocking-under-lock", scope="serve")
def _serve_blocking(unit: ServeUnit) -> list[Violation]:
    """No blocking I/O while holding a daemon lock (the run lock
    serializes execution; everything else must stay cheap)."""
    import ast as _ast

    from .ast_lint import check_blocking_under_lock
    out = []
    for path, src in unit.files:
        out.extend(check_blocking_under_lock(
            _ast.parse(src, filename=path), path))
    return out
