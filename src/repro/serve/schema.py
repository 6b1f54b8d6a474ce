"""Request/response schema for spatterd (DESIGN.md §10).

One request = one JSON suite run.  The wire format accepts either the
bare suite format ``load_suite`` already reads — a JSON list of
``{name, kernel, pattern, delta, count}`` dicts, so every existing
``suites/*.json`` file POSTs unmodified — or an envelope::

    {"patterns": [...],            # required, same entries as the bare list
     "backend": "xla",             # any of core.backends.BACKENDS
     "runs": 3,                    # min-of-K timing (paper §3.5)
     "mode": "store",              # scatter semantics: "store" | "add"
     "metric": "measured",         # table's uniform gbs column
     "row_width": 1,
     "mesh": 0,                    # N: shard bucket launches over N devices
                                   # (pattern-batch axis); [b, l]: a 2-D
                                   # (batch x lane) placement over b*l
                                   # devices (plan.Placement, DESIGN.md §11)
     "mesh_axis": "data",
     "seed": 0,                    # host-buffer RNG seed
     "stream_r": false,            # paper Eq. 1 vs a STREAM-like reference
     "stream_n": 4194304,
     "deadline_ms": 0}             # >0: queue deadline -> 504 on expiry

Every field is validated HERE, before any JAX work starts, so a bad
request is a 400 with a one-line reason and never occupies a scheduler
queue slot.  Unknown envelope keys are rejected too — the
missing-``mode=`` bug class started life as a silently-dropped option.

Responses: a 200 carries ``stats`` (SuiteStats.to_json), ``cache``
(per-request hits/misses — misses is an EXACT compile count, attributed
per launch by the scheduler — plus lifetime counters), ``plan``
(n_buckets/pad_waste), ``serve`` (scheduler telemetry: ``queued_ms``,
``launches``, ``coalesced_launches``, and ``phases_ms`` — the host
phases of the launches that served the request, ms by span name:
``plan.draw`` (its profiler span carries ``lanes=`` and ``rows=``, the
table rows drawn), ``plan.keep_mask``, ``plan.stack``, ``plan.to_device``,
``plan.warmup``, ``plan.runs``, ``plan.fetch``, ``plan.digest``; a
coalesced launch's phases are shared by every request it served; null
on a workers=0 daemon), ``elapsed_s``, and ``request_id`` (the daemon's
id for the request, also the ``req=`` of its profiler spans).  When the scheduler's bounded queue is full the daemon
answers **503 with a Retry-After header** and a ``retry_after_s`` field
— backpressure decided before the request costs anything; clients should
back off and retry the identical request (DESIGN.md §13).

The geometry budget below (``MAX_SUITE_LANES``) does double duty: it
bounds one request's assembled buffers AND caps how many concurrent
requests' work units the scheduler may coalesce into a single launch
(serve/scheduler.py), so a coalesced launch never assembles more than a
maximal single request could.
"""
from __future__ import annotations

import dataclasses
import json

# repro.core imports live INSIDE the validators (not at module top):
# importing core pulls in jax, and the client path — SpatterClient and
# the --client CLI validate requests with this schema before POSTing —
# must stay stdlib-only so a thin HTTP client never pays the multi-second
# JAX import it exists to avoid.

# upper bound on any single pattern's flattened lanes x row_width and
# table footprint x row_width (and on stream_n).  A request at the full
# 2**28-unit budget peaks at several GiB, not 1: each counted unit backs
# ~4-6 concurrent float32/int32 buffers (host idx/vals/table, their
# device copies, the output, and the digest pull), so size serving hosts
# for that — the bound's job is making the ceiling finite and known, a
# handful of request bytes can never allocate unboundedly.  The whole
# suite shares the same budget (summed below): per-pattern caps alone
# would let 64 max-size patterns stack into one bucket launch.
MAX_PATTERN_LANES = 1 << 28
MAX_SUITE_LANES = MAX_PATTERN_LANES
MAX_RUNS = 1000
# a mesh dim beyond this is a typo, not a machine (the daemon separately
# checks the product against the actually-visible device count)
MAX_MESH_DIM = 1 << 16

# wire-level choice sets (duplicated from core to stay import-light;
# tests/test_serve.py asserts they match the real definitions)
WIRE_BACKENDS = ("xla", "onehot", "scalar", "pallas")
WIRE_MODES = ("store", "add")
WIRE_METRICS = ("measured", "measured_cpu_gbs", "modeled",
                "modeled_v5e_gbs")


def parse_mesh(spec: str) -> "int | str | tuple[int, int]":
    """CLI mesh spec -> wire value: ``"8"`` -> 8, ``"4x2"`` -> (4, 2),
    ``"auto"`` -> ``"auto"`` (per-bucket cost-model placement),
    ``"auto-suite"`` -> one cost-model shape for the whole suite.

    Stays stdlib-only (the jax-free client parses ``--mesh`` with this);
    full validation happens in ``SuiteRequest`` like every other field.
    """
    s = spec.strip().lower()
    if s in ("auto", "auto-suite"):
        return s
    try:
        if "x" in s:
            b, l = s.split("x")
            return int(b), int(l)
        return int(s)
    except ValueError:
        raise ValueError(f"mesh must be N, BxL, 'auto', or 'auto-suite' "
                         f"(e.g. 8 or 4x2), got {spec!r}") from None


# the declared index-buffer length is bounded much tighter than lanes:
# generate_index materializes it as a PYTHON TUPLE (~36 bytes/element)
# during parsing, so a lanes-sized budget would still admit ~10 GiB of
# boxed ints.  Real Spatter index buffers are small (the paper's are
# tens of elements); scale belongs on the count axis.
MAX_INDEX_LEN = 1 << 22


def _spec_index_len(spec) -> int:
    """Upper-bound a pattern spec's index-buffer length WITHOUT
    materializing it (mirrors core.pattern.generate_index's grammar:
    UNIFORM/MS1/BROADCAST/STREAM carry N first, LAPLACIAN:D:L yields at
    most 2*D*L+1 offsets, comma lists count their commas).  Fails
    CLOSED: a generator-shaped spec with an unrecognized head — e.g. a
    future core generator this mirror hasn't learned — reports
    oversized, so eager expansion can never sneak past the bound
    (tests/test_serve.py pins the mirror against generate_index).
    Malformed argument lists return 0 and Pattern.from_json raises the
    real error later."""
    if not isinstance(spec, str):
        try:
            return len(spec)
        except TypeError:
            return 0
    s = spec.strip()
    head, sep, rest = s.partition(":")
    args = [a for a in rest.split(":") if a]
    try:
        if head in ("UNIFORM", "MS1", "BROADCAST", "STREAM"):
            return int(args[0])
        if head == "LAPLACIAN":
            return 2 * int(args[0]) * int(args[1]) + 1
        if head == "CUSTOM":
            return rest.count(",") + 1
    except (IndexError, ValueError):
        return 0
    if sep and head.isupper():          # unknown generator head
        return MAX_INDEX_LEN + 1
    return s.count(",") + 1             # bare comma list

@dataclasses.dataclass(frozen=True)
class SuiteRequest:
    """A validated spatterd run request."""
    patterns: tuple[dict, ...]
    backend: str = "xla"
    runs: int = 3
    mode: str = "store"
    metric: str = "measured"
    row_width: int = 1
    mesh: int | str | list = 0  # N (batch-only), [b, l] 2-D placement,
                                # "auto" (per-bucket cost model), or
                                # "auto-suite" (one suite-wide shape);
                                # normalized to int | str | tuple
    mesh_axis: str = "data"
    seed: int = 0
    stream_r: bool = False
    stream_n: int = 2 ** 22
    digest: bool = True      # per-pattern sha256 bit-identity proof;
                             # opt out to skip the device->host pull +
                             # hash on latency-critical sweeps
    deadline_ms: int = 0     # 0 = none; else queue deadline: work still
                             # queued when it expires never launches and
                             # the request returns 504 (DESIGN.md §14)

    def __post_init__(self):
        # choice sets mirrored from core (backends.BACKENDS,
        # engine.SCATTER_MODES, suite._METRIC_COLUMNS) rather than
        # imported — see the module-top note on staying jax-free; the
        # round-trip tests pin these against the real definitions
        if not self.patterns:
            raise ValueError("request needs at least one pattern")
        for i, d in enumerate(self.patterns):
            if not isinstance(d, dict):
                raise ValueError(f"patterns[{i}] is not an object: {d!r}")
        if self.backend not in WIRE_BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {sorted(WIRE_BACKENDS)}")
        if self.mode not in WIRE_MODES:
            raise ValueError(f"unknown mode {self.mode!r}; "
                             f"expected one of {WIRE_MODES}")
        if self.metric not in WIRE_METRICS:
            raise ValueError(f"unknown metric {self.metric!r}; "
                             f"expected one of {sorted(WIRE_METRICS)}")
        # runs bounds the min-over-K timing loop executed under the run
        # lock (paper uses 10); row_width multiplies every buffer and is
        # additionally folded into the per-pattern geometry bound below
        for name, hi in (("runs", MAX_RUNS), ("row_width", 4096)):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) \
                    or not 1 <= v <= hi:
                raise ValueError(f"{name} must be an int in [1, {hi}], "
                                 f"got {v!r}")
        # stream_reference is UNIFORM:8:1 with count = n // 8: below 8 it
        # blows up holding the run lock, and an uncapped n lets a few
        # request bytes allocate terabytes (the body-size limit can't see
        # it) — bound both ends here, before any JAX work
        if not isinstance(self.stream_n, int) or isinstance(self.stream_n,
                                                            bool) \
                or not 8 <= self.stream_n <= MAX_PATTERN_LANES:
            raise ValueError(f"stream_n must be an int in "
                             f"[8, {MAX_PATTERN_LANES}], "
                             f"got {self.stream_n!r}")
        # deadline_ms: 0 disables; capped at 24h so a typo'd value can't
        # pin a ticket's absolute deadline into the far future
        if not isinstance(self.deadline_ms, int) \
                or isinstance(self.deadline_ms, bool) \
                or not 0 <= self.deadline_ms <= 86_400_000:
            raise ValueError(f"deadline_ms must be an int in "
                             f"[0, 86400000], got {self.deadline_ms!r}")
        # mesh: N devices on the pattern-batch axis, [b, l] for a 2-D
        # (batch x lane) placement, "auto" (per-bucket §15 cost-model
        # selection), or "auto-suite" (one cost-model shape for the
        # whole suite).  Validated HERE — before the daemon's run lock,
        # like everything else — and the daemon additionally checks b*l
        # against the visible device count outside the lock.
        if isinstance(self.mesh, list):
            object.__setattr__(self, "mesh", tuple(self.mesh))
        mesh = self.mesh
        mesh_ok = (isinstance(mesh, int) and not isinstance(mesh, bool)
                   and 0 <= mesh <= MAX_MESH_DIM) \
            or mesh in ("auto", "auto-suite")
        if isinstance(mesh, tuple):
            mesh_ok = (len(mesh) == 2 and all(
                isinstance(s, int) and not isinstance(s, bool)
                and 1 <= s <= MAX_MESH_DIM for s in mesh))
        if not mesh_ok:
            raise ValueError(f"mesh must be an int >= 0, a [batch, lane] "
                             f"pair of ints >= 1 (dims <= {MAX_MESH_DIM}), "
                             f"'auto', or 'auto-suite', got {self.mesh!r}")
        if not isinstance(self.seed, int) or isinstance(self.seed, bool) \
                or self.seed < 0:
            raise ValueError(f"seed must be an int >= 0, got {self.seed!r}")
        if not self.mesh_axis.isidentifier():
            raise ValueError(f"mesh_axis must be an identifier-like axis "
                             f"name, got {self.mesh_axis!r}")
        for name in ("stream_r", "digest"):
            if not isinstance(getattr(self, name), bool):
                raise ValueError(f"{name} must be a bool, "
                                 f"got {getattr(self, name)!r}")

    @staticmethod
    def from_json(doc) -> "SuiteRequest":
        """Parse a decoded request body (bare pattern list or envelope)."""
        if isinstance(doc, str):
            doc = json.loads(doc)
        if isinstance(doc, list):
            return SuiteRequest(patterns=tuple(doc))
        if not isinstance(doc, dict):
            raise ValueError(f"request must be a JSON list or object, "
                             f"got {type(doc).__name__}")
        if "patterns" not in doc:
            raise ValueError('request object needs a "patterns" list')
        unknown = set(doc) - set(_OPTION_FIELDS) - {"patterns"}
        if unknown:
            raise ValueError(f"unknown request fields {sorted(unknown)}; "
                             f"expected {sorted(_OPTION_FIELDS)}")
        kw = {}
        for name, ty in _OPTION_FIELDS.items():
            if name in doc:
                v = doc[name]
                ty_name = (ty.__name__ if isinstance(ty, type)
                           else " | ".join(t.__name__ for t in ty))
                # bool is an int subclass: keep the check strict both ways
                if ty is not bool and isinstance(v, bool):
                    raise ValueError(f"{name} must be {ty_name}, got {v!r}")
                if not isinstance(v, ty):
                    raise ValueError(f"{name} must be {ty_name}, "
                                     f"got {v!r}")
                kw[name] = v
        pats = doc["patterns"]
        if not isinstance(pats, list):
            raise ValueError('"patterns" must be a list')
        return SuiteRequest(patterns=tuple(pats), **kw)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["patterns"] = list(d["patterns"])
        if isinstance(d["mesh"], tuple):        # wire form is a JSON list
            d["mesh"] = list(d["mesh"])
        return d

    def build_patterns(self) -> list[Pattern]:
        """Materialize the suite (ValueError on malformed entries).

        Also bounds the buffer geometry — per pattern AND summed over the
        suite (patterns stack into bucket launches, so 64 individually-ok
        patterns could still assemble one enormous batch): a tiny JSON
        body can declare an astronomically large ``count``, and the first
        place it would fail is a host-buffer allocation big enough to OOM
        the daemon — reject it here instead, before any JAX work.
        """
        # bound the declared index-buffer length BEFORE materializing:
        # Pattern.from_json expands generator specs eagerly, so
        # "UNIFORM:2000000000:1" would build a 2-billion-element tuple
        # during parsing — ahead of any size check on the result
        for d in self.patterns:
            n = _spec_index_len(d.get("pattern", ()))
            if n > MAX_INDEX_LEN:
                raise ValueError(
                    f"pattern {d.get('name', '?')!r} declares a "
                    f">{MAX_INDEX_LEN}-element (or unrecognized-"
                    f"generator) index buffer; put scale in count=")
        from repro.core.pattern import Pattern   # lazy: jax-free client
        try:
            pats = [Pattern.from_json(d) for d in self.patterns]
        except (IndexError, KeyError, TypeError, ValueError) as e:
            # IndexError: generator specs with too few args ("UNIFORM",
            # "MS1:8") index into their missing argument list
            raise ValueError(f"bad pattern entry: {e}") from e
        total = 0
        for p in pats:
            lanes = p.count * p.index_len
            size = max(lanes, p.footprint()) * self.row_width
            total += size
            if size > MAX_PATTERN_LANES:
                raise ValueError(
                    f"pattern {p.name!r} too large to serve: "
                    f"count*index_len={lanes}, footprint={p.footprint()}, "
                    f"row_width={self.row_width} (limit: lanes x "
                    f"row_width <= {MAX_PATTERN_LANES})")
        if total > MAX_SUITE_LANES:
            raise ValueError(
                f"suite too large to serve: {total} total lanes x "
                f"row_width > {MAX_SUITE_LANES} budget")
        return pats


# envelope option keys -> wire type, derived from the dataclass itself so
# the two can never drift (a new SuiteRequest field is automatically
# accepted by from_json); patterns is handled separately
_WIRE_TYPES = {"str": str, "int": int, "bool": bool,
               "int | str | list": (int, str, list, tuple)}
_OPTION_FIELDS: dict[str, type] = {
    f.name: _WIRE_TYPES[f.type]
    for f in dataclasses.fields(SuiteRequest) if f.name != "patterns"
}
