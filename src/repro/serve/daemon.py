"""spatterd — a long-lived suite-serving daemon on the warm ExecutorCache.

The paper's value proposition is sweeping *many* configurations cheaply
(§3.3 JSON suites, §3.5 min-over-K timing); the planner PRs made a repeat
suite run compile nothing, but only inside one-shot scripts.  spatterd is
the process that makes repeated execution the product (DESIGN.md §10):
it holds the process-wide ``ExecutorCache`` open across HTTP requests, so
the FIRST identical suite request compiles ``n_buckets`` executables and
every later one — from any client — compiles zero, and each response
carries the telemetry that proves it (per-request cache hits/misses,
where ``misses`` is an exact compile count, plus per-pattern output
digests for bit-identity).

Endpoints (all JSON; stdlib ``http.server``, no dependencies):

    POST /run      run a suite (schema.SuiteRequest; bare ``suites/*.json``
                   lists work as-is).  ``mesh: N`` in the request shards
                   every bucket launch's pattern-batch dim over N devices;
                   ``mesh: [b, l]`` places launches on a 2-D (batch x
                   lane) mesh (plan.Placement, DESIGN.md §11).  503 +
                   ``Retry-After`` when the scheduler queue is full;
                   ``deadline_ms`` in the request arms a queue deadline
                   mapped to 504 when it expires before launch.
    POST /warm     prewarm: compile/restore + first-call every executable
                   a suite needs (zero-filled buffers, nothing timed) so
                   later /run requests are execute-only
    GET  /healthz  liveness + device/backend inventory + lifetime stats
    GET  /readyz   readiness, SPLIT from liveness: 503 while the disk
                   cache preload is running, the scheduler is paused, or
                   a drain is in progress — a fleet router stops routing
                   here without declaring the process dead
    GET  /cache    lifetime ExecutorCache counters
    GET  /stats    cache counters + live scheduler snapshot (queue depth,
                   worker occupancy, launch/coalesce totals, supervision
                   ledger) + fault-injection and disk-tier telemetry
    GET  /lint     spatterlint audit of the live cache's compiled
                   executables (repro.analysis, DESIGN.md §12) — the
                   report schema the --lint CLI shares

Fault tolerance (DESIGN.md §14): ``cache_dir=`` attaches a crash-safe
persistent executable tier (core/diskcache.DiskTier) preloaded on a
background thread at startup, so a restarted daemon serves previously
seen suites with ``misses == 0``; SIGTERM begins a graceful drain
(readiness flips off, queued work completes, then the port closes); and
``faults=`` arms the deterministic fault-injection registry
(serve/faults.py) whose sites thread through the cache (compile), the
scheduler (launch, worker), and the disk tier (corruption) — chaos
tests and the CI ``chaos`` job drive every recovery path through it.

Quickstart::

    PYTHONPATH=src python -m repro.serve.daemon --port 8089 &
    PYTHONPATH=src python -m repro.serve.client --url http://127.0.0.1:8089 \
        --json suites/demo.json

Concurrency model: request *handling* is multi-threaded
(``ThreadingHTTPServer`` — parsing, validation, and serialization overlap
freely), and suite *execution* goes through the coalescing work-unit
scheduler (serve/scheduler.py, DESIGN.md §13): each request decomposes
into ``BucketWork`` items on a bounded queue, worker threads batch items
sharing an ``ExecKey`` family into single padded launches, and the
handler thread blocks on its ticket.  Per-request telemetry stays exact
WITHOUT a global lock: each compile is attributed to the one launch that
claimed the executable's ``_BuildFuture``, so summed per-request
``misses`` equal the cache's lifetime compile count.  ``workers=0``
retains the PR 4 serialized path — one run lock, stats-snapshot deltas —
as the scheduling baseline ``benchmarks/bench_serve.py`` measures
against.  The cache itself is additionally lock-protected
(plan.ExecutorCache) so /cache and /healthz can read counters mid-run.
"""
from __future__ import annotations

import argparse
import json
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from repro.core import backends as B
from repro.core.plan import ExecutorCache, SuitePlan, default_cache, make_work
from repro.core.suite import aggregate_stats, run_suite, stream_reference

from .faults import ENV_SPEC, FaultInjector
from .schema import SuiteRequest
from .scheduler import (DEFAULT_MAX_QUEUE, DEFAULT_WORKERS, DeadlineExceeded,
                        QueueFull, Scheduler, SchedulerStopped)

# how long a handler thread waits on its scheduler ticket before giving
# the client a 500 — far above any admissible suite (schema bounds runs
# and geometry), so it only fires on a genuinely wedged device
TICKET_TIMEOUT_S = 600.0

# extra wait past a request's own deadline before the handler abandons
# the ticket itself (normally a worker retires expired items first; the
# grace covers a paused or fully busy pool, where no worker ever looks)
DEADLINE_GRACE_S = 0.25


def _bounded_put(memo: dict, key, value, bound: int = 32) -> None:
    """FIFO-bounded insert: client-controlled memo keys must never grow a
    long-lived daemon's memory without limit."""
    while len(memo) >= bound:
        memo.pop(next(iter(memo)))
    memo[key] = value


class SpatterDaemon:
    """The serving process around one (usually process-wide) ExecutorCache.

    ``port=0`` binds an ephemeral port (read it back from ``.port``) —
    tests and benchmarks use that to avoid collisions.  ``start()`` serves
    from a background thread; ``serve_forever()`` blocks (the CLI path).
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 8089, *,
                 cache: ExecutorCache | None = None, quiet: bool = True,
                 workers: int = DEFAULT_WORKERS,
                 max_queue: int = DEFAULT_MAX_QUEUE,
                 cache_dir: str | None = None,
                 faults: FaultInjector | None = None):
        self.cache = cache if cache is not None else default_cache()
        self.quiet = quiet
        self.started_at = time.time()
        self.n_requests = 0
        self.faults = faults
        if faults is not None and self.cache.fault_hook is None:
            self.cache.fault_hook = faults.check
        self.disk = None
        if cache_dir:
            from repro.core.diskcache import DiskTier
            mangle = ((lambda payload: faults.mangle("disk", payload))
                      if faults is not None else None)
            self.disk = DiskTier(cache_dir, mangle=mangle)
        # readiness is NOT liveness: _ready is set once the (background)
        # disk-cache preload finishes; _draining flips on SIGTERM/stop —
        # /readyz reports 503 in either state while /healthz stays 200
        self._ready = threading.Event()
        self._draining = False
        # workers >= 1: the coalescing scheduler serves every run.
        # workers == 0: PR 4 behavior — execution serialized on _run_lock,
        # telemetry from stats-snapshot deltas — kept as the measurable
        # scheduling baseline (bench_serve) and a debugging fallback.
        self.scheduler = None if workers == 0 else Scheduler(
            self.cache, workers=workers, max_queue=max_queue, faults=faults)
        self._run_lock = threading.Lock()
        self._memo_lock = threading.Lock()     # guards _placements mutation
        self._state_lock = threading.Lock()    # guards request counters
        self._placements: dict[tuple, object] = {}   # (shape, axis) -> Placement
        self._stream_refs: dict[tuple, object] = {}   # memoized STREAM runs
        self._thread: threading.Thread | None = None
        self._httpd = ThreadingHTTPServer((host, port), _make_handler(self))
        # ThreadingHTTPServer defaults block process exit on hung handlers
        self._httpd.daemon_threads = True

    # -- lifecycle -----------------------------------------------------------
    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def _load(self) -> None:
        """Background startup loader: preload the disk tier (restored
        executables count ``disk_hits``, never ``misses``), then flip
        readiness on.  A failing preload leaves the daemon READY but
        cold — persistence is an optimization, not a dependency."""
        try:
            if self.faults is not None:
                self.faults.check("load")
            if self.disk is not None:
                n = self.cache.attach_disk(self.disk, preload=True)
                self._log("restored %d executable(s) from %s",
                          n, self.disk.root)
        except Exception as e:
            self._log("disk-cache preload failed (serving cold): %s", e)
        finally:
            self._ready.set()

    def _start_loader(self) -> None:
        threading.Thread(target=self._load, name="spatterd-loader",
                         daemon=True).start()

    def start(self) -> "SpatterDaemon":
        self._start_loader()
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="spatterd", daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._start_loader()
        self._httpd.serve_forever()

    def begin_drain(self) -> None:
        """SIGTERM entry point: flip readiness off NOW (a fleet router
        stops sending new work), then run the blocking drain on a helper
        thread — ``shutdown()`` must never run on the serving thread, and
        a signal frame interrupts exactly that thread in the CLI path."""
        self._draining = True
        threading.Thread(target=self.stop, name="spatterd-drain",
                         daemon=True).start()

    def stop(self) -> None:
        """Graceful drain: stop accepting connections, let queued and
        in-flight scheduler work finish (their handler threads still
        write responses — ``daemon_threads`` only abandons them at
        process exit), then release the port."""
        self._draining = True
        self._httpd.shutdown()
        if self.scheduler is not None:
            self.scheduler.stop(drain=True)
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def __enter__(self) -> "SpatterDaemon":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- request execution ---------------------------------------------------
    def _placement(self, mesh, axis: str):
        """Placement per (shape, batch axis), memoized by shape tuple: the
        canonical placement string — not the Mesh object's identity — keys
        the ExecutorCache, but reusing the object keeps mesh/sharding
        construction out of repeat requests.  ``mesh`` is the validated
        wire value: an int N (batch-only) or a (b, l) tuple (2-D).
        Called OUTSIDE the run lock so an oversized mesh fails fast even
        while a long run is in flight; _memo_lock covers the check +
        bounded FIFO eviction + insert (concurrent handler threads)."""
        import jax
        from repro.core.plan import Placement
        shape = (mesh, 1) if isinstance(mesh, int) else tuple(mesh)
        key = (shape, axis)
        with self._memo_lock:
            if key not in self._placements:
                n_dev = len(jax.devices())
                need = shape[0] * shape[1]
                if need > n_dev:
                    raise ValueError(
                        f"mesh={mesh} needs {need} devices, {n_dev} visible "
                        f"(start the daemon under XLA_FLAGS=--xla_force_"
                        f"host_platform_device_count={need} to fake devices "
                        f"on CPU)")
                _bounded_put(self._placements, key,
                             Placement.create(shape, batch_axis=axis))
            return self._placements[key]

    def _resolve_mesh(self, req: SuiteRequest, patterns):
        """The request's placement, auto-selected when unpinned.

        An explicit ``mesh=N``/``[b, l]`` resolves exactly as before.
        ``mesh="auto-suite"`` goes through the §15 cost model for ONE
        min-predicted-traffic shape for the whole suite (the pre-PR-10
        auto behavior).  ``mesh="auto"`` — and requests that pass no
        ``mesh=`` at all — returns the literal string: the run paths
        resolve it per bucket against the built plan
        (``plan.auto_placements``, DESIGN.md §16).  Either way the
        selection only names plain (batch, lane) shapes — placement
        strings and ExecKeys are exactly what explicit ``--mesh BxL``
        requests would produce, so warm repeats stay compile-free and
        digests bit-identical.  Single device (or no traffic win from
        sharding) resolves to ``None``, the unplaced fast path.
        """
        if req.mesh == "auto-suite":
            from repro.analysis.cost import auto_placement
            shape = auto_placement(patterns, backend=req.backend,
                                   row_width=req.row_width)
            if shape is None:
                return None
            return self._placement(tuple(shape), req.mesh_axis)
        if req.mesh == "auto" or not req.mesh:
            return "auto"
        return self._placement(req.mesh, req.mesh_axis)

    def _stream_ref_for(self, req: SuiteRequest):
        """Memoized STREAM reference RunResult for a stream_r request.

        The reference is its own jitted engine, outside the
        ExecutorCache; memoizing its RunResult means only the FIRST
        stream_r request per (backend, n, runs) compiles and times it —
        warm requests stay execute-only, keeping the misses==0
        warm-repeat proof honest.  Cold references run outside any lock
        (two racing cold requests may both compute one; the memo keeps
        whichever lands last — identical inputs, so nothing drifts).
        """
        skey = (req.backend, req.stream_n, req.runs)
        with self._memo_lock:
            ref = self._stream_refs.get(skey)
        if ref is None:
            ref = stream_reference(n=req.stream_n, runs=req.runs,
                                   backend=req.backend)
            with self._memo_lock:
                _bounded_put(self._stream_refs, skey, ref)
        return ref

    def run_request(self, req: SuiteRequest) -> dict:
        """Execute one validated request; returns the response document.

        Raises ValueError for request-shaped problems (bad pattern entry,
        mesh larger than the device count) — the handler maps those to
        400s — ``QueueFull``/``SchedulerStopped`` for backpressure (503),
        ``DeadlineExceeded`` for an expired ``deadline_ms`` (504), and
        lets genuine execution failures propagate to a 500.
        """
        # block until the startup disk preload finished: serving a known
        # suite cold while its warm executables are still deserializing
        # would break the warm-restart misses==0 proof
        self._ready.wait(TICKET_TIMEOUT_S)
        # request-shaped failures (bad patterns, oversized mesh) resolve
        # BEFORE any queueing: a 400 never occupies a queue slot
        patterns = req.build_patterns()
        mesh = self._resolve_mesh(req, patterns)
        if self.scheduler is None:
            doc = self._run_serial(req, patterns, mesh)
        else:
            doc = self._run_scheduled(req, patterns, mesh)
        with self._state_lock:
            self.n_requests += 1
        return doc

    def _run_scheduled(self, req: SuiteRequest, patterns, mesh) -> dict:
        """Submit the request's work units to the scheduler and wait.

        ``elapsed_s`` covers submit -> resolve, so it INCLUDES queue
        wait (reported separately as ``serve.queued_ms``) — under
        concurrency that is the latency the client actually saw.

        A request ``deadline_ms`` arms a scheduler queue deadline:
        normally a worker retires expired items (``DeadlineExceeded``
        resolves the ticket); if no worker ever looks (paused/wedged
        pool) the handler gives up itself after a grace period and
        CANCELS the ticket, so the expired work is removed from the
        queue — either way nothing launches after expiry and the client
        gets a 504.  Any ticket abandoned by timeout is cancelled too
        (the abandoned-ticket fix: workers must not launch work whose
        handler — and therefore client — is gone).
        """
        t0 = time.perf_counter()
        stream_ref = self._stream_ref_for(req) if req.stream_r else None
        plan = SuitePlan.build(patterns)
        if isinstance(mesh, str):          # "auto": per-bucket cost model
            from repro.core.plan import auto_placements
            mesh = auto_placements(plan, mesh, mesh_axis=req.mesh_axis,
                                   backend=req.backend,
                                   row_width=req.row_width)
        works = make_work(plan, backend=req.backend, runs=req.runs,
                          row_width=req.row_width, mode=req.mode,
                          seed=req.seed, placement=mesh, digest=req.digest)
        deadline_s = req.deadline_ms / 1e3 if req.deadline_ms else None
        ticket = self.scheduler.submit(works, deadline_s=deadline_s)
        wait_s = (TICKET_TIMEOUT_S if deadline_s is None
                  else min(TICKET_TIMEOUT_S, deadline_s + DEADLINE_GRACE_S))
        try:
            ticket.wait(wait_s)
        except TimeoutError:
            self.scheduler.cancel(ticket)
            if deadline_s is not None:
                raise DeadlineExceeded(
                    f"deadline_ms={req.deadline_ms} expired before the "
                    f"request's work launched") from None
            raise
        results = [ticket.results[i] for i in range(len(patterns))]
        stats = aggregate_stats(results, metric=req.metric, plan=plan,
                                stream_ref=stream_ref)
        return self._response(req, stats, mesh,
                              hits=ticket.hits, misses=ticket.misses,
                              serve=ticket.telemetry(),
                              elapsed_s=time.perf_counter() - t0)

    def _run_serial(self, req: SuiteRequest, patterns, mesh) -> dict:
        """PR 4 baseline path (``workers=0``): one run lock, telemetry
        from cache-stats snapshot deltas bracketing the run."""
        with self._run_lock:
            # timed inside the lock: elapsed_s is THIS request's
            # execution, not time spent queued behind other requests
            t0 = time.perf_counter()
            stream_ref = self._stream_ref_for(req) if req.stream_r else None
            before = self.cache.stats()
            stats = run_suite(
                patterns, backend=req.backend, runs=req.runs,
                row_width=req.row_width, metric=req.metric, mode=req.mode,
                seed=req.seed, cache=self.cache, mesh=mesh,
                mesh_axis=req.mesh_axis, stream_r=req.stream_r,
                stream_n=req.stream_n, stream_ref=stream_ref,
                digest=req.digest)
            after = self.cache.stats()
        delta = after.delta(before)
        return self._response(req, stats, mesh,
                              hits=delta.hits, misses=delta.misses,
                              serve=None,
                              elapsed_s=time.perf_counter() - t0)

    def _response(self, req: SuiteRequest, stats, mesh, *, hits: int,
                  misses: int, serve: dict | None,
                  elapsed_s: float) -> dict:
        # the serial path hands the unresolved "auto" string through
        # (run_suite resolved its own copy); re-resolve here for
        # reporting — the per-bucket selection is a pure function of
        # (plan, backend, row_width, devices), so this names exactly the
        # placements the run used
        if isinstance(mesh, str):
            from repro.core.plan import auto_placements
            mesh = auto_placements(stats.plan, mesh,
                                   mesh_axis=req.mesh_axis,
                                   backend=req.backend,
                                   row_width=req.row_width)
        if isinstance(mesh, list):
            pad_waste = stats.plan.pad_waste_for(mesh)
            placement = [m.placement if m is not None else "single"
                         for m in mesh]
        else:
            pad_waste = stats.plan.pad_waste(
                *(mesh.grid if mesh is not None else (1, 1)))
            placement = mesh.placement if mesh is not None else "single"
        return {
            "ok": True,
            "stats": stats.to_json(req.metric),
            "cache": {
                # this request's traffic; misses == exact compile count
                # (attributed per launch on the scheduler path)
                "hits": hits,
                "misses": misses,
                "size": self.cache.stats().size,
                "lifetime": self.cache.stats().to_json(),
            },
            "plan": {
                "n_buckets": stats.plan.n_buckets,
                # the plan's static padding waste at exact-fit batches — a
                # lower bound when best_batch serves a larger warm
                # executable (member bandwidth attribution already uses
                # the actual launched batch, plan.run_plan)
                "pad_waste": pad_waste,
                # the placement(s) actually used — for mesh="auto" (and
                # unpinned requests) a per-bucket list of the cost
                # model's choices, in bucket order
                "placement": placement,
            },
            # scheduler telemetry: queued_ms, launches, coalesced_launches
            # (null on the workers=0 baseline path)
            "serve": serve,
            "elapsed_s": elapsed_s,
        }

    def warm(self, req: SuiteRequest) -> dict:
        """POST /warm: make every executable the suite needs hot.

        For each bucket executable the request's plan implies, serve it
        through the cache (disk restore > compile, with the same
        pallas→xla degradation the run path gets) and then CALL it once
        on zero-filled buffers — an AOT-compiled ``fn.lower().compile()``
        alone would not populate the jit dispatch cache, so the first
        real request would still pay tracing overhead.  Zero buffers are
        safe for both kinds: a gather reads row 0, a scatter's all-False
        keep mask writes nothing.  Nothing is timed and no results are
        produced; later /run requests are execute-only.
        """
        import jax
        import jax.numpy as jnp

        from repro.core.plan import (bucket_builder, enumerate_executables,
                                     key_avals)
        t0 = time.perf_counter()
        self._ready.wait(TICKET_TIMEOUT_S)
        patterns = req.build_patterns()
        mesh = self._resolve_mesh(req, patterns)
        plan = SuitePlan.build(patterns)
        if isinstance(mesh, str):          # "auto": per-bucket cost model
            from repro.core.plan import auto_placements
            mesh = auto_placements(plan, mesh, mesh_axis=req.mesh_axis,
                                   backend=req.backend,
                                   row_width=req.row_width)
        placements = (mesh if isinstance(mesh, list)
                      else [mesh] * len(plan.buckets))
        units = enumerate_executables(plan, backend=req.backend,
                                      row_width=req.row_width, mode=req.mode,
                                      placement=mesh)
        before = self.cache.stats()
        compiled = 0
        for bucket, pl_b, (key, builder, _) in zip(plan.buckets, placements,
                                                   units):
            fb = (bucket_builder("xla", bucket.spec, key.mode, pl_b)
                  if req.backend != "xla" else None)
            fn, served, built, _ = self.cache.serve_poly_info(key, builder,
                                                              fb)
            compiled += bool(built)
            # first-call at the SERVED batch (best_batch may be larger)
            args = tuple(jnp.zeros(a.shape, a.dtype)
                         for a in key_avals(served))
            if pl_b is not None:
                args = pl_b.place(key.kind, args)
            jax.block_until_ready(fn(*args))
        delta = self.cache.stats().delta(before)
        with self._state_lock:
            self.n_requests += 1
        return {
            "ok": True,
            "n_executables": len(units),
            "compiled": compiled,
            "cache": {"hits": delta.hits, "misses": delta.misses,
                      "disk_hits": delta.disk_hits,
                      "degraded": delta.degraded,
                      "lifetime": self.cache.stats().to_json()},
            "elapsed_s": time.perf_counter() - t0,
        }

    def readiness(self) -> dict:
        """GET /readyz: can this process take NEW traffic right now?

        Distinct from /healthz liveness — a loading, paused, or draining
        daemon is alive (health 200) but not ready (503), which is what
        a fleet router needs to stop routing without killing the pod.
        """
        snap = (self.scheduler.snapshot()
                if self.scheduler is not None else None)
        loading = not self._ready.is_set()
        paused = bool(snap and snap["paused"])
        draining = self._draining or bool(snap and snap["stopping"])
        ready = not (loading or paused or draining)
        return {"ok": ready, "ready": ready, "loading": loading,
                "paused": paused, "draining": draining}

    def stats(self) -> dict:
        """GET /stats: lifetime cache counters + live scheduler state +
        fault-injection and disk-tier telemetry."""
        return {
            "ok": True,
            "n_requests": self.n_requests,
            "uptime_s": time.time() - self.started_at,
            "cache": self.cache.stats().to_json(),
            # null when running the workers=0 serialized baseline
            "scheduler": (self.scheduler.snapshot()
                          if self.scheduler is not None else None),
            "disk": self.disk.stats() if self.disk is not None else None,
            "faults": (self.faults.snapshot()
                       if self.faults is not None else None),
        }

    def lint(self) -> dict:
        """Static audit of every compiled executable in the live cache.

        Runs the executable-scope spatterlint rules against each cached
        ExecKey, reconstructing launch avals from the key alone — so the
        audit also proves the keys describe their executables honestly.
        Read-only (``ExecutorCache.entries``): it can run mid-request
        without perturbing the hits/misses telemetry, and it takes no
        lock the run path needs.
        """
        from repro.analysis.lint import lint_cache
        report = lint_cache(self.cache)
        return {"ok": report.ok, "report": report.to_json()}

    def cost(self) -> dict:
        """GET /cost: static traffic accounting of the live cache.

        Every cached ExecKey gets the §15 per-unit byte split (useful
        terms need the plan, so a bare key reports launch geometry
        only), reconciled against its lowered StableHLO signature
        (``traffic-conservation``) and the committed byte baseline
        (``cost-regression``).  Restored DiskTier entries are opaque
        exported calls — they degrade to key-geometry terms and the
        key-only rules, exactly like ``GET /lint``'s downgrade.
        Read-only, same as ``lint``.
        """
        from repro.analysis.cost import cost_cache
        report = cost_cache(self.cache)
        return {"ok": report.ok, "report": report.to_json()}

    def health(self) -> dict:
        import jax
        return {
            "ok": True,
            "service": "spatterd",
            "n_devices": len(jax.devices()),
            "backends": sorted(B.BACKENDS),
            "n_requests": self.n_requests,
            "uptime_s": time.time() - self.started_at,
            "cache": self.cache.stats().to_json(),
        }

    def _log(self, fmt: str, *args) -> None:
        if not self.quiet:
            print(f"spatterd: {fmt % args}", flush=True)


MAX_BODY_BYTES = 64 << 20     # one request can't OOM a long-lived daemon


def _make_handler(daemon: SpatterDaemon):
    class Handler(BaseHTTPRequestHandler):
        server_version = "spatterd/1.0"
        protocol_version = "HTTP/1.1"
        # socket timeout: a stalled upload or an idle keep-alive
        # connection must not pin a handler thread forever (the
        # stdlib default is no timeout at all)
        timeout = 120

        def log_message(self, fmt, *args):          # route through the daemon
            daemon._log(fmt, *args)

        def _reply(self, code: int, doc: dict,
                   headers: dict | None = None) -> None:
            body = json.dumps(doc).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path in ("/healthz", "/health"):
                self._reply(200, daemon.health())
            elif self.path == "/readyz":
                doc = daemon.readiness()
                self._reply(200 if doc["ready"] else 503, doc)
            elif self.path == "/cache":
                self._reply(200, {"ok": True,
                                  "cache": daemon.cache.stats().to_json()})
            elif self.path == "/stats":
                self._reply(200, daemon.stats())
            elif self.path == "/lint":
                self._reply(200, daemon.lint())
            elif self.path == "/cost":
                self._reply(200, daemon.cost())
            else:
                self._reply(404, {"ok": False,
                                  "error": f"no such path {self.path!r}"})

        def do_POST(self):
            # a body we cannot fully drain would desync HTTP/1.1
            # keep-alive (leftover bytes parse as the NEXT request's
            # start line): bad framing gets an error AND a closed
            # connection
            te = (self.headers.get("Transfer-Encoding") or "").lower()
            if "chunked" in te:
                self.close_connection = True
                self._reply(411, {"ok": False,
                                  "error": "chunked bodies unsupported; "
                                           "send Content-Length"})
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                if length < 0:
                    raise ValueError(length)
            except (TypeError, ValueError):
                self.close_connection = True
                self._reply(400, {"ok": False,
                                  "error": "bad Content-Length header"})
                return
            if length > MAX_BODY_BYTES:
                self.close_connection = True
                self._reply(413, {"ok": False,
                                  "error": f"body {length} bytes > "
                                           f"{MAX_BODY_BYTES} limit"})
                return
            # drain the body unconditionally: on HTTP/1.1 keep-alive an
            # unread body would be parsed as the NEXT request's start line
            body = self.rfile.read(length)
            if self.path not in ("/run", "/warm"):
                self._reply(404, {"ok": False,
                                  "error": f"no such path {self.path!r}; "
                                           f"POST /run or /warm"})
                return
            try:
                doc = json.loads(body)
                req = SuiteRequest.from_json(doc)
            except (ValueError, KeyError, TypeError) as e:
                self._reply(400, {"ok": False, "error": f"bad request: {e}"})
                return
            try:
                if self.path == "/warm":
                    self._reply(200, daemon.warm(req))
                else:
                    self._reply(200, daemon.run_request(req))
            except (QueueFull, SchedulerStopped) as e:
                # backpressure, decided BEFORE the run touched a queue
                # slot: the client should retry, not fail — Retry-After
                # scales with how deep the backlog is
                retry = 1 if isinstance(e, SchedulerStopped) else max(
                    1, round(e.depth / max(1, e.limit) * 5))
                self._reply(503, {"ok": False, "error": str(e),
                                  "retry_after_s": retry},
                            headers={"Retry-After": str(retry)})
            except DeadlineExceeded as e:
                # the request's own deadline_ms expired in-queue: the
                # expired work never launched (scheduler contract)
                self._reply(504, {"ok": False, "error": str(e),
                                  "deadline_ms": req.deadline_ms})
            except ValueError as e:
                self._reply(400, {"ok": False, "error": str(e)})
            except Exception as e:   # execution failure: report, stay alive
                self._reply(500, {"ok": False,
                                  "error": f"{type(e).__name__}: {e}"})

    return Handler


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="spatterd: long-lived Spatter suite server "
                    "(warm ExecutorCache across requests)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8089)
    ap.add_argument("--workers", type=int, default=DEFAULT_WORKERS,
                    help="scheduler worker threads (0 = PR 4 serialized "
                         "run-lock baseline)")
    ap.add_argument("--max-queue", type=int, default=DEFAULT_MAX_QUEUE,
                    help="bounded scheduler queue (BucketWork items); "
                         "overflow returns 503 + Retry-After")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent executable cache directory (JAX AOT "
                         "serialization): a restarted daemon preloads it "
                         "and serves previously seen suites with 0 "
                         "compiles")
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec, e.g. "
                         "'compile:fail:1,worker:kill:2' (default: env "
                         f"{ENV_SPEC}); see repro.serve.faults")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for injected-latency jitter (reproducible "
                         "chaos)")
    ap.add_argument("--verbose", action="store_true",
                    help="log one line per handled request")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()
    faults = (FaultInjector.from_spec(args.faults, seed=args.fault_seed)
              if args.faults else FaultInjector.from_env())
    daemon = SpatterDaemon(args.host, args.port, quiet=not args.verbose,
                           workers=args.workers, max_queue=args.max_queue,
                           cache_dir=args.cache_dir, faults=faults)

    def _on_sigterm(signum, frame):
        # graceful drain off the signal frame: readiness flips 503
        # immediately, the blocking shutdown runs on a helper thread
        # (shutdown() deadlocks if called from the serving thread)
        daemon.begin_drain()

    signal.signal(signal.SIGTERM, _on_sigterm)
    print(f"spatterd listening on {daemon.url}  "
          f"(POST /run /warm, GET /healthz /readyz /stats)", flush=True)
    try:
        daemon.serve_forever()
        print("spatterd drained cleanly", flush=True)
    except KeyboardInterrupt:
        daemon.stop()


if __name__ == "__main__":
    main()
