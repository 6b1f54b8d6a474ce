#!/usr/bin/env python3
"""One run of one benchmark cell: spatterd serving a Spatter deployment.

    python bench/run.py --workload lulesh.gather.pallas --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``: the deployment's patterns in spatterd's
wire form) and a traffic mix (``bench/traffic/<traffic>.json``: which of
them, ``select``, and the request options, ``request``).  A run:

1. set-up: starts an in-process ``SpatterDaemon`` on an ephemeral port
   (coalescing scheduler, JAX's persistent compilation cache at
   ``.jax_cache`` in the checkout), and warms each pattern of the cell
   through ``SpatterClient.warm``;
2. window: one closed-loop client thread sends the cycle, one request
   per selected pattern in listed order, over and over with
   ``SpatterClient.run_suite`` (request seed ``--seed``).  The window
   opens at the first send and closes at the cycle boundary nearest
   ``--seconds`` (after one cycle at least), so it holds whole cycles:
   every cycle is the same work, and the rate does not depend on where
   the window stops;
3. check: stops the daemon, then compares every response's digests with
   the plain reference (``bench/reference.py``), computed once per
   pattern on a few host threads, and prints each number compared beside
   its limit;
4. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
   ``checks``.  With ``--trace 0`` the metrics are the cell's end-to-end
   metrics; with ``--trace 1`` the window runs under the JAX profiler,
   each request's program executions are counted from the trace, and
   the metrics are its per-layer ones.

Each metric is read by ``bench/metrics/<name>.py`` (``read(ctx)``), found
by its name; a reader that finds nothing returns None and the metric is
left out.  A run that finds no TPU, or fewer chips than the cell asks
for, exits 1 without a result line.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402
from bench.reduce import REQUEST, WINDOW  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
SPEC = os.path.join(ROOT, "BENCHMARK.json")
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
READY_TIMEOUT_S = 600.0
REFERENCE_THREADS = 4
SEED_RANGE = 1 << 63      # spatterd takes seeds >= 0; larger ones wrap
TRAFFIC_KEYS = {"select", "request"}


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


# -- the cell ----------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    def metrics(self, trace: bool) -> list[dict]:
        return self.per_layer if trace else self.end_to_end

    def cycle(self) -> list[dict]:
        """The patterns of one cycle, one request each, in listed order:
        those whose fields equal every entry of ``select``."""
        sel = self.traffic.get("select", {})
        return [p for p in self.config["patterns"]
                if all(p.get(k) == v for k, v in sel.items())]

    @property
    def options(self) -> dict:
        return dict(self.traffic["request"])

    @property
    def row_width(self) -> int:
        return self.config.get("row_width", 1)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: str = SPEC) -> Cell:
    spec = load_json(spec_path)
    root = os.path.dirname(os.path.abspath(spec_path))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic {w['traffic']!r}: unknown keys "
                         f"{sorted(unknown)}")
    return Cell(name=name, chips=w["chips"],
                config=load_json(os.path.join(root, cfg["file"])),
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


# -- the device ----------------------------------------------------------------

def require_chip(chips: int) -> dict:
    """The device JAX reports; NoChip unless it is a TPU with enough chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Compiles:
    """Programs JAX built, from its own monitoring events: every build
    (``backend_compile_duration`` also wraps a read of the persistent
    cache) and the builds that the persistent cache served."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.count = 0
        self.cached = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_build(self, event: str, duration: float, **_) -> None:
        if event == self.BUILD:
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cached += 1


# -- the window ----------------------------------------------------------------

@dataclasses.dataclass
class Request:
    pattern: dict
    runs: int
    latency_s: float
    elapsed_s: float | None = None
    misses: int | None = None
    digest: str | None = None
    error: str | None = None
    executions: int | None = None    # from the trace of a traced run

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""
    cell: Cell
    requests: list[Request]
    window_s: float
    setup_s: float
    peaks: dict
    trace: object = None       # bench.reduce.Trace of a traced run


def start_daemon():
    from repro.core.plan import ExecutorCache
    from repro.serve.client import SpatterClient
    from repro.serve.daemon import SpatterDaemon
    daemon = SpatterDaemon("127.0.0.1", 0, cache=ExecutorCache()).start()
    client = SpatterClient(daemon.url, timeout=READY_TIMEOUT_S)
    t0 = time.perf_counter()
    while not client.readyz().get("ready"):
        if time.perf_counter() - t0 > READY_TIMEOUT_S:
            daemon.stop()
            raise RuntimeError("spatterd never became ready")
        time.sleep(0.05)
    return daemon, client


def send(client, pat: dict, options: dict, seed: int,
         annotate: bool) -> Request:
    from repro.serve.client import ServerError
    runs = options.get("runs", 10)
    t0 = time.perf_counter()
    try:
        if annotate:
            import jax
            with jax.profiler.TraceAnnotation(f"{REQUEST} {pat['name']}"):
                doc = client.run_suite([pat], seed=seed, **options)
        else:
            doc = client.run_suite([pat], seed=seed, **options)
    except ServerError as e:
        return Request(pat, runs, time.perf_counter() - t0, error=str(e))
    rows = doc["stats"]["table"]
    return Request(pat, runs, time.perf_counter() - t0,
                   elapsed_s=doc["elapsed_s"], misses=doc["cache"]["misses"],
                   digest=rows[0]["digest"] if len(rows) == 1 else None)


def drive(client, cycle, options: dict, seed: int, seconds: float,
          annotate: bool = False) -> tuple[list[Request], float]:
    """Whole cycles, closing at the cycle boundary nearest ``seconds``
    (by the mean cycle so far), one cycle at least; (requests,
    window_s)."""
    reqs: list[Request] = []
    t0 = time.perf_counter()
    cycles = 0
    while True:
        for pat in cycle:
            reqs.append(send(client, pat, options, seed, annotate))
        cycles += 1
        elapsed = time.perf_counter() - t0
        if elapsed + elapsed / cycles / 2 >= seconds:
            return reqs, elapsed


def count_executions(requests: list[Request], trace) -> None:
    """Set each request's ``executions`` from the trace: how many times
    its program ran on the device.  Fails unless the trace holds one
    span per request, in order, and every served request ran its
    program at least once.  A trace with no device programs at all (a
    CPU run) leaves them unset."""
    from bench import reduce
    if not trace.modules:
        return
    spans = trace.requests()
    names = [e.name[len(REQUEST):].strip() for e in spans]
    if names != [r.pattern["name"] for r in requests]:
        raise ValueError(f"the trace's request spans {names} do not match "
                         f"the {len(requests)} requests sent")
    for r, n in zip(requests, reduce.executions(trace)):
        if r.ok and n == 0:
            raise ValueError(f"{r.pattern['name']}: the trace shows no "
                             f"execution of its program")
        r.executions = n


# -- correctness ---------------------------------------------------------------

def expected_digests(requests: list[Request], seed: int,
                     row_width: int) -> dict:
    """The reference digest of every pattern the requests name, each
    computed once, on a few threads (numpy and hashlib release the GIL)."""
    from concurrent.futures import ThreadPoolExecutor
    pats = {r.pattern["name"]: r.pattern for r in requests}
    with ThreadPoolExecutor(REFERENCE_THREADS) as pool:
        digests = pool.map(lambda p: reference.digest(p, seed, row_width),
                           pats.values())
        return dict(zip(pats, digests))


def check(requests: list[Request], seed: int, row_width: int,
          expected=None) -> dict:
    """Every served digest against the reference's; returns the numbers
    compared, each with its limit.  ``expected`` maps a pattern name to
    its reference digest (computed here when not given)."""
    if expected is None:
        expected = expected_digests(requests, seed, row_width)
    mismatched = missing = 0
    for r in requests:
        if not r.ok or r.digest is None:
            missing += 1
        elif r.digest != expected[r.pattern["name"]]:
            mismatched += 1
    return {"digest_mismatches": {"value": mismatched, "limit": 0},
            "requests_failed": {"value": missing, "limit": 0}}


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# -- metrics -------------------------------------------------------------------

def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: list[dict], ctx: Context) -> dict:
    out = {}
    for m in metrics:
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- one run -------------------------------------------------------------------

def use_compile_cache() -> None:
    """JAX's persistent compilation cache at one fixed place in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every
    program in it however fast it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        trace_dir: str, device: dict, start: float = PROCESS_START) -> dict:
    """Set-up, window, check; returns the result document."""
    import jax
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" \
        else {}
    seed = seed % SEED_RANGE
    compiles = Compiles()
    cycle = cell.cycle()
    options = cell.options
    warm_opts = {k: v for k, v in options.items()
                 if k in ("backend", "mode", "runs", "row_width")}
    t_daemon = time.perf_counter()
    daemon, client = start_daemon()
    try:
        t_warm = time.perf_counter()
        for pat in cycle:
            client.warm([pat], **warm_opts)
        t_open = time.perf_counter()
        setup_s = t_open - start
        log(f"set-up {setup_s:.3f} s: start-up {t_daemon - start:.3f} s, "
            f"daemon {t_warm - t_daemon:.3f} s, warm-up "
            f"{t_open - t_warm:.3f} s; {compiles.count} programs built, "
            f"{compiles.cached} of them from the persistent cache")
        n0 = compiles.count
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 2
            opts.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                with jax.profiler.TraceAnnotation(WINDOW):
                    reqs, window_s = drive(client, cycle, options, seed,
                                           seconds, annotate=True)
        else:
            reqs, window_s = drive(client, cycle, options, seed, seconds)
        built_in_window = compiles.count - n0
        mem = memory_peak_bytes(cell.chips)
    finally:
        daemon.stop()
    del daemon, client
    lat = [r.latency_s for r in reqs]
    log(f"{cell.name}: seed {seed}, window {window_s:.3f} s: "
        f"{len(reqs) // len(cycle)} cycles of {len(cycle)} requests, "
        f"{built_in_window} programs built in it")
    log(f"latency samples {len(lat)}: median "
        f"{statistics.median(lat):.4f} s, max {max(lat):.4f} s")
    slow = sorted(reqs, key=lambda r: -r.latency_s)[:3]
    log("slowest: " + ", ".join(
        f"{r.pattern['name']} {r.latency_s:.3f} s "
        f"(served {r.elapsed_s if r.elapsed_s is None else round(r.elapsed_s, 3)} s)"
        for r in slow))
    ctx = Context(cell=cell, requests=reqs, window_s=window_s,
                  setup_s=setup_s, peaks=peaks)
    dev = dict(device, memory_peak_bytes=mem)
    breakdown = None
    if trace:
        from bench import reduce
        ctx.trace = reduce.load(reduce.find_xplane(trace_dir))
        count_executions(reqs, ctx.trace)
        log("program executions per request: "
            + ", ".join(f"{r.pattern['name']} {r.executions}"
                        for r in reqs[:len(cycle)]))
        dev.update(busy_s=reduce.busy_s(ctx.trace),
                   window_s=ctx.trace.window_s)
        breakdown = {"device_ops": reduce.top_ops(ctx.trace),
                     "idle_gaps": reduce.longest_gaps(ctx.trace)}
    t_ref = time.perf_counter()
    checks = check(reqs, seed, cell.row_width)
    log(f"reference and check {time.perf_counter() - t_ref:.3f} s")
    doc = {"correct": passes(checks),
           "attempted": len(reqs),
           "failed": sum(not r.ok for r in reqs),
           "metrics": read_metrics(cell.metrics(trace), ctx),
           "device": dev}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["checks"] = checks
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        use_compile_cache()
        device = require_chip(cell.chips)
        peaks_for(device["kind"])
        doc = run(cell, args.seed, args.seconds, bool(args.trace),
                  trace_dir=os.path.join(TRACE_DIR, cell.name),
                  device=device)
    except Exception as e:
        traceback.print_exc()
        log(f"bench FAILED: {type(e).__name__}: {e}")
        return 1
    for name, c in doc["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
