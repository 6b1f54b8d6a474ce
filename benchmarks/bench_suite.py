"""Canonical suite benchmark -> BENCH_suite.json (perf trajectory).

Runs a JSON suite (default ``suites/demo.json``) through the planner on
each backend and writes one machine-readable record per (pattern, backend):
measured/modeled GB/s, attributed wall time, plus per-backend compile
counts (ExecutorCache.misses — exact) and the pallas one-launch-per-bucket
census (pallas_call primitives in each store/gather bucket executable's
jaxpr).  Two §16 sections ride along: ``autotune`` (the pallas sweep
under the legacy fixed tiles vs the deterministic tile search, plus the
per-geometry tiles chosen) and ``pallas_lane`` (lane-sharded pallas on
the host's accelerators, or on 8 fake CPU devices in a child process,
bit-identity checked against the single-device planner).  The file is
read-merge-written — other benches own their own sections.  CI uploads it as an artifact so the perf
trajectory is tracked across PRs; compare against the committed baseline
with::

    PYTHONPATH=src python -m benchmarks.run --quick --only suite

``--quick`` scales pattern counts down (recorded in ``meta.count_cap``) so
the pallas interpret-mode grids stay small on CPU; absolute numbers are
only comparable within a matching ``meta`` block.
"""
from __future__ import annotations

import dataclasses
import json
import os
import platform
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp

from repro.core import ExecutorCache, SuitePlan, load_suite, run_suite
from repro.core.plan import _assemble_bucket, _build_executable
from repro.core.tracing import count_primitives
from repro.kernels import autotune

from .harness import emit

DEFAULT_SUITE = "suites/demo.json"
DEFAULT_OUT = "BENCH_suite.json"
BACKENDS = ("xla", "onehot", "scalar", "pallas")

# §16 lane-sharded pallas sweep: on the host's own accelerators when it
# has several, else in a child process pinned to the CPU with 8 fake
# devices (the forced device count must never leak into this process,
# and a child must never contend for a chip this process holds)
FAKE_DEVICES = 8
_LANE_CHILD = textwrap.dedent("""\
    import json, sys
    sys.path[:0] = [%(root)r, %(src)r]
    from benchmarks.bench_suite import _lane_rows
    print(json.dumps(_lane_rows(%(suite)r, %(runs)d, %(cap)d)))
    """)


def lane_shapes(n_dev: int) -> tuple:
    """Lane-sharded placements over ``n_dev`` devices: all lanes, and
    half the devices on the batch axis when there are at least four."""
    return ((1, n_dev),) + (((n_dev // 2, 2),) if n_dev >= 4 else ())


def device_label() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "count": len(jax.devices())}


def _lane_rows(suite: str, runs: int, cap: int) -> dict:
    """hmean per lane-sharded shape + bit-identity vs single device, on
    the devices this process sees."""
    pats = load_suite(suite)
    if cap:
        pats = [dataclasses.replace(p, count=min(p.count, cap))
                for p in pats]
    out = {"device": device_label()}
    ref = run_suite(pats, backend="pallas", runs=runs,
                    cache=ExecutorCache(), digest=True)
    d_ref = [r.out_digest for r in ref.results]
    out["single"] = {"hmean_gbs": ref.hmean_gbs}
    for b, l in lane_shapes(len(jax.devices())):
        cache = ExecutorCache()
        t0 = time.perf_counter()
        stats = run_suite(pats, backend="pallas", runs=runs,
                          cache=cache, mesh=(b, l), digest=True)
        out[f"{b}x{l}"] = {
            "hmean_gbs": stats.hmean_gbs,
            "wall_s": time.perf_counter() - t0,
            "compiles": cache.stats().misses,
            "digests_match_single":
                [r.out_digest for r in stats.results] == d_ref,
        }
    return out


def _pallas_lane_sweep(suite: str, runs: int, cap: int) -> dict:
    """Lane-sharded pallas: in-process on the host's accelerators when
    there are several, else on 8 fake CPU devices in a child (its record
    says ``platform: cpu``)."""
    if jax.devices()[0].platform != "cpu" and len(jax.devices()) > 1:
        return _lane_rows(suite, runs, cap)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    code = _LANE_CHILD % {"root": root, "src": os.path.join(root, "src"),
                          "suite": os.path.abspath(suite), "cap": cap,
                          "runs": runs}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{FAKE_DEVICES}")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"pallas-lane child failed: {r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _pallas_launch_census(plan: SuitePlan) -> list[dict]:
    """pallas_call count per bucket executable (acceptance: store == 1)."""
    rows = []
    for bucket in plan.buckets:
        spec = bucket.spec
        mode = "store" if spec.kind == "scatter" else ""
        args, _ = _assemble_bucket(plan, bucket, jnp.float32, 1, 0)
        fn = _build_executable("pallas", spec.kind, mode or "store")
        counts = count_primitives(jax.make_jaxpr(fn)(*args))
        rows.append({
            "kind": spec.kind, "idx_len": spec.idx_len,
            "footprint": spec.footprint, "batch": args[1].shape[0],
            "pallas_calls": counts.get("pallas_call", 0),
            "sort_prims": counts.get("sort", 0),
        })
    return rows


def run(runs: int = 3, *, suite: str = DEFAULT_SUITE,
        out_path: str | None = DEFAULT_OUT, count_cap: int | None = None,
        backends=BACKENDS) -> dict:
    quick = runs <= 3
    if count_cap is None:
        count_cap = 512 if quick else 0          # 0 = uncapped
    patterns = load_suite(suite)
    if count_cap:
        patterns = [dataclasses.replace(p, count=min(p.count, count_cap))
                    for p in patterns]
    plan = SuitePlan.build(patterns)

    results = []
    per_backend = {}
    tiles: dict = {}
    for backend in backends:
        cache = ExecutorCache()
        t0 = time.perf_counter()
        with autotune.recording() as rec:
            stats = run_suite(patterns, backend=backend, runs=runs,
                              cache=cache)
        wall = time.perf_counter() - t0
        tiles.update(rec)
        per_backend[backend] = {
            "compiles": cache.misses,
            "n_buckets": stats.plan.n_buckets,
            "wall_s": wall,
            "hmean_measured_gbs": stats.hmean_gbs,
        }
        for r in stats.results:
            results.append({
                "pattern": r.pattern.name,
                "kind": r.pattern.kind,
                "type": r.pattern.classify(),
                "backend": backend,
                "measured_gbs": r.measured_gbs,
                "modeled_gbs": r.modeled_gbs,
                "time_s": r.time_s,
            })
        emit(f"suite/{backend}", wall * 1e6,
             f"{cache.misses}compiles;hmean={stats.hmean_gbs:.3f}gbs")

    # the before leg of the §16 autotuner: the same pallas sweep under
    # the legacy fixed tiles (what every PR before the search shipped)
    before = None
    if "pallas" in backends:
        with autotune.disabled():
            t0 = time.perf_counter()
            legacy = run_suite(patterns, backend="pallas", runs=runs,
                               cache=ExecutorCache())
            legacy_wall = time.perf_counter() - t0
        before = {"hmean_measured_gbs": legacy.hmean_gbs,
                  "wall_s": legacy_wall}
        tuned = per_backend["pallas"]["hmean_measured_gbs"]
        speedup = (tuned / legacy.hmean_gbs) if legacy.hmean_gbs else -1.0
        emit("suite/pallas_legacy_tiles", legacy_wall * 1e6,
             f"hmean={legacy.hmean_gbs:.3e}gbs;tuned_speedup={speedup:.2f}x")

    lane = None
    if "pallas" in backends:
        lane = _pallas_lane_sweep(suite, max(1, min(runs, 2)),
                                  min(count_cap or 128, 128))
        for shape, row in lane.items():
            if shape in ("single", "device"):
                continue
            emit(f"suite/pallas_lane_{shape}", row["wall_s"] * 1e6,
                 f"{row['hmean_gbs']:.3e}gbs;"
                 f"ident={row['digests_match_single']}")

    doc = {
        "meta": {
            "suite": suite,
            "runs": runs,
            "count_cap": count_cap,
            "n_patterns": len(patterns),
            "n_buckets": plan.n_buckets,
            "jax": jax.__version__,
            "device": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "host": platform.machine(),
        },
        "backends": per_backend,
        "pallas_bucket_launches": _pallas_launch_census(plan),
        "results": results,
        # §16: what the deterministic tile search bought on this host —
        # legacy-tile leg vs the autotuned pallas sweep above — plus the
        # per-geometry tiles it chose (the wire form DiskTier persists)
        "autotune": {
            "before_legacy_tiles": before,
            "after_hmean_measured_gbs":
                per_backend.get("pallas", {}).get("hmean_measured_gbs"),
            "tiles": autotune.to_wire(tiles),
        },
        # §16: lane-sharded pallas (the host's accelerators, or 8 fake CPU
        # devices in a child — see its "device" label) — every shape must
        # stay bit-identical to the single-device planner
        "pallas_lane": lane,
    }
    if out_path:                       # None = CSV only, no trajectory write
        # read-merge-write: other benches (mesh_sweep, serve_concurrency,
        # ...) own their sections of the trajectory file
        prev = {}
        if os.path.exists(out_path):
            with open(out_path) as f:
                prev = json.load(f)
        prev.update(doc)
        with open(out_path, "w") as f:
            json.dump(prev, f, indent=2)
        emit("suite/json", 0.0, out_path)
    return doc


if __name__ == "__main__":
    run()
