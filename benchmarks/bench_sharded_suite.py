"""Multi-device suite execution: the 2-D placement layer's mesh-shape
sweep (core/plan.py Placement, DESIGN.md §11).

Runs the two placement-sensitive suites — ``suites/apps.json`` (Table 5
appdb proxies, many near-singleton buckets) and ``suites/widelane.json``
(few patterns, huge counts) — on the host's accelerators when it has
several, else inside a subprocess pinned to the CPU that forces
``N_DEV`` fake host devices (XLA_FLAGS must be set before jax
initializes, so this cannot run in the parent process).  Each suite runs
single-device and at every pow-2 mesh shape of the device count
(``8x1``, ``4x2``, ``2x4``, ``1x8`` on eight); per shape we record
aggregate harmonic-mean GB/s, wall clock, exact compile count, and the
plan's pad waste at that ``(batch, lane)`` grid — the number the 2-D
layer exists to shrink (a ``8x1`` launch of a
2-member bucket wastes 6/8 of the mesh on scratch patterns; ``4x2``
moves half of that parallelism onto the lane axis).

The per-shape records merge into ``BENCH_suite.json`` (key
``mesh_sweep``, labelled with the device it ran on) so the shape
trajectory rides the canonical perf record.

On a CPU host the fake devices share the same cores, so wall-clock
parity (not speedup) is the expected result — the bench verifies the
placement layer's overhead structure and padding accounting; the
per-device split is the number that scales on real multi-chip hardware.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .harness import emit

N_DEV = 8                       # fake CPU devices when no chips are present
SUITES = ("apps", "widelane")
OUT_PATH = "BENCH_suite.json"

_CHILD = textwrap.dedent("""\
    import json, sys
    sys.path[:0] = [%(root)r, %(src)r]
    from benchmarks.bench_sharded_suite import sweep
    print(json.dumps(sweep(%(root)r, %(runs)d, %(cap)d)))
    """)


def grid_shapes(n_dev: int) -> tuple:
    """Every pow-2 (batch, lane) factorization of ``n_dev``, batch-major
    first: 8 -> 8x1, 4x2, 2x4, 1x8."""
    out = []
    b = n_dev
    while b >= 1:
        if n_dev % b == 0:
            out.append((b, n_dev // b))
        b //= 2
    return tuple(out)


def sweep(root: str, runs: int, cap: int) -> dict:
    """The mesh-shape sweep on the devices this process sees."""
    import dataclasses
    import time

    import jax

    from repro.core import ExecutorCache, SuitePlan, load_suite, run_suite
    d = jax.devices()[0]
    out = {"device": {"platform": d.platform, "device_kind": d.device_kind,
                      "count": len(jax.devices())}}
    for name in SUITES:
        pats = load_suite(os.path.join(root, "suites", name + ".json"))
        if cap:
            pats = [dataclasses.replace(p, count=min(p.count, cap))
                    for p in pats]
        plan = SuitePlan.build(pats)

        cache = ExecutorCache()
        t0 = time.perf_counter()
        single = run_suite(pats, backend="xla", runs=runs, cache=cache)
        rec = {"n_patterns": len(pats), "n_buckets": plan.n_buckets,
               "single": {"hmean_gbs": single.hmean_gbs,
                          "wall_s": time.perf_counter() - t0,
                          "compiles": cache.stats().misses,
                          "pad_waste": plan.pad_waste()},
               "shapes": {}}
        for b, l in grid_shapes(len(jax.devices())):
            cache = ExecutorCache()
            t0 = time.perf_counter()
            stats = run_suite(pats, backend="xla", runs=runs, cache=cache,
                              mesh=(b, l))
            rec["shapes"][f"{b}x{l}"] = {
                "hmean_gbs": stats.hmean_gbs,
                "wall_s": time.perf_counter() - t0,
                "compiles": cache.stats().misses,
                "pad_waste": plan.pad_waste(b, l),
            }
        out[name] = rec
    return out


def run(runs: int = 3, *, out_path: str | None = OUT_PATH) -> dict:
    """Sweep on the host's accelerators when there are several; else in
    a child pinned to the CPU with ``N_DEV`` fake devices (its record
    says ``platform: cpu``) — the parent may hold a chip, and the forced
    device count must never leak into it."""
    import jax
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    cap = 2048 if runs <= 3 else 0          # quick mode: count cap
    if jax.devices()[0].platform != "cpu" and len(jax.devices()) > 1:
        swept = sweep(root, runs, cap)
    else:
        code = _CHILD % {"root": root, "src": os.path.join(root, "src"),
                         "runs": runs, "cap": cap}
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count="
                             f"{N_DEV}")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=540, env=env)
        if r.returncode != 0:
            raise RuntimeError(
                f"sharded-suite child failed: {r.stderr[-2000:]}")
        swept = json.loads(r.stdout.strip().splitlines()[-1])
    device = swept.pop("device")

    for name, rec in swept.items():
        emit(f"sharded_suite/{name}_single",
             rec["single"]["wall_s"] * 1e6,
             f"{rec['single']['hmean_gbs']:.2f}GB/s;"
             f"waste={rec['single']['pad_waste']:.0%}")
        for shape, row in rec["shapes"].items():
            emit(f"sharded_suite/{name}_{shape}",
                 row["wall_s"] * 1e6,
                 f"{row['hmean_gbs']:.2f}GB/s;"
                 f"waste={row['pad_waste']:.0%};"
                 f"{row['compiles']}compiles")

    # merge the sweep into the canonical trajectory record (bench_suite
    # owns the rest of the file; a missing file still gets the sweep).
    # ``out_path=None`` skips the write — run.py passes it on full CSV
    # sweeps so the committed baseline is never silently clobbered (the
    # same guard bench_suite honors).  Resolved against the repo root
    # like the suite inputs, so an explicit write from another cwd still
    # updates the canonical file; count_cap rides in the record because
    # capped counts change widelane's whole geometry (pad_waste included)
    # and the numbers are only comparable within a matching cap.
    if out_path:
        if not os.path.isabs(out_path):
            out_path = os.path.join(root, out_path)
        doc = {}
        if os.path.exists(out_path):
            with open(out_path) as f:
                doc = json.load(f)
        doc["mesh_sweep"] = {"device": device, "runs": runs,
                             "count_cap": cap, "suites": swept}
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=2)
        emit("sharded_suite/json", 0.0, out_path)
    return swept


if __name__ == "__main__":
    run()
