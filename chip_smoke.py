#!/usr/bin/env python3
"""Chip smoke test: spatterd and the planner, end to end, on a TPU.

    python chip_smoke.py              # one chip: requests A and B, restart
    python chip_smoke.py --chips 4    # four chips: request B placed on a mesh

One chip.  An in-process ``SpatterDaemon`` (ephemeral port, coalescing
scheduler, a disk cache at ``.chip_smoke_cache/``) is driven over HTTP
with ``SpatterClient`` — the path the CLI and every client take:

  A  ``suites/demo.json`` at its committed counts on every backend
     (xla, pallas, scalar, onehot), in store and add modes;
  B  the paper's Table 5 LULESH proxies at full scale (``appdb``, scale
     1.0): LULESH-G7, -G0, -S1 and -S3, 2^25 useful lanes each, on xla
     and on pallas (LULESH-S1 excepted, see ``PALLAS_SKIP``);
  restart  a second daemon on the same disk cache re-sends A on xla and
     must restore what the first one compiled (``disk_hits > 0``,
     ``misses == 0``).

Every request is sent twice and the second send must compile nothing.
Every pattern's digest is checked against a plain numpy reference (a
gather, a last-write-wins store, ``np.add.at``) built from the same
seeded host buffers.  Add mode may reorder float sums: where its digest
differs, the device output is fetched through the planner, must hash to
the served digest, and must lie within DESIGN.md §11's add-mode
tolerance.  No launch may be degraded, and every Pallas executable must
compile to a Mosaic kernel (``tpu_custom_call``), not the interpreter.

Four chips (``--chips 4``): request B on xla at ``mesh=4``, ``[2,2]``
and ``[1,4]``, and on pallas at ``[1,4]`` and ``4``; each run's digests
must equal the same request at ``mesh=0`` in this process, and every
placed executable must span all four devices.

Any failure exits non-zero without a result line.  On success the last
line of stdout is ``{"ok": true, "device": {...}}``: the platform, kind
and count JAX reports.  The script never reports a share of a peak.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(ROOT, ".chip_smoke_cache")
DEMO = os.path.join(ROOT, "suites", "demo.json")
BACKENDS = ("xla", "pallas", "scalar", "onehot")
MODES = ("store", "add")
B_NAMES = ("LULESH-G7", "LULESH-G0", "LULESH-S1", "LULESH-S3")
# the one-hot scatter's grid grows with table rows x lanes (ROADMAP
# queue 1, item 4): a 2^25-row table against 2^25 lanes does not finish
PALLAS_SKIP = {"LULESH-S1": "one-hot scatter grid grows with table x "
                            "lanes (2^25-row table x 2^25 lanes)"}
# DESIGN.md §11: add mode's float-reassociation tolerance
ADD_RTOL = ADD_ATOL = 1e-6
READY_TIMEOUT_S = 600.0


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """Backend compile seconds, from JAX's own compile-duration events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.count


class Reference:
    """Numpy reference digests (and add-mode arrays) per pattern and
    mode, from the same seeded host buffers the planner assembles."""

    def __init__(self, seed: int):
        self.seed = seed
        self._digests: dict = {}

    def output(self, p, mode: str):
        import numpy as np

        from repro.core.engine import make_host_buffers
        src, idx, vals, _ = make_host_buffers(p, 1, seed=self.seed)
        if p.kind == "gather":
            return src[idx]
        out = np.zeros_like(src)          # the planner scatters into zeros
        if mode == "add":
            np.add.at(out, idx, vals)
        else:                             # last write wins
            _, first = np.unique(idx[::-1], return_index=True)
            last = idx.size - 1 - first
            out[idx[last]] = vals[last]
        return out

    def digest(self, p, mode: str) -> str:
        key = (p.name, p.kind, mode if p.kind == "scatter" else "")
        if key not in self._digests:
            self._digests[key] = sha256(self.output(p, mode))
        return self._digests[key]


def sha256(a) -> str:
    import numpy as np
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


class Smoke:
    def __init__(self, seed: int):
        import numpy as np

        from repro.core import appdb, load_suite
        self.np = np
        self.seed = seed
        self.ref = Reference(seed)
        self.compiles = Compiles()
        with open(DEMO) as f:
            self.demo_wire = json.load(f)
        self.demo = load_suite(DEMO)
        by_name = {p.name: p for p in appdb.scale_counts(appdb.ALL_PATTERNS,
                                                         1.0)}
        self.paper = [by_name[n] for n in B_NAMES]

    # -- daemon lifecycle ----------------------------------------------------
    def start_daemon(self):
        from repro.core.plan import ExecutorCache
        from repro.serve.client import SpatterClient
        from repro.serve.daemon import SpatterDaemon
        daemon = SpatterDaemon("127.0.0.1", 0, cache=ExecutorCache(),
                               workers=2, cache_dir=CACHE_DIR).start()
        client = SpatterClient(daemon.url, timeout=READY_TIMEOUT_S)
        t0 = time.perf_counter()
        while not client.readyz().get("ready"):
            check(time.perf_counter() - t0 < READY_TIMEOUT_S,
                  "daemon never became ready")
            time.sleep(0.1)
        return daemon, client

    # -- one request ---------------------------------------------------------
    def send(self, client, label: str, wire, pats, *, backend: str,
             mode: str, mesh=0, want_warm: bool):
        c0, n0 = self.compiles.mark()
        t0 = time.perf_counter()
        doc = client.run_suite(list(wire), backend=backend, mode=mode,
                               runs=1, seed=self.seed, digest=True,
                               mesh=mesh)
        wall = time.perf_counter() - t0
        c1, n1 = self.compiles.mark()
        cache = doc["cache"]
        serve = doc["serve"] or {}
        log(f"{label}: {wall:.3f} s client latency, "
            f"{doc['elapsed_s']:.3f} s served, "
            f"{n1 - n0} XLA compiles in {c1 - c0:.3f} s, "
            f"cache hits={cache['hits']} misses={cache['misses']} "
            f"lifetime={cache['lifetime']} "
            f"launches={serve.get('launches')} "
            f"degraded_launches={serve.get('degraded_launches')}")
        check(serve.get("degraded_launches", 0) == 0
              and cache["lifetime"]["degraded"] == 0,
              f"{label}: a launch was degraded to the xla fallback")
        if want_warm:
            check(cache["misses"] == 0,
                  f"{label}: warm repeat compiled {cache['misses']}")
        digests = [row["digest"] for row in doc["stats"]["table"]]
        check(len(digests) == len(pats) and all(digests),
              f"{label}: missing digests")
        return doc, digests

    def verify(self, label: str, daemon, pats, digests, *, backend: str,
               mode: str) -> None:
        """Every digest equals the numpy reference's; in add mode a
        scatter may instead match within the add-mode tolerance."""
        np = self.np
        plan = None
        for i, (p, got) in enumerate(zip(pats, digests)):
            want = self.ref.digest(p, mode)
            if got == want:
                continue
            from repro.core.plan import SuitePlan, execute_bucket
            plan = plan or SuitePlan.build(pats)
            bucket = next(b for b in plan.buckets if i in b.members)
            outs = execute_bucket(plan, bucket, backend=backend, mode=mode,
                                  seed=self.seed, cache=daemon.cache)
            dev = outs[bucket.members.index(i)]
            ref = self.ref.output(p, mode)
            wrong = int(np.count_nonzero(dev != ref))
            log(f"{label}: {p.name} digest differs from numpy reference; "
                f"refetched output has {wrong}/{ref.size} elements off, "
                f"hashes to the served digest: {sha256(dev) == got}")
            check(mode == "add" and p.kind == "scatter",
                  f"{label}: {p.name} digest differs from numpy reference")
            check(sha256(dev) == got,
                  f"{label}: {p.name} planner output does not hash to the "
                  f"served digest")
            err = float(np.max(np.abs(dev - ref)))
            check(np.allclose(dev, ref, rtol=ADD_RTOL, atol=ADD_ATOL),
                  f"{label}: {p.name} add-mode max |err| {err:.3e} beyond "
                  f"rtol=atol={ADD_RTOL}")
            log(f"{label}: {p.name} add-mode sums reordered, max |err| "
                f"{err:.3e} within rtol=atol={ADD_RTOL} (DESIGN.md §11)")

    def request(self, daemon, client, label, wire, pats, *, backend, mode,
                mesh=0, sends: int = 2):
        digests = None
        for k in range(sends):
            _, got = self.send(client, f"{label} send {k + 1}", wire, pats,
                               backend=backend, mode=mode, mesh=mesh,
                               want_warm=k > 0)
            check(digests is None or got == digests,
                  f"{label}: digests changed between sends")
            digests = got
        self.verify(label, daemon, pats, digests, backend=backend, mode=mode)
        return digests

    def paper_request(self, backend: str):
        pats = [p for p in self.paper
                if backend != "pallas" or p.name not in PALLAS_SKIP]
        return [p.to_json() for p in pats], pats

    def check_mosaic(self, daemon) -> int:
        """Every cached pallas executable compiles to a Mosaic kernel."""
        from repro.core.plan import key_avals
        n = 0
        for key, fn in daemon.cache.entries():
            if key.backend != "pallas":
                continue
            text = fn.lower(*key_avals(key)).compile().as_text()
            check("tpu_custom_call" in text,
                  f"pallas executable {key} has no tpu_custom_call: it "
                  f"ran in interpret mode")
            n += 1
        return n

    # -- phases --------------------------------------------------------------
    def one_chip(self) -> None:
        for p in self.paper:
            log(f"size {p.name}: {p.kind}, {p.count * p.index_len} lanes, "
                f"footprint {p.footprint()} elements "
                f"({p.footprint() * 4 / 2**20:.1f} MiB float32)")
        daemon, client = self.start_daemon()
        try:
            a_xla = {}
            for backend in BACKENDS:
                for mode in MODES:
                    d = self.request(daemon, client, f"A {backend}/{mode}",
                                     self.demo_wire, self.demo,
                                     backend=backend, mode=mode)
                    if backend == "xla":
                        a_xla[mode] = d
            for backend in ("xla", "pallas"):
                if backend == "pallas":
                    for name, why in PALLAS_SKIP.items():
                        log(f"B pallas: skipping {name}: {why}")
                wire, pats = self.paper_request(backend)
                self.request(daemon, client, f"B {backend}/store", wire,
                             pats, backend=backend, mode="store")
            n = self.check_mosaic(daemon)
            check(n > 0, "no pallas executable was cached")
            log(f"mosaic: {n} pallas executables hold a tpu_custom_call")
            disk = daemon.disk.stats()
            log(f"disk tier after first daemon: {disk}")
            check(disk["store_failures"] == 0,
                  f"{disk['store_failures']} executables failed to persist")
        finally:
            daemon.stop()
        daemon, client = self.start_daemon()
        try:
            for mode in MODES:
                label = f"restart A xla/{mode}"
                doc, got = self.send(client, label, self.demo_wire,
                                     self.demo, backend="xla", mode=mode,
                                     want_warm=True)
                check(doc["cache"]["lifetime"]["disk_hits"] > 0,
                      f"{label}: nothing was restored from disk")
                check(got == a_xla[mode],
                      f"{label}: digests differ from the first daemon's")
        finally:
            daemon.stop()

    def four_chips(self) -> None:
        import jax

        from repro.core.plan import key_avals
        check(len(jax.devices()) == 4,
              f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
        daemon, client = self.start_daemon()
        try:
            for backend, meshes in (("xla", (4, [2, 2], [1, 4])),
                                    ("pallas", ([1, 4], 4))):
                wire, pats = self.paper_request(backend)
                ref = self.request(daemon, client, f"B {backend} mesh=0",
                                   wire, pats, backend=backend,
                                   mode="store", sends=1)
                for mesh in meshes:
                    label = f"B {backend} mesh={mesh}"
                    _, got = self.send(client, label, wire, pats,
                                       backend=backend, mode="store",
                                       mesh=mesh, want_warm=False)
                    check(got == ref, f"{label}: digests differ from mesh=0")
            placed = 0
            for key, fn in daemon.cache.entries():
                if not key.placement:
                    continue
                compiled = fn.lower(*key_avals(key)).compile()
                devs = {d for s in compiled.input_shardings[0]
                        for d in s.device_set}
                check(len(devs) == 4,
                      f"{key.placement} {key.kind} executable spans "
                      f"{len(devs)} devices, not 4")
                placed += 1
            check(placed > 0, "no placed executable was cached")
            log(f"placement: {placed} placed executables span 4 devices")
            log(f"mosaic: {self.check_mosaic(daemon)} pallas executables "
                f"hold a tpu_custom_call")
        finally:
            daemon.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: requests A, B and the restart; 4: request B "
                         "placed across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="host-buffer RNG seed")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.core.compile_cache import use_compile_cache
        cache_dir = use_compile_cache()
        import jax
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        log(f"device: {device}, jax {jax.__version__}, compile cache "
            f"{cache_dir}")
        check(dev.platform == "tpu",
              f"no TPU: JAX's first device is {dev.platform}")
        shutil.rmtree(CACHE_DIR, ignore_errors=True)
        smoke = Smoke(args.seed)
        if args.chips == 4:
            smoke.four_chips()
        else:
            smoke.one_chip()
        log(f"total: {time.perf_counter() - t0:.1f} s, XLA compiles "
            f"{smoke.compiles.count} in {smoke.compiles.seconds:.1f} s")
    except Exception as e:                        # any phase failing fails
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
