"""The paper's CLI, reproduced (§3.4):

    ./spatter -k Gather -p UNIFORM:8:1 -d 8 -l $((2**24))
becomes
    PYTHONPATH=src python examples/spatter_cli.py -k Gather -p UNIFORM:8:1 \
        -d 8 -l 65536 [-b xla|onehot|scalar|pallas] [--json suites/x.json]

Prints the paper's outputs (min-time bandwidth) plus the TPU-model columns
(modeled v5e GB/s, tile efficiency, reuse factor).

Multi-device suites (--json mode): ``--mesh N`` splits every bucket
launch's pattern-batch dim over N devices (the paper §3.4 thread-scaling
story, scaled to devices); ``--mesh BxL`` (e.g. ``4x2``) places launches
on a 2-D (pattern-batch x lane) mesh — the lane axis splits *within*
each pattern, so suites with few patterns but huge counts still fill the
mesh (core/plan.py Placement, DESIGN.md §11).  On a CPU-only host, force
fake devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/spatter_cli.py --json suite.json \\
        --mesh 4x2

Scatter write semantics: ``--mode store`` (last-write-wins, the paper's
default) or ``--mode add`` (accumulation), on both single-pattern and
suite runs.

Static audit (spatterlint, DESIGN.md §12) — check every executable the
planner would build for a suite WITHOUT running it, plus the serving
layer's lock-discipline lint; non-zero exit on any violation::

    PYTHONPATH=src python examples/spatter_cli.py --lint suites/demo.json \\
        [--mesh 4x2] [--lint-out LINT_report.json]

Static traffic accounting (spattercost, DESIGN.md §15) — predict the
exact bytes every executable moves, reconcile against the lowered
StableHLO, and convert to GB/s via the BENCH-calibrated roofline; with
``--mesh auto`` the placement is chosen by minimum predicted traffic::

    PYTHONPATH=src python examples/spatter_cli.py --cost suites/demo.json \\
        [--mesh auto|4x2] [--cost-out COST_report.json]

``--mesh auto`` also works on a live run (--json): the suite executes on
the min-predicted-cost placement, with ExecKeys (and digests) identical
to the same explicit --mesh run.

spatterd quickstart (the serving layer, DESIGN.md §10) — one process
keeps the ExecutorCache warm across requests, so only the FIRST request
for a suite shape compiles anything:

    # terminal 1: the daemon (add XLA_FLAGS=...device_count=8 for --mesh 8)
    PYTHONPATH=src python examples/spatter_cli.py --serve --port 8089
    # terminal 2: any number of clients, any number of times
    PYTHONPATH=src python examples/spatter_cli.py \\
        --client http://127.0.0.1:8089 --json suites/demo.json
    # the response prints "cache ... misses 0" from the second request on,
    # with per-pattern sha256 digests proving bit-identical results
"""
import argparse

# argparse defaults are None sentinels so the --serve/--client branches
# can tell "flag omitted" from "flag given" exactly (comparing against a
# real default would silently drop an explicit `--runs 10`); LOCAL_DEFAULTS
# is applied only on the local execution path, and the help text is the
# single place each default is narrated.
LOCAL_DEFAULTS = dict(kernel="Gather", pattern="UNIFORM:8:1", delta=8,
                      count=1 << 16, backend="xla", runs=10, row_width=1,
                      mesh=0, mode="store", host="127.0.0.1", port=8089)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-k", "--kernel", default=None,
                    choices=["Gather", "Scatter", "gather", "scatter"],
                    help="access kind (default Gather)")
    ap.add_argument("-p", "--pattern", default=None,
                    help="UNIFORM:N:S | MS1:N:B:G | LAPLACIAN:D:L:S | "
                         "BROADCAST:N:R | i0,i1,...  (default UNIFORM:8:1)")
    ap.add_argument("-d", "--delta", type=int, default=None,
                    help="stride between accesses (default 8)")
    ap.add_argument("-l", "--count", type=int, default=None,
                    help="access count (default 65536)")
    ap.add_argument("-b", "--backend", default=None,
                    choices=["xla", "onehot", "scalar", "pallas"],
                    help="backend (default xla)")
    ap.add_argument("-r", "--runs", type=int, default=None,
                    help="min-of-K timing (paper §3.5, default 10)")
    ap.add_argument("--row-width", type=int, default=None,
                    help="TPU row granularity (default 1 = paper's scalar "
                         "element)")
    ap.add_argument("--json", default=None,
                    help="run a JSON suite file instead (paper §3.3)")
    ap.add_argument("--no-batch", action="store_true",
                    help="suite mode: one compile per pattern instead of "
                         "the bucketed planner (plan.py)")
    ap.add_argument("--mesh", default=None, metavar="N|BxL|auto",
                    help="suite mode: shard bucket launches over N devices "
                         "(pattern-batch axis) or a BxL (batch x lane) 2-D "
                         "placement, e.g. 4x2; 'auto' picks the minimum "
                         "predicted-traffic shape (DESIGN.md §15); "
                         "default 0 = off")
    ap.add_argument("--mode", default=None, choices=["store", "add"],
                    help="scatter write semantics: last-write-wins store "
                         "(paper default) or add accumulation")
    ap.add_argument("--stream-r", action="store_true",
                    help="suite mode: also time a STREAM-like reference "
                         "and report paper Eq. 1 Pearson's R")
    ap.add_argument("--lint", default=None, metavar="SUITE",
                    help="spatterlint: statically audit every executable "
                         "the planner would build for SUITE (no execution; "
                         "repro.analysis, DESIGN.md §12) plus the serving-"
                         "layer concurrency lint; honors --mesh/--backend/"
                         "--mode/--row-width and exits non-zero on any "
                         "violation")
    ap.add_argument("--lint-out", default=None, metavar="FILE",
                    help="--lint: also write the JSON lint report (the "
                         "same schema GET /lint serves)")
    ap.add_argument("--cost", default=None, metavar="SUITE",
                    help="spattercost: statically predict the bytes every "
                         "executable the planner would build for SUITE "
                         "moves (no execution; repro.analysis.cost, "
                         "DESIGN.md §15), reconciled against the lowered "
                         "HLO and converted to GB/s via the calibrated "
                         "roofline; honors --mesh (incl. 'auto')/"
                         "--backend/--mode/--row-width and exits non-zero "
                         "on any violation")
    ap.add_argument("--cost-out", default=None, metavar="FILE",
                    help="--cost: also write the JSON cost report (the "
                         "same schema GET /cost serves; jax-free to "
                         "consume)")
    ap.add_argument("--serve", action="store_true",
                    help="run spatterd: serve JSON suites over HTTP off "
                         "the warm executor cache (repro.serve)")
    ap.add_argument("--client", default=None, metavar="URL",
                    help="POST --json to a running spatterd instead of "
                         "executing locally")
    ap.add_argument("--stats", action="store_true",
                    help="--client: print the daemon's /stats document "
                         "(cache counters + scheduler queue/worker "
                         "snapshot) instead of posting a suite")
    ap.add_argument("--host", default=None,
                    help="--serve bind address (default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=None,
                    help="--serve port (default 8089)")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="--serve: persist compiled executables here so a "
                         "restarted daemon starts warm (DESIGN.md §14)")
    args = ap.parse_args()

    def _given(names):
        # identity checks: 0 is a legitimate explicit value (--port 0
        # binds ephemeral), and 0 == False would swallow it
        return [f"--{n.replace('_', '-')}" for n in names
                if getattr(args, n) is not None
                and getattr(args, n) is not False]

    if args.lint is not None:
        # a static audit executes nothing: run-shaped options are a
        # contradiction, not something to drop silently
        bad = _given(("json", "no_batch", "client", "kernel", "pattern",
                      "delta", "count", "runs", "stream_r", "host",
                      "port", "stats", "cache_dir", "cost",
                      "cost_out")) + (["--serve"] if args.serve else [])
        if bad:
            ap.error(f"{', '.join(bad)}: not applicable to --lint "
                     f"(static audit; only --mesh/--backend/--mode/"
                     f"--row-width apply)")
        from repro.analysis.lint import lint_serve, lint_suite_file
        from repro.serve.schema import parse_mesh
        try:
            mesh = parse_mesh(str(args.mesh)) if args.mesh is not None \
                else 0
        except ValueError as e:
            ap.error(f"--mesh: {e}")
        if mesh == "auto":
            # resolve before the audit so the report names the concrete
            # shape the cost model chose (DESIGN.md §15)
            from repro.analysis.cost import auto_placement
            from repro.core import load_suite
            mesh = auto_placement(
                load_suite(args.lint),
                row_width=args.row_width or LOCAL_DEFAULTS["row_width"],
            ) or 0
        backends = (args.backend,) if args.backend else ("xla", "pallas")
        try:
            report = lint_serve().merge(lint_suite_file(
                args.lint, mesh=mesh, backends=backends,
                mode=args.mode or LOCAL_DEFAULTS["mode"],
                row_width=args.row_width or LOCAL_DEFAULTS["row_width"]))
        except (ValueError, OSError) as e:
            ap.error(f"--lint: {e}")
        if args.lint_out:
            report.dump(args.lint_out)
        print(report.summary())
        if not report.ok:
            raise SystemExit(1)
        return

    if args.lint_out is not None:
        ap.error("--lint-out requires --lint SUITE")

    if args.cost is not None:
        # like --lint, a static traffic analysis executes nothing
        bad = _given(("json", "no_batch", "client", "kernel", "pattern",
                      "delta", "count", "runs", "stream_r", "host",
                      "port", "stats",
                      "cache_dir")) + (["--serve"] if args.serve else [])
        if bad:
            ap.error(f"{', '.join(bad)}: not applicable to --cost "
                     f"(static analysis; only --mesh/--backend/--mode/"
                     f"--row-width apply)")
        from repro.analysis.cost import cost_suite_file
        from repro.serve.schema import parse_mesh
        try:
            mesh = parse_mesh(str(args.mesh)) if args.mesh is not None \
                else 0
        except ValueError as e:
            ap.error(f"--mesh: {e}")
        backends = (args.backend,) if args.backend else ("xla", "pallas")
        try:
            report = cost_suite_file(
                args.cost, mesh=mesh or None, backends=backends,
                mode=args.mode or LOCAL_DEFAULTS["mode"],
                row_width=args.row_width or LOCAL_DEFAULTS["row_width"])
        except (ValueError, OSError) as e:
            ap.error(f"--cost: {e}")
        if args.cost_out:
            report.dump(args.cost_out)
        print(report.summary())
        if not report.ok:
            raise SystemExit(1)
        return

    if args.cost_out is not None:
        ap.error("--cost-out requires --cost SUITE")

    if args.serve:
        if args.client:
            ap.error("--serve and --client are exclusive modes: run the "
                     "daemon OR talk to one")
        # execution options are PER-REQUEST in serve mode (they ride in
        # each POST body): refuse them rather than dropping them silently
        dropped = _given(("json", "no_batch", "mesh", "mode", "backend",
                          "row_width", "runs", "kernel", "pattern",
                          "delta", "count", "stream_r", "stats"))
        if dropped:
            ap.error(f"{', '.join(dropped)}: per-request options — pass "
                     f"them to --client (or in the POST body), not --serve")
        from repro.serve import daemon
        host = LOCAL_DEFAULTS["host"] if args.host is None else args.host
        port = LOCAL_DEFAULTS["port"] if args.port is None else args.port
        argv = ["--host", host, "--port", str(port)]
        if args.cache_dir is not None:
            argv += ["--cache-dir", args.cache_dir]
        daemon.main(argv)
        return

    if args.client:
        if args.stats:
            # the read-only stats verb: no suite, no execution options
            extra = _given(("json", "no_batch", "mesh", "mode", "backend",
                            "row_width", "runs", "kernel", "pattern",
                            "delta", "count", "stream_r", "host", "port",
                            "cache_dir"))
            if extra:
                ap.error(f"{', '.join(extra)}: --stats is a read-only "
                         f"query; it takes only --client URL")
            from repro.serve import client as sc
            sc.main(["--url", args.client, "--stats"])
            return
        if not args.json:
            ap.error("--client needs --json SUITE to post (or --stats)")
        if args.no_batch:
            ap.error("--no-batch is local-only: spatterd always runs the "
                     "bucketed planner")
        single = _given(("kernel", "pattern", "delta", "count"))
        if single:
            ap.error(f"{', '.join(single)}: single-pattern options don't "
                     f"apply to --client suite posts (use --json)")
        local = _given(("host", "port", "cache_dir"))
        if local:
            ap.error(f"{', '.join(local)}: --serve options — the target "
                     f"daemon is the --client URL")
        # delegate to the client CLI (like --serve delegates to
        # daemon.main): this wrapper forwards the paper CLI's common
        # options; the FULL wire surface (--metric, --seed, --stream-n,
        # --no-digest, envelope files) lives on `python -m
        # repro.serve.client`.  Only flags the user gave are forwarded
        # (None = omitted), so an envelope suite file's own fields are
        # never silently overridden by CLI defaults
        from repro.serve import client as sc
        argv = ["--url", args.client, "--json", args.json]
        for flag, name in (("--backend", "backend"), ("--runs", "runs"),
                           ("--mode", "mode"), ("--mesh", "mesh"),
                           ("--row-width", "row_width")):
            v = getattr(args, name)
            if v is not None:
                argv += [flag, str(v)]
        if args.stream_r:
            argv += ["--stream-r"]
        sc.main(argv)
        return

    stray = _given(("host", "port", "cache_dir"))
    if stray:
        ap.error(f"{', '.join(stray)}: --serve options (add --serve, or "
                 f"target a running daemon with --client URL)")
    if args.stats:
        ap.error("--stats queries a running daemon: add --client URL")

    # local execution from here on: resolve the omitted flags to the
    # paper defaults, then pay the JAX startup the --serve/--client
    # branches above deliberately avoid
    opt = {k: v if getattr(args, k) is None else getattr(args, k)
           for k, v in LOCAL_DEFAULTS.items()}
    if opt["runs"] < 1:
        ap.error("--runs must be >= 1 (min-of-K timing needs a run)")
    if args.stream_r and not args.json:
        ap.error("--stream-r only applies to --json suite mode")
    from repro.core import GSEngine, Placement, load_suite, make_pattern, \
        run_suite
    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()

    mesh = None
    mesh_grid = (1, 1)
    from repro.serve.schema import parse_mesh
    try:
        mesh_shape = parse_mesh(str(opt["mesh"]))
    except ValueError as e:
        ap.error(f"--mesh: {e}")
    if mesh_shape:                             # 0 = off (the default)
        if not args.json:
            ap.error("--mesh only applies to --json suite mode")
        if args.no_batch:
            ap.error("--mesh requires the bucketed planner (drop --no-batch)")
        if mesh_shape == "auto":
            # §15 cost model picks the shape; the run below then uses the
            # same ExecKeys an explicit --mesh BxL would, so warm caches
            # and digests are shared with explicit-mesh runs
            from repro.analysis.cost import auto_placement
            mesh_shape = auto_placement(load_suite(args.json),
                                        row_width=opt["row_width"])
            chosen = "single (1x1)" if mesh_shape is None \
                else "x".join(map(str, mesh_shape))
            print(f"mesh : auto-selected {chosen} "
                  f"(min predicted traffic, DESIGN.md §15)")
        if mesh_shape:
            try:
                mesh = Placement.create(mesh_shape)  # validates devices
            except ValueError as e:
                ap.error(f"--mesh: {e}")
            mesh_grid = mesh.grid

    if args.json:
        stats = run_suite(load_suite(args.json), backend=opt["backend"],
                          runs=opt["runs"], row_width=opt["row_width"],
                          mode=opt["mode"], stream_r=args.stream_r,
                          batch=not args.no_batch, mesh=mesh)
        print(f"{'name':24s} {'type':16s} {'cpu GB/s':>9s} {'v5e GB/s':>9s} "
              f"{'tile_eff':>8s}")
        for r in stats.results:
            print(f"{r.pattern.name:24s} {r.pattern.classify():16s} "
                  f"{r.measured_gbs:9.2f} {r.modeled_gbs:9.1f} "
                  f"{r.tile_efficiency:8.3f}")
        print(f"\nsuite: min {stats.min_gbs:.2f}  max {stats.max_gbs:.2f}  "
              f"harmonic-mean {stats.hmean_gbs:.2f} GB/s   (paper §3.5)")
        if stats.stream_gbs is not None:
            print(f"stream: {stats.stream_gbs:.2f} GB/s reference   "
                  f"Pearson R={stats.stream_r:.3f} (paper Eq. 1)")
        if stats.plan is not None:
            print(f"plan : {len(stats.results)} patterns -> "
                  f"{stats.plan.n_buckets} shape buckets "
                  f"(pad waste {stats.plan.pad_waste(*mesh_grid):.1%})")
        if mesh is not None:
            b, l = mesh_grid
            n_dev = b * l
            print(f"mesh : {mesh.placement} — pattern-batch x{b}, "
                  f"lanes x{l} (aggregate GB/s above; per-device = /"
                  f"{n_dev})")
        return

    p = make_pattern(opt["pattern"], kind=opt["kernel"].lower(),
                     delta=opt["delta"], count=opt["count"])
    print(f"pattern  : {list(p.index)}")
    print(f"type     : {p.classify()}   delta={p.delta}  count={p.count}")
    print(f"footprint: {p.footprint()} elems   reuse={p.reuse_factor():.2f}x")
    r = GSEngine(p, backend=opt["backend"], mode=opt["mode"],
                 row_width=opt["row_width"]).run(runs=opt["runs"])
    print(f"time     : {r.time_s*1e6:.1f} us (min of {opt['runs']})")
    print(f"bandwidth: {r.measured_gbs:.2f} GB/s measured(cpu)   "
          f"{r.modeled_gbs:.1f} GB/s modeled(v5e)   "
          f"tile_eff={r.tile_efficiency:.3f}")


if __name__ == "__main__":
    main()
