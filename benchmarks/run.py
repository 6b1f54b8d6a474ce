"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Emits ``name,us_per_call,derived`` CSV rows.  Mapping (DESIGN.md §7):
    Fig 3/5  -> bench_uniform_stride     Table 4 -> bench_app_patterns
    Fig 4    -> bench_prefetch           Table 3 STREAM -> bench_stream
    Fig 6    -> bench_vector_vs_scalar   beyond-paper   -> bench_llm_gs

The ``suite`` bench additionally writes ``BENCH_suite.json`` (per-pattern
measured/modeled GB/s, per-backend compile counts, pallas launch census) —
the canonical cross-PR perf trajectory record; CI uploads it as an
artifact.  ``--suite-json`` overrides the output path.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timing repetitions")
    ap.add_argument("--only", default=None,
                    help="run a single bench by name")
    ap.add_argument("--suite-json", default=None, metavar="PATH",
                    help="output path for the suite bench's "
                         "BENCH_suite.json record")
    args = ap.parse_args()
    runs = 3 if args.quick else 5

    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bench_app_patterns, bench_llm_gs, bench_prefetch,
                   bench_roofline, bench_serve, bench_sharded_suite,
                   bench_stream, bench_suite, bench_suite_scaling,
                   bench_uniform_stride, bench_vector_vs_scalar)
    # only an explicit request (--suite-json or --only suite) writes the
    # canonical BENCH_suite.json; a full CSV sweep must not silently
    # clobber a committed baseline in the cwd
    if args.suite_json:
        suite_kw = {"out_path": args.suite_json}
    elif args.only == "suite":
        suite_kw = {}
    else:
        suite_kw = {"out_path": None}
    # same guard for the mesh-shape sweep's merge into BENCH_suite.json
    sharded_kw = {} if args.only == "sharded_suite" else {"out_path": None}
    # and for the cost-model record's cost_model key
    roofline_kw = {} if args.only == "roofline" else {"out_path": None}
    # and for the serving-concurrency sweep's serve_concurrency key
    serve_kw = {} if args.only == "serve" else {"out_path": None}
    benches = {
        "stream": lambda: bench_stream.run(runs=runs),
        "uniform_stride": lambda: bench_uniform_stride.run(runs=runs),
        "prefetch": lambda: bench_prefetch.run(runs=runs),
        "vector_vs_scalar": lambda: bench_vector_vs_scalar.run(runs=runs),
        "app_patterns": lambda: bench_app_patterns.run(runs=runs),
        "llm_gs": lambda: bench_llm_gs.run(runs=runs),
        "roofline": lambda: bench_roofline.run(runs=runs, **roofline_kw),
        "suite_scaling": lambda: bench_suite_scaling.run(runs=runs),
        "sharded_suite": lambda: bench_sharded_suite.run(runs=runs,
                                                         **sharded_kw),
        "suite": lambda: bench_suite.run(runs=runs, **suite_kw),
        "serve": lambda: bench_serve.run(runs=runs, **serve_kw),
    }
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception as e:      # report, keep the suite running
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}",
                  file=sys.stderr)
            raise
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
