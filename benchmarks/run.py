"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only chain,dims]
                                            [--json OUTDIR]

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).
With ``--json OUTDIR`` additionally writes one ``BENCH_<module>.json``
per module mapping row name → us_per_call, so the perf trajectory is
machine-readable across PRs.  The schema (including the serve suite's
metrics fields) and how to read the scheduler statistics are documented
in ``docs/BENCHMARKS.md``.

Modules:
  chain      paper Fig. 7/8 + Table 4 (chain length × dtype, speedups,
             throughput)
  dims       paper Fig. 9 (width/height dependency)
  operators  paper Table 5 (geodesic operators vs queue baselines)
  crossover  paper §4.3/§5 (chained 3×3 vs O(1)/px window crossover)
  roofline   §Roofline terms from the dry-run artifacts
  serve      repro.serve micro-batching: single-request latency vs
             batched throughput across bucket sizes (occupancy, cache
             hit-rate and FPS in the derived column)
  pipeline   repro.api expression pipeline: fused vs per-stage ASF
             (pad/launch round-trip counts from Executable.stats())
             and the compile-cache hit rate
  gdt        generalised geodesic distance: wavefront requeue vs
             raster-sweep schedules vs the binary L1 QDT baseline
"""
from __future__ import annotations

import argparse
import json
import pathlib

from benchmarks import (bench_chain, bench_crossover, bench_dims,
                        bench_gdt, bench_operators, bench_pipeline,
                        bench_roofline, bench_serve, bench_table3)
from benchmarks.common import emit
from repro.core.compile_cache import enable_compile_cache

MODULES = {
    "chain": bench_chain,
    "dims": bench_dims,
    "operators": bench_operators,
    "crossover": bench_crossover,
    "table3": bench_table3,
    "roofline": bench_roofline,
    "serve": bench_serve,
    "pipeline": bench_pipeline,
    "gdt": bench_gdt,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (1024², long chains)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--json", default=None, metavar="OUTDIR",
                    help="write BENCH_<module>.json files (name -> "
                         "us_per_call) into OUTDIR")
    args = ap.parse_args()
    enable_compile_cache()

    names = args.only.split(",") if args.only else list(MODULES)
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        ap.error(f"unknown suite(s) {', '.join(sorted(unknown))}; "
                 f"available: {', '.join(MODULES)}")
    outdir = None
    if args.json is not None:
        outdir = pathlib.Path(args.json)
        outdir.mkdir(parents=True, exist_ok=True)

    print("name,us_per_call,derived")
    for name in names:
        rows = MODULES[name].run(quick=not args.full)
        emit(rows)
        if outdir is not None:
            payload = {r["name"]: r["us_per_call"] for r in rows}
            path = outdir / f"BENCH_{name}.json"
            path.write_text(json.dumps(payload, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
