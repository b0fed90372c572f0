#!/usr/bin/env python3
"""Finds an open-loop cell's knee: the highest offered rate the served
path sustains.

    python3 bench/sweep.py --workload chain1500.rate --seed 7 \\
        --seconds 10 --rates 20,40,60,80

One process stages the cell once, then drives a fresh service through
one window per rate, lowest first.  For each rate it prints the offered
and answered rates, the latency median and 95th percentile, how many
requests were still unanswered when the window closed, and how late the
generator ran.  The knee is the highest rate whose requests were almost
all answered inside the window (``answered / sent`` at least
``--keep-up``) with a bounded tail; the cell's fixed rate is set from
it once, by hand, in ``bench/cells/<traffic>.json``.  The last line of
standard output is one JSON object with every row and the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--keep-up", type=float, default=0.97)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run, spec

    run.use_compile_cache()
    wl = spec.workload(spec.benchmark(), args.workload)
    cell = spec.cell(wl)
    if cell["loop"] != "open":
        raise spec.SpecError(f"{args.workload} is not an open loop")
    cfg, cmod = spec.config(wl["config"])
    try:
        device = run.device_info(wl["chips"], spec.peaks)
    except run.NoChip as e:
        print(f"bench/sweep.py: {e}; nothing was run", file=sys.stderr)
        return 2
    import jax

    from bench import load

    span = jax.profiler.TraceAnnotation
    st = run.stage(args.seed, cell, cfg, cmod, span)
    rows, knee = [], None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        r = run.window(st, args.seconds, span, rate_hz=rate)
        lat = load.latencies_ms(r.sent)
        answered = len(r.answered)
        row = {"rate_hz": rate, "sent": len(r.sent), "answered": answered,
               "answered_hz": answered / args.seconds,
               "unanswered_at_close": len(r.sent) - answered,
               "p50_ms": load.percentile(lat, 50),
               "p95_ms": load.percentile(lat, 95),
               "gen_lag_p95_ms": load.percentile(
                   [s.lag * 1e3 for s in r.sent], 95),
               "batch_fill_pct": 100 * r.stats["totals"]["batch_occupancy"],
               "compiles": r.compiles}
        rows.append(row)
        print(f"[{device['kind']} x{device['count']}] {json.dumps(row)}",
              file=sys.stderr, flush=True)
        if answered >= args.keep_up * len(r.sent):
            knee = rate
    print(json.dumps({"workload": args.workload, "device": device,
                      "seconds": args.seconds, "rows": rows,
                      "knee_hz": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
