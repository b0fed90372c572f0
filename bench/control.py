#!/usr/bin/env python3
"""The control of ``correct``: the plain reference in the program's
place, computed a precision step below the configuration's.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 5

For each seed it runs the cell as ``bench/run.py`` does, through a short
window at the cell's own load, but every answer the service delivers is
replaced, where it is produced, by the configuration's reference
computed on inputs cut to their top 4 bits (int4 below the configured
uint8).  A sound comparison must call every such run incorrect.  The
last line of standard output is one JSON object: per seed, the numbers
compared and ``correct``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The control's precision: int4 below the configurations' uint8.
KEEP_BITS = 4


def answer_with_control(cfg: dict, cmod, op: str, params: dict,
                        keep_bits: int = KEEP_BITS):
    """Patch the service's demux so that each request's answer is the
    reference at ``keep_bits`` on that request's own images; returns the
    context manager that undoes it."""
    import jax.numpy as jnp

    from repro.serve.executor import Executor

    real = Executor._demux

    def demux(self, key, requests, n_slots, outputs, converged, t_dispatch,
              util=None):
        shape = outputs[0].shape[1:]
        rows = []
        for req in requests:
            imgs = tuple(jnp.asarray(im)[None] for im in req.images)
            ans = cmod.reference(cfg, op, params, imgs, keep_bits)[0]
            h, w = ans.shape
            rows.append(jnp.pad(ans, ((0, shape[0] - h), (0, shape[1] - w))))
        rows += [jnp.zeros(shape, outputs[0].dtype)] * (n_slots - len(rows))
        return real(self, key, requests, n_slots, (jnp.stack(rows),),
                    converged, t_dispatch, util=util)

    @contextlib.contextmanager
    def patched():
        Executor._demux = demux
        try:
            yield
        finally:
            Executor._demux = real

    return patched()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run, spec

    run.use_compile_cache()
    wl = spec.workload(spec.benchmark(), args.workload)
    cell = spec.cell(wl)
    cfg, cmod = spec.config(wl["config"])
    results = {}
    with answer_with_control(cfg, cmod, cell["op"], cell.get("params") or {}):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = run.main(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds)])
            if rc:
                return rc
            line = json.loads(out.getvalue().strip().splitlines()[-1])
            results[seed] = {"correct": line["correct"],
                             "checks": line["checks"],
                             "device": line["device"]["kind"]}
            print(f"[{line['device']['kind']}] control seed {seed}: "
                  f"{line['checks']}", file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "keep_bits": KEEP_BITS,
                      "runs": results}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
