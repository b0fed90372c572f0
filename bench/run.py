#!/usr/bin/env python3
"""Runs one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell is one ``workloads`` entry of ``BENCHMARK.json``: a deployment
(``bench/configs/<config>``) under a traffic mix
(``bench/cells/<traffic>.json``).  The run

1. makes the cell's input pool on the device from ``--seed`` and
   fetches it to the host once: requests arrive as host arrays;
2. builds ``repro.serve.Service`` with its defaults and warms every
   batch shape the traffic can form, through the whole served path;
3. drives a fresh service with the traffic for ``--seconds``
   (``bench.load``): nothing compiles in this window;
4. waits for every answer due, reads the device's peak memory, frees
   the program's state, and compares a sample of the answers with the
   plain reference (``bench.check``);
5. prints, as the last line of standard output, one JSON object with
   ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
   (and ``breakdown`` with ``--trace 1``), the numbers compared last.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window (``bench.trace``) and the service's counters.  Every metric is
computed by its reader in ``bench/metrics/<name>.py``.

The run fails, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is
kept in ``<checkout>/.jax_cache``, so only a checkout's first run of a
cell compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    cell: dict
    config: dict
    seconds: float
    setup_s: float
    t0: float
    t_end: float
    t_close: float       # the clock once the window's work was answered
    sent: list
    stats: dict
    compiles: int = 0
    trace: dict | None = None

    @property
    def answered(self) -> list:
        """Requests answered correctly inside the window."""
        return [s for s in self.sent
                if s.ok and s.ticket.t_done <= self.t_end]


def log(device: str, msg: str) -> None:
    print(f"[{device}] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, peaks) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks(devs[0].device_kind)  # an unknown device is an error
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def use_compile_cache() -> None:
    """Keep every compiled program, however small, in the checkout's
    persistent cache, through ``repro.core.compile_cache``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    from repro.core.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()


class CompileCounter:
    """Counts the programs JAX lowers (to compile them or to find them
    in the persistent cache) while armed.  JAX listeners cannot be
    removed, so the process has one counter."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax

            self = super().__new__(cls)
            self.armed, self.count = False, 0
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            cls._instance = self
        return cls._instance

    def _on_duration(self, event, _secs, **_):
        if self.armed and event == (
                "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.count += 1


def batch_sizes(max_batch: int, cell: dict) -> list[int]:
    """Every canonical batch the cell's loop can form: an open loop any
    number of queued requests, a closed loop at most ``in_flight``."""
    from repro.serve.bucketer import canonical_batch

    most = max_batch if cell["loop"] == "open" else cell["in_flight"]
    return sorted({canonical_batch(n, max_batch)
                   for n in range(1, most + 1)})


@dataclasses.dataclass
class Stage:
    """A cell's inputs on the host and its warmed service settings."""

    cell: dict
    config: dict
    pool: tuple          # per input, (pool, H, W) host arrays
    settings: dict       # Service keyword arguments (the defaults: {})
    rng: object          # the seed's generator, for the traffic

    def submit(self, svc, item: int):
        cell = self.cell
        return svc.submit(cell["op"], *(a[item] for a in self.pool),
                          params=cell.get("params"))


def stage(seed: int, cell: dict, cfg: dict, cmod, span) -> Stage:
    """Set-up before the window: the pool from the seed, made on the
    device in one call and fetched to the host once, and every batch
    shape the traffic can form compiled (or loaded) and run through the
    whole served path: prepare, stage, run, demux, finalize."""
    import jax
    import numpy as np

    from repro.serve import Service

    rng = np.random.default_rng(seed)
    key = jax.random.key(int(rng.integers(2**31)))
    with span("bench.stage_pool"):
        pool = tuple(np.asarray(a) for a in
                     cmod.make_pool(cfg, key, cell["pool"]))
    st = Stage(cell, cfg, pool, dict(cfg.get("service", {})), rng)
    warm = Service(**st.settings)
    sizes = batch_sizes(warm.max_batch, cell)
    warm.warmup([dict(op=cell["op"], shape=pool[0].shape[1:],
                      dtype=pool[0].dtype, params=cell.get("params"),
                      batch=b) for b in sizes])
    for b in sizes:
        tickets = [st.submit(warm, i % cell["pool"]) for i in range(b)]
        warm.flush()
        jax.block_until_ready([t.result() for t in tickets])
    return st


def window(st: Stage, seconds: float, span, *, trace: bool = False,
           rate_hz: float | None = None, sample=None) -> Run:
    """Drive a fresh service with the cell's traffic for ``seconds``,
    then wait for every answer due.  ``rate_hz`` overrides an open
    loop's rate (the knee sweep); ``sample`` sees every answer."""
    import numpy as np

    from bench import load
    from repro.serve import Service

    cell = st.cell
    svc = Service(**st.settings)
    if cell["loop"] == "open":
        due = load.open_gaps(rate_hz or cell["rate_hz"], seconds, st.rng)
        items = load.item_order(cell["pool"], len(due), st.rng)
    else:
        items = load.item_order(cell["pool"], 1 << 16, st.rng)

    def submit(i):
        item = int(items[i])
        return item, st.submit(svc, item)

    def on_sent(s):
        if sample is not None:
            s.ticket.add_done_callback(lambda _t, s=s: sample.offer(s))

    driver = load.Driver(svc, submit, int(np.prod(st.pool[0].shape[1:])),
                         span=span, on_sent=on_sent)
    counter = CompileCounter()
    if trace:
        import jax.profiler

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    counter.armed, counter.count = True, 0
    t0 = time.monotonic()
    t_end = t0 + seconds
    with span("bench.window"):
        if cell["loop"] == "open":
            driver.run_open(due, t0, t_end)
        else:
            driver.run_closed(cell["in_flight"], t_end)
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    driver.settle(t_end + load.LATE_S)
    t_close = t_end
    if cell["loop"] == "closed":
        # a closed loop sends nothing once the time is up and waits for
        # the requests it has in flight: its rate counts all of that work
        # over all of that time
        done = [s.ticket.t_done for s in driver.sent if s.done]
        t_close = max([t_end, *done] if len(done) == len(driver.sent)
                      else [time.monotonic()])
    return Run(cell=cell, config=st.config, seconds=seconds,
               setup_s=t0 - T_START, t0=t0, t_end=t_end, t_close=t_close,
               sent=driver.sent, stats=svc.stats(), compiles=counter.count)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    use_compile_cache()
    from bench import spec

    bm = spec.benchmark()
    wl = spec.workload(bm, args.workload)
    cell = spec.cell(wl)
    cfg, cmod = spec.config(wl["config"])
    metrics = spec.metrics_for(bm, wl["name"], bool(args.trace))
    readers = {m["name"]: spec.reader(m["name"]) for m in metrics}
    if cell["op"] not in cmod.OPS:
        raise spec.SpecError(f"{wl['config']} serves {cmod.OPS}, the cell "
                             f"asks for {cell['op']!r}")
    try:
        device = device_info(wl["chips"], spec.peaks)
    except NoChip as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    on_chip = f"{device['kind']} x{device['count']}"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check
    from bench import trace as T

    span = jax.profiler.TraceAnnotation
    log(on_chip, f"{wl['name']}: seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}, jax {jax.__version__}")

    st = stage(args.seed, cell, cfg, cmod, span)
    sample = check.Sample(np.random.default_rng([args.seed, 1]))
    run = window(st, args.seconds, span, trace=bool(args.trace),
                 sample=sample)
    log(on_chip, f"window {args.seconds} s: {len(run.sent)} sent, "
        f"{run.compiles} compiles inside it")
    dev0 = jax.devices()[0]
    device["memory_peak_bytes"] = int(
        (dev0.memory_stats() or {}).get("peak_bytes_in_use", 0))
    if args.trace:
        run.trace = T.reduce(T.read(TRACE_DIR), window="bench.window")
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    # the answers to compare, on the host; then free the program's state
    kept = [s for s in sample.kept if s.ok]
    answers = [np.asarray(s.ticket.value) for s in kept]
    for s in sample.kept:
        s.ticket.value = None
    gc.collect()
    expected = np.asarray(cmod.reference(
        cfg, cell["op"], cell.get("params") or {},
        tuple(jnp.asarray(a) for a in st.pool)))
    failed = sum(not s.ok for s in run.sent)
    correct, checks = check.verdict({
        "failed_requests": failed,
        "mismatched_px": check.mismatches(answers, [s.item for s in kept],
                                          expected),
        "answers_compared": len(answers),
    })

    values = {}
    for m in metrics:
        v = readers[m["name"]](run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for name, m in values.items():
        log(on_chip, f"{name} = {m['value']} {m['unit']}")
    missing = [m["name"] for m in metrics if m["name"] not in values]
    if missing and not args.trace:
        # the harness takes every end-to-end metric itself: one that
        # reads nothing is a fault of the harness, and the run has no
        # result
        log(on_chip, f"no reading for {', '.join(missing)}; no result")
        return 1
    if missing:
        # a per-layer reader that finds nothing to read returns nothing,
        # and its metric is left out of the line
        log(on_chip, f"no reading for {', '.join(missing)}; left out")
    out = {"correct": correct, "attempted": len(run.sent), "failed": failed,
           "metrics": values, "device": device}
    if args.trace:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['min']}")
        log(on_chip, f"check {name} = {c['value']} ({bound})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
