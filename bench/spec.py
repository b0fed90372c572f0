"""Finds every part of the benchmark by its name in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix;
each lives in files of its own, so that a new cell, configuration or
metric is a new file and never an edit:

``bench/configs/<config>.json``   the deployment's sizes and source
``bench/configs/<config>.py``     its input pool and plain reference
``bench/cells/<traffic>.json``    the traffic mix: op, loop, rate
``bench/metrics/<metric>.py``     one reader per metric
``bench/peaks.json``              published peaks by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(ValueError):
    """A name that the benchmark's files do not define, or define
    inconsistently."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json (have "
                    f"{', '.join(w['name'] for w in bm['workloads'])})")


def cell(wl: dict, bench: str = BENCH) -> dict:
    """The traffic file of workload ``wl``; it must name the same
    configuration."""
    c = _json(os.path.join(bench, "cells", wl["traffic"] + ".json"))
    if c.get("config", wl["config"]) != wl["config"]:
        raise SpecError(f"cells/{wl['traffic']}.json is for configuration "
                        f"{c['config']!r}, the workload for {wl['config']!r}")
    return c


def config(name: str, bench: str = BENCH):
    """``(sizes, module)`` of configuration ``name``."""
    cfg = _json(os.path.join(bench, "configs", name + ".json"))
    mod = _module(os.path.join(bench, "configs", name + ".py"), name)
    return cfg, mod


def reader(metric: str, bench: str = BENCH):
    """The ``read(run)`` function of metric ``metric``."""
    mod = _module(os.path.join(bench, "metrics", metric + ".py"), metric)
    return mod.read


def metrics_for(bm: dict, wl_name: str, trace: bool) -> list[dict]:
    """The metrics a run of ``wl_name`` reports: the end-to-end ones
    without the trace, the per-layer ones with it.  A metric without a
    ``workloads`` list belongs to every cell that reports the end-to-end
    metric it moves (end-to-end metrics: to every cell)."""
    e2e = {m["name"]: m for m in bm["end_to_end"]}

    def has(m):
        if "workloads" in m:
            return wl_name in m["workloads"]
        if "moves" in m:
            return has(e2e[m["moves"]])
        return True

    return [m for m in bm["per_layer" if trace else "end_to_end"] if has(m)]


def peaks(device_kind: str, bench: str = BENCH) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = _json(os.path.join(bench, "peaks.json"))
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json") from None
