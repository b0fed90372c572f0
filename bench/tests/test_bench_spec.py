"""``BENCHMARK.json`` and the files it names: every name is well formed,
and every part of a cell is found by its name alone."""
import json
import os
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return spec.benchmark()


def test_top_level_keys(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert bm["command"][1] == "bench/run.py"
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51


def test_names_and_units(bm):
    names = [c["name"] for c in bm["configs"]]
    names += [w["name"] for w in bm["workloads"]]
    metrics = bm["end_to_end"] + bm["per_layer"]
    for w in bm["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in bm["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in metrics:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    assert len(set(c["name"] for c in bm["configs"])) == len(bm["configs"])
    assert len(set(w["name"] for w in bm["workloads"])) == len(
        bm["workloads"])
    assert len(set(m["name"] for m in metrics)) == len(metrics)


def test_bounds(bm):
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in bm["end_to_end"]} >= {"setup_s"}


def test_per_layer_moves_a_metric_its_cells_report(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["per_layer"]:
        moved = e2e[m["moves"]]
        assert m["layer"] and "\n" not in m["layer"]
        for w in m.get("workloads", moved.get("workloads", cells)):
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)


def test_every_cell_finds_its_parts_by_name(bm):
    for w in bm["workloads"]:
        cell = spec.cell(w)
        cfg, mod = spec.config(w["config"])
        assert cfg["name"] == w["config"]
        assert cell["op"] in mod.OPS
        assert cell["loop"] in ("open", "closed")
        for m in spec.metrics_for(bm, w["name"], False):
            assert callable(spec.reader(m["name"]))
        per_layer = spec.metrics_for(bm, w["name"], True)
        assert per_layer, w["name"]
        for m in per_layer:
            assert callable(spec.reader(m["name"]))
        assert "setup_s" in [m["name"] for m in
                             spec.metrics_for(bm, w["name"], False)]


def test_configs_file_matches_benchmark(bm):
    for c in bm["configs"]:
        cfg, _ = spec.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_unknown_names_are_errors(bm):
    with pytest.raises(spec.SpecError):
        spec.workload(bm, "no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.config("no_such_config")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_new_cell_is_found_without_edits(tmp_path, bm):
    """A cell added as files only: a traffic file and a metric reader in
    a copy of the benchmark directory are found by name."""
    bench = tmp_path / "bench"
    for sub in ("cells", "metrics"):
        (bench / sub).mkdir(parents=True)
    (bench / "cells" / "extra.json").write_text(json.dumps(
        {"config": "paper_frames_u8_1024", "op": "geodesic",
         "params": {"n": 64, "op": "dilate"}, "loop": "closed",
         "in_flight": 1, "pool": 2}))
    (bench / "metrics" / "extra_metric.py").write_text(
        "def read(run):\n    return 1.5\n")
    wl = {"name": "frames.extra", "config": "paper_frames_u8_1024",
          "traffic": "extra", "chips": 1}
    assert spec.cell(wl, str(bench))["params"]["n"] == 64
    assert spec.reader("extra_metric", str(bench))(None) == 1.5
    bad = dict(wl, config="tissue_tiles_u8_2048")
    with pytest.raises(spec.SpecError):
        spec.cell(bad, str(bench))
    assert os.path.exists(os.path.join(spec.BENCH, "peaks.json"))
