"""Whole runs of ``bench/run.py`` on the CPU at a tiny size, with the look
for a chip skipped: a sound run is correct, and the control and each
fault that a cell can have, planted in the timed path, come out not
correct."""
import json

import jax.numpy as jnp
import pytest

from bench import control, run, spec
from repro.serve.executor import Executor

TINY = {
    "paper_frames_u8_1024": {"height": 64, "width": 128},
    "tissue_tiles_u8_2048": {"tile_px": 64, "nuclei": 12},
}


def tiny(monkeypatch, loop):
    """Shrink every configuration and cell; serve on the XLA backend
    (the Pallas interpreter is covered by test_bench_configs)."""
    real_config, real_cell = spec.config, spec.cell

    def config(name, bench=spec.BENCH):
        cfg, mod = real_config(name, bench)
        cfg = dict(cfg, **TINY[name],
                   service={"backend": "xla", "max_batch": 2})
        return cfg, mod

    def cell(wl, bench=spec.BENCH):
        c = dict(real_cell(wl, bench), pool=3, **loop)
        if c["op"] == "geodesic":
            c["params"] = dict(c["params"], n=min(c["params"]["n"], 40))
        return c

    monkeypatch.setattr(spec, "config", config)
    monkeypatch.setattr(spec, "cell", cell)
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    monkeypatch.setattr(run, "device_info", lambda chips, peaks: {
        "platform": "cpu", "kind": "cpu", "count": chips})


def run_once(capsys, workload, seed=2**31 + 7, seconds=0.5):
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0"]) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert "check mismatched_px" in out.err.strip().splitlines()[-2]
    return line


OPEN = {"loop": "open", "rate_hz": 20.0}
CLOSED = {"loop": "closed", "in_flight": 2}


@pytest.mark.parametrize("workload,loop", [
    ("chain1500.rate", OPEN), ("chain1500.rate", CLOSED),
    ("tissue.hmax", CLOSED)])
def test_sound_run_is_correct(monkeypatch, capsys, workload, loop):
    tiny(monkeypatch, loop)
    line = run_once(capsys, workload)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["checks"]["answers_compared"]["value"] >= 2
    names = {m["name"] for m in spec.metrics_for(
        spec.benchmark(), workload, False)}
    assert set(line["metrics"]) == names
    assert all(m["value"] > 0 for m in line["metrics"].values())


def _unchanged(real, entry, stacked):
    outputs, conv, util = real(entry, stacked)
    return (stacked[0],), conv, util


def _half_batch(real, entry, stacked):
    outputs, conv, util = real(entry, stacked)
    half = stacked[0].shape[0] // 2
    return (outputs[0].at[half:].set(stacked[0][half:]),), conv, util


def _altered(real, entry, stacked):
    outputs, conv, util = real(entry, stacked)
    o = outputs[0]
    return (o.at[0, 3, 5].set(o[0, 3, 5] ^ jnp.uint8(1)),), conv, util


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered])
@pytest.mark.parametrize("workload", ["chain1500.rate", "tissue.hmax"])
def test_fault_in_timed_path_is_not_correct(monkeypatch, capsys, workload,
                                            fault):
    tiny(monkeypatch, CLOSED)
    real = Executor._call_entry
    monkeypatch.setattr(Executor, "_call_entry", staticmethod(
        lambda entry, stacked: fault(real, entry, stacked)))
    line = run_once(capsys, workload)
    assert line["correct"] is False
    assert line["checks"]["mismatched_px"]["value"] > 0


@pytest.mark.parametrize("workload,loop", [
    ("chain1500.rate", OPEN), ("tissue.hmax", CLOSED)])
def test_control_is_not_correct(monkeypatch, capsys, workload, loop):
    tiny(monkeypatch, loop)
    wl = spec.workload(spec.benchmark(), workload)
    cell = spec.cell(wl)
    cfg, cmod = spec.config(wl["config"])
    with control.answer_with_control(cfg, cmod, cell["op"],
                                     cell.get("params") or {}):
        line = run_once(capsys, workload)
    assert line["correct"] is False
    assert line["checks"]["mismatched_px"]["value"] > 0


def test_metric_without_reading_prints_no_result(monkeypatch, capsys):
    """A metric declared for the cell whose reader finds nothing (a
    kernel not matched in the trace) fails the run, never drops out."""
    tiny(monkeypatch, CLOSED)
    real = spec.reader
    monkeypatch.setattr(spec, "reader", lambda name, bench=spec.BENCH: (
        (lambda _run: None) if name == "setup_s" else real(name, bench)))
    rc = run.main(["--workload", "tissue.hmax", "--seed", "3",
                   "--seconds", "0.5", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no reading for setup_s" in out.err


def test_per_layer_metric_without_reading_is_left_out(monkeypatch, capsys):
    """A traced run on the CPU: its trace has no TPU plane, so the
    readers of device numbers find nothing and their metrics are left
    out of the line, while the host's and the counters' metrics stay."""
    tiny(monkeypatch, OPEN)
    assert run.main(["--workload", "chain1500.rate", "--seed", "5",
                     "--seconds", "0.5", "--trace", "1"]) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "tail_latency_p95_ms", "gen_lag_p95_ms", "batch_fill_pct"}
    assert "no reading for launches_per_frame" in out.err
    assert "left out" in out.err
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_prints_no_result(monkeypatch, capsys):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    rc = run.main(["--workload", "chain1500.rate", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_sweep_reports_each_rate(monkeypatch, capsys):
    from bench import sweep

    tiny(monkeypatch, OPEN)
    assert sweep.main(["--workload", "chain1500.rate", "--seed", "5",
                       "--seconds", "0.3", "--rates", "10,30"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [r["rate_hz"] for r in line["rows"]] == [10.0, 30.0]
    assert all(r["compiles"] == 0 for r in line["rows"])
    assert line["knee_hz"] in (None, 10.0, 30.0)
