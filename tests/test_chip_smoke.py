"""``chip_smoke.py`` off the chip: its phases at 64 px on the CPU (the
Pallas kernels in interpret mode), and its refusal to run without a TPU.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase,kwargs", [
    ("phase_erode", dict(dtype="uint8")),
    ("phase_erode", dict(dtype="float32")),
    ("phase_hmax", dict(continuous=False)),
    ("phase_hmax", dict(continuous=True)),
    ("phase_segment", dict(oracle_size=32)),
], ids=["erode-uint8", "erode-float32", "hmax-batch", "hmax-continuous",
        "segment"])
def test_phase_at_64px(smoke, phase, kwargs):
    rep = getattr(smoke, phase)(size=64, **kwargs)  # raises on a mismatch
    assert rep["chunks"] > 0
    # interpret mode lowers no Mosaic kernel; on the chip main() requires it
    assert rep["mosaic"] is False
    if kwargs.get("continuous"):
        assert rep["refills"] > 0


def test_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_four_chip_phase_on_virtual_devices():
    # the 2x2-mesh phase on four virtual CPU devices (XLA_FLAGS must be
    # set before JAX starts, hence the child process)
    code = ("import importlib.util, sys; "
            "spec = importlib.util.spec_from_file_location("
            "'chip_smoke', sys.argv[1]); "
            "m = importlib.util.module_from_spec(spec); "
            "spec.loader.exec_module(m); "
            "rep = m.phase_four_chips(size=128, n=9); "
            "print(rep['chunks'], rep['mosaic'])")
    out = subprocess.run(
        [sys.executable, "-c", code, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 PYTHONPATH=os.path.join(ROOT, "src")))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["1", "False"]
