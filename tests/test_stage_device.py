"""Staging builds each batch stack on the device, where prepare left the
inputs: the stack must equal, byte for byte, the host construction it
replaces (``np.full`` with the absorbing fill, then each request's plane
copied into the top-left of its slot), on every path that stages — the
batch launch, sentinel slots, the all-sentinel warm-up, the continuous
engine's one-slot staging and a recovery re-run — and a served batch
reads nothing back to the host before its demux."""
import jax
import numpy as np
import pytest

from repro.serve import Service
from repro.serve import faults as F
from repro.serve.bucketer import pad_fill, stage_planes
from repro.serve.continuous import SlotEngine

pytestmark = pytest.mark.serve


def _host_stack(planes, n_slots, hw, dtype, which):
    """The host construction the device staging replaces."""
    buf = np.full((n_slots, *hw), pad_fill(dtype, which), dtype)
    for i, p in enumerate(planes):
        p = np.asarray(p)
        buf[i, :p.shape[0], :p.shape[1]] = p
    return buf


def _image(rng, shape, dtype):
    if np.dtype(dtype).kind == "f":
        return rng.uniform(0.0, 1.0, shape).astype(dtype)
    return rng.integers(0, 200, shape).astype(dtype)


def _record(monkeypatch, cls, name):
    """Wrap ``cls.name`` so every call's arguments and result are kept."""
    calls = []
    real = getattr(cls, name)

    def spy(self, *args, **kw):
        out = real(self, *args, **kw)
        calls.append((self, args, out))
        return out

    monkeypatch.setattr(cls, name, spy)
    return calls


def _service_stacks(calls):
    """(device stack, host construction) per input of each recorded
    ``Service._stage`` call."""
    pairs = []
    for _svc, (info, key, requests, n_slots, *_), out in calls:
        for j, got in enumerate(out):
            want = _host_stack([r.inputs[j] for r in requests], n_slots,
                               key.hw, np.dtype(key.dtype), info.fills[j])
            pairs.append((got, want))
    return pairs


def _helper(dtype, which, shapes, n_slots):
    def case(rng, monkeypatch):
        planes = [jax.numpy.asarray(_image(rng, s, dtype)) for s in shapes]
        got = stage_planes(planes, n_slots, (64, 64), dtype, which)
        return [(got, _host_stack(planes, n_slots, (64, 64), dtype,
                                  which))]
    return case


def _served(n_requests, max_batch=4, shapes=((40, 56),), faults="",
            op="hmax", params=(("h", 40),)):
    def case(rng, monkeypatch):
        calls = _record(monkeypatch, Service, "_stage")
        svc = Service(backend="xla", max_batch=max_batch,
                      max_delay_ms=1e9, pad_quantum=64,
                      faults=F.parse(faults), sleep=lambda s: None)
        tickets = [svc.submit(op, _image(rng, shapes[i % len(shapes)],
                                         np.uint8), params=dict(params))
                   for i in range(n_requests)]
        svc.flush()
        assert all(t.outcome == "ok" for t in tickets)
        if faults:
            assert svc.stats()["counters"]["retried"] == 1
            assert len(calls) == 2  # the launch and its re-run
        return _service_stacks(calls)
    return case


def _warmup(rng, monkeypatch):
    calls = _record(monkeypatch, Service, "_stage")
    svc = Service(backend="xla", max_batch=4)
    svc.warmup([dict(op="hmax", shape=(40, 56), dtype=np.uint8,
                     params={"h": 40}, batch=4)])
    assert [len(args[2]) for _, args, _ in calls] == [0]
    return _service_stacks(calls)


def _continuous(rng, monkeypatch):
    calls = _record(monkeypatch, SlotEngine, "_staged")
    svc = Service(backend="pallas", continuous=True, max_batch=2,
                  max_delay_ms=1e9, pad_quantum=16, refill_quantum=2)
    shapes = ((16, 24), (12, 32), (16, 32))
    tickets = [svc.submit("hmax", _image(rng, s, np.uint8),
                          params={"h": 40}) for s in shapes]
    svc.flush()
    assert all(t.outcome == "ok" for t in tickets)
    assert len(calls) == len(shapes)
    pairs = []
    for engine, (req, _), out in calls:
        for j, got in enumerate(out):
            want = _host_stack([req.inputs[j]], 1, engine.key.hw,
                               np.dtype(engine.key.dtype),
                               engine.info.fills[j])[0]
            pairs.append((got, want))
    return pairs


CASES = {
    "u8": _helper(np.uint8, "hi", [(64, 64), (40, 56)], 2),
    "u16": _helper(np.uint16, "lo", [(33, 47), (64, 64)], 2),
    "f32_inf_hi": _helper(np.float32, "hi", [(60, 9), (1, 64)], 2),
    "f32_inf_lo": _helper(np.float32, "lo", [(60, 9), (64, 1)], 4),
    # fill-holes pads with the lattice top, hmax with its bottom
    "ragged": _served(3, max_batch=4, shapes=((40, 56), (64, 64), (33, 17)),
                      op="hfill", params=()),
    "sentinels": _served(3, max_batch=4),
    "warmup": _warmup,
    "continuous": _continuous,
    "recovery": _served(2, max_batch=2, faults="dispatch:n=1"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_device_stack_equals_host_stack(rng, monkeypatch, case):
    pairs = CASES[case](rng, monkeypatch)
    assert pairs
    for got, want in pairs:
        assert isinstance(got, jax.Array)
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def test_served_hmax_reads_nothing_back_before_demux(rng):
    """A batch of hmax tiles puts each image on the device once and
    reads no byte of it back before its demux: the images' bytes up,
    nothing down."""
    svc = Service(backend="xla", max_batch=2, max_delay_ms=1e9)
    images = [_image(rng, (64, 64), np.uint8) for _ in range(2)]
    tickets = [svc.submit("hmax", im, params={"h": 40}) for im in images]
    svc.flush()
    assert all(t.outcome == "ok" for t in tickets)
    totals = svc.stats()["totals"]
    assert totals["d2h_bytes"] == 0
    assert totals["h2d_bytes"] == sum(im.nbytes for im in images)
    assert totals["pixels"] == sum(im.size for im in images)
