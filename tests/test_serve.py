"""repro.serve: bucketing/demux round-trips must be invisible in the
outputs (bit-exact vs direct operator calls, assert_array_equal), while
the metrics must show the machinery actually worked — batch occupancy,
deadline flushes, compiled-program cache hits and LRU eviction.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import api
from repro import gdt as G
from repro.core import morphology as M
from repro.core import operators as OPS
from repro.kernels import ops as K
from repro.serve import Service, registry
from repro.serve.bucketer import bucket_hw, canonical_batch, pad_fill

pytestmark = pytest.mark.serve


class FakeClock:
    """Deterministic time source for deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _image(rng, shape, dtype):
    if np.dtype(dtype).kind == "f":
        return rng.uniform(0.0, 1.0, shape).astype(dtype)
    return rng.integers(0, 255, shape).astype(dtype)


def _compiled(expr, *images):
    """An op with no eager entry point, compiled at the images' own
    (unpadded) shape on the xla backend."""
    return api.compile(expr, images[0].shape, images[0].dtype, "xla")(*images)


_REC = {"erode": M.erode_reconstruct, "dilate": M.dilate_reconstruct}
_GEO = {"erode": M.geodesic_erode, "dilate": M.geodesic_dilate}

#: Reference for every registered serve op: the op called directly on
#: the unpadded images on the xla backend, with the request's images as
#: positional arguments and its params as keywords.
DIRECT = {
    "erode": lambda f, s: K.erode(f, s, backend="xla"),
    "dilate": lambda f, s: K.dilate(f, s, backend="xla"),
    "opening": lambda f, s: K.opening(f, s, backend="xla"),
    "closing": lambda f, s: K.closing(f, s, backend="xla"),
    "reconstruct": lambda mk, m, op="dilate": _REC[op](mk, m),
    "geodesic": lambda mk, m, n, op="erode": _GEO[op](mk, m, n),
    "qdt": lambda f: OPS.qdt_raw(f),  # (d, r)
    "hmax": lambda f, h: OPS.hmax(f, h),
    "dome": lambda f, h: OPS.dome(f, h),
    "hfill": lambda f: OPS.hfill(f),
    "raobj": lambda f: OPS.raobj(f),
    "open_rec": lambda f, s: OPS.opening_by_reconstruction(f, s),
    "asf": lambda f, s: OPS.asf(f, s),
    "qdt_l1": lambda f: OPS.qdt(f),
    "gdt": lambda f, sd, lamb=1.0, nu=1e6: G.gdt(f, sd, lamb, nu),
    "seg_scribble": lambda f, sc, lamb=1.0, nu=1e6: _compiled(
        G.seg_scribble_expr(lamb, nu), f, sc),
    "seg_hmin": lambda f, h, lamb=1.0, nu=1e6: _compiled(
        G.seg_hmin_expr(h, lamb, nu), f),
}


def _direct(op, images, params):
    """Reference: the operator called directly on the unpadded images."""
    return DIRECT[op](*map(jnp.asarray, images), **params)


# ---------------------------------------------------------------------------
# acceptance: shuffled mixed-shape/dtype stream is bit-exact vs direct calls
# ---------------------------------------------------------------------------


def test_mixed_stream_bit_exact(rng):
    """A shuffled stream mixing shapes, dtypes, pad-safe and exact-shape
    ops must round-trip bit-exactly through bucketing, pad-to-bucket
    canonicalization, sentinel batch padding and the demux crop."""
    shapes = [(60, 90), (90, 60), (64, 96), (33, 47)]
    cases = []
    for i, shape in enumerate(shapes):
        for dtype in (np.uint8, np.float32):
            h = 40 if dtype == np.uint8 else 0.2
            f = _image(rng, shape, dtype)
            cases.append(("hmax", (f,), {"h": h}))
            cases.append(("hfill", (f,), {}))
            cases.append(("erode", (f,), {"s": 4}))
            cases.append(("asf", (f,), {"s": 2}))  # exact-shape bucket
    svc = Service(backend="xla", max_batch=4, max_delay_ms=1e9,
                  pad_quantum=32, clock=FakeClock())
    # two rounds in different shuffled orders: round 2 replays every
    # bucket, so the compiled-program cache must serve it from hits
    for round_ in range(2):
        order = rng.permutation(len(cases))
        tickets = [
            (i, svc.submit(cases[i][0], *cases[i][1], params=cases[i][2]))
            for i in order
        ]
        svc.flush()
        for i, t in tickets:
            op, images, params = cases[i]
            np.testing.assert_array_equal(
                np.asarray(t.result()),
                np.asarray(_direct(op, images, params)),
                err_msg=f"{op} on {images[0].shape} {images[0].dtype}")
    stats = svc.stats()
    assert stats["totals"]["requests"] == 2 * len(cases)
    # mixed shapes that quantize to one bucket must actually co-batch
    assert any(b["batch_occupancy"] > 0 and b["requests"] > 1
               for b in stats["buckets"].values())
    assert stats["cache"]["hit_rate"] > 0  # round 2 reuses programs


def test_pallas_backend_stream_exact(rng):
    """Serving through the Pallas fast path (the shared active-band
    scheduler) with shapes that share one padded bucket."""
    f1 = _image(rng, (60, 90), np.uint8)
    f2 = _image(rng, (64, 96), np.uint8)
    svc = Service(backend="pallas", max_batch=2, max_delay_ms=1e9,
                  pad_quantum=32, clock=FakeClock())
    t1 = svc.submit("hmax", f1, params={"h": 40})
    t2 = svc.submit("hmax", f2, params={"h": 40})
    svc.flush()
    assert svc.stats()["totals"]["batches"] == 1  # co-batched in one bucket
    np.testing.assert_array_equal(
        np.asarray(t1.result()), np.asarray(OPS.hmax(jnp.asarray(f1), 40)))
    np.testing.assert_array_equal(
        np.asarray(t2.result()), np.asarray(OPS.hmax(jnp.asarray(f2), 40)))


def test_arity2_and_multi_output(rng):
    """reconstruct (two inputs) and qdt (two outputs) round-trip."""
    mask = _image(rng, (48, 64), np.uint8)
    marker = np.minimum(_image(rng, (48, 64), np.uint8), mask)
    f = _image(rng, (40, 56), np.uint8)
    svc = Service(backend="xla", max_batch=2, max_delay_ms=1e9,
                  pad_quantum=32, clock=FakeClock())
    tr = svc.submit("reconstruct", marker, mask, params={"op": "dilate"})
    tq = svc.submit("qdt", f)
    svc.flush()
    np.testing.assert_array_equal(
        np.asarray(tr.result()),
        np.asarray(M.dilate_reconstruct(jnp.asarray(marker),
                                        jnp.asarray(mask))))
    d, r = tq.result()
    dw, rw = OPS.qdt_raw(jnp.asarray(f))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dw))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rw))


# ---------------------------------------------------------------------------
# bucketer: deadline flush, occupancy, sentinel padding
# ---------------------------------------------------------------------------


def test_deadline_flush(rng):
    """A straggler request never waits more than max_delay_ms."""
    clock = FakeClock()
    svc = Service(backend="xla", max_batch=4, max_delay_ms=5.0,
                  pad_quantum=32, clock=clock)
    f = _image(rng, (32, 32), np.uint8)
    t = svc.submit("erode", f, params={"s": 3})
    assert svc.pending() == 1 and not t.done  # under deadline: queued
    clock.advance(0.004)
    svc.poll()
    assert svc.pending() == 1  # 4ms < 5ms: still queued
    clock.advance(0.002)
    svc.poll()  # 6ms: deadline exceeded -> launched
    assert svc.pending() == 0
    svc.flush()
    assert t.done
    np.testing.assert_array_equal(
        np.asarray(t.result()),
        np.asarray(K.erode(jnp.asarray(f), 3, backend="xla")))


def test_batch_occupancy_and_sentinels(rng):
    """3 requests into a max_batch=4 bucket: batch padded to the
    canonical size with sentinel slots, occupancy reported as 3/4."""
    clock = FakeClock()
    svc = Service(backend="xla", max_batch=4, max_delay_ms=1e9,
                  pad_quantum=32, clock=clock)
    frames = [_image(rng, (30, 40), np.uint8) for _ in range(3)]
    tickets = [svc.submit("dilate", f, params={"s": 3}) for f in frames]
    svc.flush()
    for f, t in zip(frames, tickets):
        np.testing.assert_array_equal(
            np.asarray(t.result()),
            np.asarray(K.dilate(jnp.asarray(f), 3, backend="xla")))
    (bucket,) = svc.stats()["buckets"].values()
    assert bucket["requests"] == 3
    assert bucket["batches"] == 1
    assert bucket["batch_occupancy"] == pytest.approx(0.75)


def test_full_bucket_launches_immediately(rng):
    clock = FakeClock()
    svc = Service(backend="xla", max_batch=2, max_delay_ms=1e9,
                  pad_quantum=32, clock=clock)
    f = _image(rng, (16, 16), np.uint8)
    svc.submit("erode", f, params={"s": 2})
    assert svc.pending() == 1
    svc.submit("erode", f, params={"s": 2})
    assert svc.pending() == 0  # bucket filled -> launched without a poll


def test_ticket_result_drives_pipeline(rng):
    """Ticket.result() on a queued request completes it without an
    explicit flush()."""
    svc = Service(backend="xla", max_batch=8, max_delay_ms=1e9,
                  pad_quantum=32, clock=FakeClock())
    f = _image(rng, (24, 24), np.uint8)
    t = svc.submit("erode", f, params={"s": 2})
    np.testing.assert_array_equal(
        np.asarray(t.result()),
        np.asarray(K.erode(jnp.asarray(f), 2, backend="xla")))


def test_bucket_helpers():
    assert bucket_hw(60, 90, 32) == (64, 96)
    assert bucket_hw(64, 96, 32) == (64, 96)
    assert canonical_batch(1, 8) == 1
    assert canonical_batch(3, 8) == 4
    assert canonical_batch(5, 4) == 4
    assert canonical_batch(3, 3) == 3  # cap wins over power-of-two rounding
    assert pad_fill(np.uint8, "hi") == 255
    assert pad_fill(np.uint8, "lo") == 0
    assert np.isinf(pad_fill(np.float32, "hi"))


# ---------------------------------------------------------------------------
# compiled-program cache: hits, warm-up prefill, LRU eviction
# ---------------------------------------------------------------------------


def test_cache_hits_and_plan(rng):
    clock = FakeClock()
    svc = Service(backend="pallas", max_batch=1, max_delay_ms=1e9,
                  pad_quantum=32, clock=clock)
    f = _image(rng, (40, 60), np.uint8)
    for _ in range(3):
        svc.submit("erode", f, params={"s": 4})
    svc.flush()
    stats = svc.stats()["cache"]
    assert stats["misses"] == 1 and stats["hits"] == 2
    # the cached entry embeds the ChainPlan the program compiled against
    (entry,) = svc.cache.entries()
    assert entry.plan is not None and entry.plan.key[2] >= 64  # width_pad


def test_cache_warmup_prefill(rng):
    svc = Service(backend="xla", max_batch=2, max_delay_ms=1e9,
                  pad_quantum=32, clock=FakeClock())
    svc.warmup([{"op": "erode", "params": {"s": 4}, "shape": (40, 60),
                 "dtype": np.uint8, "batch": 2}])
    assert svc.cache.stats()["warm_builds"] == 1
    f1, f2 = (_image(rng, (40, 60), np.uint8) for _ in range(2))
    t1 = svc.submit("erode", f1, params={"s": 4})
    t2 = svc.submit("erode", f2, params={"s": 4})
    svc.flush()
    t1.result(), t2.result()
    stats = svc.cache.stats()
    assert stats["misses"] == 0 and stats["hits"] == 1  # warm hit only


def test_cache_lru_eviction(rng):
    """Eviction follows recency of *use*, not insertion: touching A
    before inserting C must evict B, and A must stay resident."""
    clock = FakeClock()
    svc = Service(backend="xla", max_batch=1, max_delay_ms=1e9,
                  pad_quantum=16, cache_capacity=2, clock=clock)
    A, B, C = (16, 16), (32, 32), (48, 48)

    def hit(shape):
        svc.submit("erode", _image(rng, shape, np.uint8), params={"s": 2})

    hit(A)   # miss, insert A
    hit(B)   # miss, insert B
    hit(A)   # hit: A becomes most-recently-used
    hit(C)   # miss: evicts B (LRU), not A
    hit(A)   # hit: A survived the eviction
    svc.flush()
    stats = svc.cache.stats()
    assert stats["entries"] == 2
    assert stats["misses"] == 3
    assert stats["hits"] == 2
    assert stats["evictions"] == 1


def test_dispatch_failure_resolves_tickets(rng):
    """A program that fails at dispatch must resolve every co-batched
    ticket with a *typed* error instead of stranding them — and the
    failure must not propagate out of submit/poll (the PR 7 robustness
    contract; the recovery ladder itself is covered in
    tests/test_faults.py)."""
    from repro.serve.errors import PoisonedRequestError
    from repro.serve.registry import OpSpec, _REGISTRY, register

    def bad_run(inputs, params, backend, plan):
        raise RuntimeError("boom")

    register(OpSpec(name="_boom_test", params={}, run=bad_run))
    try:
        svc = Service(backend="xla", max_batch=2, max_delay_ms=1e9,
                      pad_quantum=16, clock=FakeClock(), max_retries=1)
        t1 = svc.submit("_boom_test", _image(rng, (8, 8), np.uint8))
        # fills the bucket -> launch -> trace raises inside dispatch;
        # the recovery ladder resolves both tickets, nothing escapes
        t2 = svc.submit("_boom_test", _image(rng, (8, 8), np.uint8))
        for t in (t1, t2):
            assert t.done and t.error is not None
            assert t.outcome == "poisoned"
            with pytest.raises(PoisonedRequestError, match="poisoned"):
                t.result()
            assert isinstance(t.error.cause, RuntimeError)  # boom preserved
        counters = svc.stats()["counters"]
        assert counters["batch_failures"] >= 1
        assert counters["retried"] >= 1
        assert counters["poisoned"] == 2
    finally:
        _REGISTRY.pop("_boom_test", None)


def test_optimizer_counters(rng):
    """The optimizer counters: ``rewrites_applied`` counts rule
    applications behind admitted requests (ASF's adjacent dilate
    chains merge), ``programs_shared`` fires when a distinct source
    graph joins an already-compiled program identity (HMAX and DOME
    are one dilate-reconstruction)."""
    svc = Service(backend="xla", max_batch=1, max_delay_ms=1e9,
                  pad_quantum=16, clock=FakeClock())
    f = _image(rng, (24, 24), np.uint8)
    t = svc.submit("asf", f, params={"s": 1})
    svc.flush()
    np.testing.assert_array_equal(
        np.asarray(t.result()),
        np.asarray(_direct("asf", (f,), {"s": 1})))
    counters = svc.stats()["counters"]
    assert counters["rewrites_applied"] >= 1
    assert counters["programs_shared"] == 0
    t1 = svc.submit("hmax", f, params={"h": 40})
    svc.flush()
    t2 = svc.submit("dome", f, params={"h": 40})
    svc.flush()
    assert t1.done and t2.done
    assert svc.stats()["counters"]["programs_shared"] == 1


# ---------------------------------------------------------------------------
# registry: schema-as-data validation
# ---------------------------------------------------------------------------


def test_registry_lists_hooked_ops():
    names = registry.names()
    for expected in ("hmax", "dome", "hfill", "raobj", "open_rec", "asf",
                     "erode", "dilate", "opening", "closing", "reconstruct",
                     "geodesic", "qdt", "qdt_l1"):
        assert expected in names


def test_registry_param_validation(rng):
    svc = Service(backend="xla", clock=FakeClock())
    f = _image(rng, (16, 16), np.uint8)
    with pytest.raises(KeyError, match="unknown op"):
        svc.submit("nope", f)
    with pytest.raises(ValueError, match="missing required param"):
        svc.submit("hmax", f)
    with pytest.raises(ValueError, match="unknown params"):
        svc.submit("hfill", f, params={"x": 1})
    with pytest.raises(ValueError, match="must be one of"):
        svc.submit("reconstruct", f, f, params={"op": "median"})
    with pytest.raises(ValueError, match="must be >="):
        svc.submit("erode", f, params={"s": 0})
    with pytest.raises(ValueError, match="takes 2 image"):
        svc.submit("reconstruct", f, params={"op": "dilate"})
    # params canonicalize to a stable hashable key (int h coerces float)
    spec = registry.get("hmax")
    assert spec.canonical_params({"h": 40}) == (("h", 40.0),)
