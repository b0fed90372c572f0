"""The event-driven serving engine under the deterministic virtual
clock (PR 9): timer-driven flushes with no caller, deadline expiry as
timers (including the expiry-during-compile race), continuous slot
refill bit-exactness, time-weighted occupancy accounting, load
shedding, close semantics, adaptive pad-quantum — plus the in-process
flake detector (one scenario replayed twice must produce identical
counters).

Bit-exactness is the anchor invariant: a request served from a
refilled slot (admitted mid-flight while other slots iterate) must
produce *exactly* the bytes a solo execution produces.
"""
import asyncio

import numpy as np
import jax.numpy as jnp
import pytest
from numpy.testing import assert_array_equal

from serve_sim import SimHarness, selftest_scenario
from repro.core import operators as OPS
from repro.kernels import ops as K
from repro.serve import (AsyncService, Service, ServiceClosedError,
                         VirtualClock)
from repro.serve.errors import (DeadlineExceededError, ExecutorError,
                                QueueFullError)
from repro.serve.loop import EventLoop
from repro.serve.metrics import ServeMetrics

pytestmark = pytest.mark.serve


@pytest.fixture
def rng():
    return np.random.default_rng(1702)


def _image(rng, shape=(16, 16), dtype=np.uint8):
    return rng.integers(0, 255, shape).astype(dtype)


def _recon_pair(rng, shape=(32, 32), slow=False):
    """(marker, mask) for ``reconstruct``; ``slow=True`` builds a
    serpentine mask so the propagation front must walk most of the
    image — many scheduler chunks, the straggler the continuous engine
    exists for."""
    h, w = shape
    if slow:
        f = np.full(shape, 0.1, np.float32)
        for r in range(0, h, 2):
            f[r, :] = 0.9
            if r + 1 < h:
                f[r + 1, -1 if (r // 2) % 2 == 0 else 0] = 0.9
        m = np.full(shape, 0.05, np.float32)
        m[0, 0] = 0.8
    else:
        f = rng.random(shape).astype(np.float32)
        m = (0.9 * f).astype(np.float32)
    return np.minimum(m, f), f


# ---------------------------------------------------------------------------
# the event loop itself
# ---------------------------------------------------------------------------


def test_event_loop_fires_in_when_seq_order():
    clk = VirtualClock()
    loop = EventLoop(clk)
    fired = []
    loop.call_at(2.0, lambda: fired.append("late"))
    loop.call_at(1.0, lambda: fired.append("a"))
    loop.call_at(1.0, lambda: fired.append("b"))  # same instant: arm order
    h = loop.call_at(1.5, lambda: fired.append("cancelled"))
    h.cancel()
    assert loop.run_due() == 0 and fired == []  # nothing due at t=0
    clk.advance(1.2)
    assert loop.run_due() == 2 and fired == ["a", "b"]
    assert loop.next_deadline() == 2.0
    clk.advance(1.0)
    loop.run_due()
    assert fired == ["a", "b", "late"] and loop.pending() == 0


def test_event_loop_cancel_mid_firing():
    """A due callback cancelling a later due timer suppresses it."""
    clk = VirtualClock()
    loop = EventLoop(clk)
    fired = []
    handles = {}
    handles["b"] = loop.call_at(1.0, lambda: fired.append("b"))

    def cancel_b():
        fired.append("a")
        handles["b"].cancel()

    loop.call_at(0.5, cancel_b)
    clk.advance(2.0)
    loop.run_due()
    assert fired == ["a"]


def test_virtual_clock_monotonic():
    clk = VirtualClock(5.0)
    assert clk() == 5.0
    clk.advance(1.5)
    assert clk() == 6.5
    with pytest.raises(ValueError):
        clk.advance(-0.1)


# ---------------------------------------------------------------------------
# timer-driven flush: the deadline flush fires from a timer, not a caller
# ---------------------------------------------------------------------------


def test_flush_timer_launches_without_flush_call(rng):
    clk = VirtualClock()
    svc = Service(backend="xla", max_batch=4, max_delay_ms=5.0,
                  pad_quantum=16, clock=clk)
    im = _image(rng)
    t = svc.submit("hfill", im)
    assert not t.done and svc.pending() == 1
    clk.advance(0.003)
    svc.pump()
    assert svc.pending() == 1  # 3ms < 5ms: timer not due yet
    clk.advance(0.003)
    svc.pump()                 # flush timer fires → bucket launches
    assert svc.pending() == 0
    while svc.work_pending():
        svc.pump()
    assert t.done and t.outcome == "ok"
    assert_array_equal(np.asarray(t.result()),
                       np.asarray(OPS.hfill(jnp.asarray(im))))


def test_asyncio_flush_fires_with_no_caller(rng):
    """The tentpole property: under AsyncService, a lone sub-batch
    request completes from the loop's own timer wakeups — no poll(),
    no flush(), no result() driving it."""
    im = _image(rng)

    async def main():
        svc = AsyncService(backend="xla", max_batch=8, max_delay_ms=5.0,
                           pad_quantum=16)
        t = svc.submit("hfill", im)
        deadline = asyncio.get_running_loop().time() + 30.0
        while not t.done:  # only sleeping — never pumping the service
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.01)
        await svc.close()
        return t

    t = asyncio.run(main())
    assert t.outcome == "ok"
    assert_array_equal(np.asarray(t.value),
                       np.asarray(OPS.hfill(jnp.asarray(im))))


def test_async_result_and_close(rng):
    im = _image(rng)

    async def main():
        svc = AsyncService(backend="xla", max_batch=8, max_delay_ms=2.0,
                           pad_quantum=16)
        val = await svc.run("hfill", im)
        await svc.close()
        with pytest.raises(ServiceClosedError):
            svc.submit("hfill", im)
        return val

    val = asyncio.run(main())
    assert_array_equal(np.asarray(val),
                       np.asarray(OPS.hfill(jnp.asarray(im))))


# ---------------------------------------------------------------------------
# deadline expiry as timers
# ---------------------------------------------------------------------------


def test_deadline_expiry_ordering(rng):
    """Two queued deadlines expire in deadline order, each the moment
    its timer fires — not in a burst at the next poll."""
    clk = VirtualClock()
    svc = Service(backend="xla", max_batch=8, max_delay_ms=1e9,
                  pad_quantum=16, clock=clk)
    ta = svc.submit("hfill", _image(rng), deadline_ms=10.0)
    tb = svc.submit("hfill", _image(rng), deadline_ms=30.0)
    clk.advance(0.015)
    svc.pump()
    assert ta.done and ta.outcome == "deadline" and not tb.done
    clk.advance(0.025)
    svc.pump()
    assert tb.outcome == "deadline"
    assert ta.t_done < tb.t_done
    with pytest.raises(DeadlineExceededError):
        ta.result()
    assert svc.stats()["counters"]["expired"] == 2
    assert svc.pending() == 0 and not svc.work_pending()


def test_expiry_during_compile_not_dispatched(rng, monkeypatch):
    """Regression for the launch/deadline race: previously expiry was
    only checked in poll() *before* staging, so a request whose
    deadline lapsed during a long trace/compile was still dispatched.
    Now launch re-checks after compiling."""
    clk = VirtualClock()
    svc = Service(backend="xla", max_batch=1, max_delay_ms=1e9,
                  pad_quantum=16, clock=clk)
    real_entry_for = svc._entry_for

    def slow_entry_for(*a, **kw):
        clk.advance(0.05)  # "compile" takes 50ms
        return real_entry_for(*a, **kw)

    monkeypatch.setattr(svc, "_entry_for", slow_entry_for)
    t = svc.submit("hfill", _image(rng), deadline_ms=10.0)
    # max_batch=1 → submit launched inline; the deadline lapsed inside
    # the compile, and the post-compile re-check must have shed it
    assert t.done and t.outcome == "deadline"
    assert svc.stats()["counters"]["expired"] == 1
    assert svc.stats()["totals"]["requests"] == 0  # nothing dispatched


def test_expired_request_keeps_bucket_flush_armed(rng):
    """Expiry of the bucket's oldest re-arms the flush timer for the
    new oldest instead of dropping it."""
    clk = VirtualClock()
    svc = Service(backend="xla", max_batch=8, max_delay_ms=50.0,
                  pad_quantum=16, clock=clk)
    ta = svc.submit("hfill", _image(rng), deadline_ms=10.0)
    clk.advance(0.005)
    tb = svc.submit("hfill", _image(rng))  # no deadline
    clk.advance(0.010)
    svc.pump()  # ta expires; tb must still be flush-scheduled
    assert ta.outcome == "deadline" and not tb.done
    clk.advance(0.045)  # past tb's max_delay
    svc.pump()
    while svc.work_pending():
        svc.pump()
    assert tb.outcome == "ok"


# ---------------------------------------------------------------------------
# continuous batching: slot refill
# ---------------------------------------------------------------------------


def test_continuous_refill_bit_exact(rng):
    """The tentpole invariant: requests admitted into slots freed
    mid-flight (a serpentine straggler keeps the session alive)
    complete bit-exactly vs the direct operator call, and the refills
    counter proves mid-flight admission actually happened."""
    clk = VirtualClock()
    svc = Service(continuous=True, max_batch=4, refill_quantum=1,
                  max_delay_ms=1.0, pad_quantum=16, clock=clk)
    cases = [_recon_pair(rng, slow=True)] + [_recon_pair(rng)
                                             for _ in range(3)]
    tickets = [svc.submit("reconstruct", m, f) for m, f in cases]
    clk.advance(0.002)
    svc.poll()  # flush timer → engine spawned, first wave admitted
    eng = next(iter(svc._engines.values()))
    assert eng.occupied
    # second wave arrives while the straggler is resident
    for _ in range(6):
        m, f = _recon_pair(rng)
        cases.append((m, f))
        tickets.append(svc.submit("reconstruct", m, f))
        svc.poll()  # one engine round per arrival: fast slots free up
    for _ in range(2000):
        if all(t.done for t in tickets):
            break
        clk.advance(0.001)
        svc.poll()
    assert all(t.done for t in tickets)
    assert svc.stats()["counters"]["refills"] > 0
    for (m, f), t in zip(cases, tickets):
        assert t.outcome == "ok"
        ref = np.asarray(K.reconstruct(m, f, op="dilate"))
        assert_array_equal(np.asarray(t.result()), ref)


def test_continuous_compile_failure_surfaces(rng, monkeypatch):
    """A refillable bucket whose program fails to build fails its
    tickets with a typed error carrying the compile error — no quiet
    batch-path fallback, nothing raised out of the timer, nothing left
    queued — and another bucket's flush timer due at the same instant
    still fires."""
    clk = VirtualClock()
    svc = Service(continuous=True, max_batch=4, max_delay_ms=1.0,
                  pad_quantum=16, clock=clk)
    real_entry_for = svc._entry_for

    def entry_for(key, *args, **kwargs):
        if key.dtype == "float32":  # the reconstruct bucket only
            raise RuntimeError("compile failed")
        return real_entry_for(key, *args, **kwargs)

    monkeypatch.setattr(svc, "_entry_for", entry_for)
    broken = [svc.submit("reconstruct", *_recon_pair(rng))
              for _ in range(2)]
    img = _image(rng)
    other = svc.submit("hfill", img)  # second bucket, same flush instant
    clk.advance(0.002)
    svc.poll()  # both flush timers due: neither may be lost
    for t in broken:
        assert t.done and t.outcome == "executor"
        assert isinstance(t.error, ExecutorError)
        assert "compile failed" in str(t.error.cause)
        with pytest.raises(ExecutorError):
            t.result()
    for _ in range(200):
        if other.done:
            break
        svc.poll()
    assert other.outcome == "ok"
    assert_array_equal(np.asarray(other.result()),
                       np.asarray(OPS.hfill(jnp.asarray(img))))
    assert not any(k.dtype == "float32" for k in svc._engines)
    assert svc.pending() == 0
    assert svc.stats()["counters"]["batch_failures"] == 1


def test_continuous_matches_batch_path(rng):
    """continuous=True and the plain batch path must be value-identical
    on the same traffic (refill changes scheduling, never bytes)."""
    cases = [_recon_pair(rng) for _ in range(5)]
    results = {}
    for cont in (False, True):
        svc = Service(continuous=cont, max_batch=4, max_delay_ms=1e9,
                      pad_quantum=16, clock=VirtualClock())
        ts = [svc.submit("reconstruct", m, f) for m, f in cases]
        svc.flush()
        results[cont] = [np.asarray(t.result()) for t in ts]
    for a, b in zip(results[False], results[True]):
        assert_array_equal(a, b)


def test_occupancy_accounting():
    """Continuous occupancy is time-weighted: busy slot-rounds over
    total slot-rounds, not requests over slots."""
    m = ServeMetrics()
    m.record_round("b", n_busy=2, n_slots=4, t=0.0)
    m.record_round("b", n_busy=4, n_slots=4, t=1.0)
    m.record_round("b", n_busy=1, n_slots=4, t=2.0)
    s = m.summary()
    assert s["buckets"]["b"]["rounds"] == 3
    assert s["buckets"]["b"]["batch_occupancy"] == pytest.approx(7 / 12)
    # the batch-path formula still applies when no rounds were recorded
    m2 = ServeMetrics()
    m2.record_batch("c", n_real=3, n_slots=4, pixels=16, t_dispatch=0.0,
                    t_done=1.0, latencies_s=[0.1] * 3)
    assert m2.summary()["buckets"]["c"]["batch_occupancy"] == 0.75


def test_work_occupancy_chunk_weighted():
    """work_occupancy weighs by scheduler chunks, not slot fill: a
    full batch whose straggler holds the device while its mates idle
    scores low even though every slot carries a request."""
    m = ServeMetrics()
    # batch path: 4 real slots, but one ran 40 chunks while the other
    # three converged in 2 → busy 46 of a 160-chunk device reservation
    m.record_batch("b", n_real=4, n_slots=4, pixels=16, t_dispatch=0.0,
                   t_done=1.0, latencies_s=[0.1] * 4,
                   busy_chunks=46, cap_chunks=160)
    s = m.summary()["buckets"]["b"]
    assert s["batch_occupancy"] == 1.0           # fill metric saturates
    assert s["work_occupancy"] == pytest.approx(46 / 160)
    # engine rounds: refill keeps the chunk counters dense
    m2 = ServeMetrics()
    m2.record_round("c", n_busy=4, n_slots=4, t=0.0,
                    busy_chunks=8, cap_chunks=8)
    m2.record_round("c", n_busy=2, n_slots=4, t=1.0,
                    busy_chunks=4, cap_chunks=8)
    s2 = m2.summary()
    assert s2["buckets"]["c"]["work_occupancy"] == pytest.approx(12 / 16)
    assert s2["totals"]["work_occupancy"] == pytest.approx(12 / 16)
    # without chunk counters the field falls back to the fill metric
    m3 = ServeMetrics()
    m3.record_round("d", n_busy=1, n_slots=4, t=0.0)
    assert m3.summary()["buckets"]["d"]["work_occupancy"] == 0.25


def test_work_occupancy_straggler_batch_vs_engine(rng):
    """End to end: the same straggler-plus-fast traffic scores a lower
    work_occupancy on the poll batch path (the straggler's chunks
    reserve all four lanes) than fill occupancy suggests, and the
    continuous engine reports refills plus its own chunk accounting."""
    cases = [_recon_pair(rng, slow=True)] + [_recon_pair(rng)
                                             for _ in range(3)]
    svc = Service(continuous=False, max_batch=4, max_delay_ms=1e9,
                  pad_quantum=16, clock=VirtualClock())
    ts = [svc.submit("reconstruct", m, f) for m, f in cases]
    svc.flush()
    assert all(t.outcome == "ok" for t in ts)
    tot = svc.stats()["totals"]
    assert tot["batch_occupancy"] == 1.0  # all four slots held requests
    # the straggler ran ~35x its batch-mates' chunks: most of the
    # device reservation was spent on one image
    assert 0.0 < tot["work_occupancy"] < 0.5


def test_engine_occupancy_from_rounds(rng):
    """The served bucket's occupancy reflects the recorded rounds."""
    clk = VirtualClock()
    svc = Service(continuous=True, max_batch=4, refill_quantum=2,
                  max_delay_ms=1e9, pad_quantum=16, clock=clk)
    ts = [svc.submit("reconstruct", *_recon_pair(rng)) for _ in range(2)]
    svc.flush()
    assert all(t.outcome == "ok" for t in ts)
    label = next(iter(svc.stats()["buckets"]))
    b = svc.stats()["buckets"][label]
    assert b["rounds"] >= 1
    # 2 busy slots of 4 every round → exactly 0.5 while both run
    assert 0.0 < b["batch_occupancy"] <= 1.0


# ---------------------------------------------------------------------------
# shedding, close, adaptive quantum
# ---------------------------------------------------------------------------


def test_queue_full_sheds_under_virtual_clock(rng):
    clk = VirtualClock()
    svc = Service(backend="xla", max_batch=8, max_queue=2,
                  max_delay_ms=5.0, pad_quantum=16, clock=clk)
    t1 = svc.submit("hfill", _image(rng))
    t2 = svc.submit("hfill", _image(rng))
    with pytest.raises(QueueFullError):
        svc.submit("hfill", _image(rng))
    assert svc.stats()["counters"]["shed"] == 1
    clk.advance(0.01)
    svc.pump()
    while svc.work_pending():
        svc.pump()
    assert t1.outcome == "ok" and t2.outcome == "ok"
    assert svc.stats()["totals"]["requests"] == 2


def test_backpressure_watermark_launches_early(rng):
    """At the high-water mark admission force-launches the fullest
    bucket instead of waiting out max_delay."""
    clk = VirtualClock()
    svc = Service(backend="xla", max_batch=8, high_water=3,
                  max_delay_ms=1e9, pad_quantum=16, clock=clk)
    ts = [svc.submit("hfill", _image(rng)) for _ in range(3)]
    # third admission hit the watermark → bucket launched despite the
    # infinite flush delay
    assert svc.pending() == 0
    assert svc.stats()["counters"]["backpressure_flushes"] >= 1
    while svc.work_pending():
        svc.pump()
    assert all(t.outcome == "ok" for t in ts)


def test_closed_service_rejects(rng):
    svc = Service(backend="xla", max_batch=2, pad_quantum=16,
                  clock=VirtualClock())
    t = svc.submit("hfill", _image(rng))
    svc.close()
    assert svc.closed and t.done  # close drains admitted work
    with pytest.raises(ServiceClosedError):
        svc.submit("hfill", _image(rng))
    svc.close()  # idempotent


def test_adaptive_quantum_splits_on_pad_waste(rng):
    svc = Service(backend="xla", max_batch=8, max_delay_ms=1e9,
                  pad_quantum=64, adaptive_quantum=True, adapt_every=4,
                  clock=VirtualClock())
    for _ in range(4):
        svc.submit("hfill", _image(rng, (33, 33)))
    # 33x33 in 64x64 buckets: ~73% pad waste → quantum halves
    assert svc.stats()["counters"]["quantum_splits"] >= 1
    assert set(svc._quantum.values()) == {32}
    svc.flush()


def test_adaptive_quantum_merges_sparse_buckets(rng):
    svc = Service(backend="xla", max_batch=8, max_delay_ms=1e9,
                  pad_quantum=8, adaptive_quantum=True, adapt_every=4,
                  clock=VirtualClock())
    for shape in ((16, 16), (24, 24), (32, 32), (16, 16)):
        svc.submit("hfill", _image(rng, shape))
    # three quantum-aligned grids at zero pad waste → quantum doubles
    assert svc.stats()["counters"]["quantum_merges"] >= 1
    assert set(svc._quantum.values()) == {16}
    svc.flush()


# ---------------------------------------------------------------------------
# the flake detector, in process: one scenario, two replays, same counters
# ---------------------------------------------------------------------------


def test_selftest_scenario_deterministic():
    """The CI flake-detector contract: the canonical sim scenario
    replayed twice produces byte-identical summaries (counters, bucket
    rounds, outcomes) — no hidden wall-clock or ordering dependence."""
    kw = dict(continuous=True, max_batch=4, max_delay_ms=4.0,
              pad_quantum=32, refill_quantum=2)
    a = selftest_scenario(SimHarness(**kw))
    b = selftest_scenario(SimHarness(**kw))
    assert a == b
    assert sum(1 for o in a["outcomes"] if o != "pending") == len(
        a["outcomes"])
