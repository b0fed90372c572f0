"""The open-loop schedule, the loops' bookkeeping and the due-time
latency arithmetic, on a fake service and a fake clock."""
import math

import numpy as np
import pytest

from bench import load


def test_open_gaps_same_load_every_seed():
    a = load.open_gaps(50.0, 10.0, np.random.default_rng(1))
    b = load.open_gaps(50.0, 10.0, np.random.default_rng(2))
    assert not np.array_equal(a, b)
    # the same set of gaps, in another order: counts differ by the one
    # request that the shuffled tail may push past the window
    assert abs(len(a) - len(b)) <= 1
    assert 490 <= len(a) <= 501
    assert np.all(np.diff(a) > 0) and a[-1] < 10.0
    full = np.sort(-np.log1p(-(np.arange(501) + 0.5) / 501) / 50.0)
    for due in (a, b):
        gaps = np.diff(np.concatenate([[0.0], due]))
        assert np.isin(np.round(gaps, 12), np.round(full, 12)).all()


def test_open_gaps_are_exponential_quantiles():
    due = load.open_gaps(100.0, 1e4, np.random.default_rng(0))
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(0.01, rel=0.01)
    # exponential: the median gap is ln 2 of the mean
    assert np.median(gaps) == pytest.approx(0.01 * math.log(2), rel=0.01)


def test_item_order_uses_every_item_equally():
    items = load.item_order(4, 10, np.random.default_rng(3))
    assert len(items) >= 10
    for r in range(len(items) // 4):
        assert sorted(items[4 * r:4 * r + 4]) == [0, 1, 2, 3]


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Ticket:
    def __init__(self):
        self.done = False
        self.error = None
        self.degraded = False
        self.t_done = 0.0


class FakeService:
    """Answers each request ``service_s`` after its batch starts, one
    request at a time; ``pump`` blocks (advances the clock) like a
    drain."""

    def __init__(self, clock, service_s):
        self.clock = clock
        self.service_s = service_s
        self.queue = []

    def submit(self):
        t = Ticket()
        self.queue.append(t)
        return t

    def work_pending(self):
        return bool(self.queue)

    def next_deadline(self):
        return None

    def pump(self):
        if not self.queue:
            return False
        t = self.queue.pop(0)
        self.clock.t += self.service_s
        t.done, t.t_done = True, self.clock.t
        return True


def driver(service_s):
    clock = Clock()
    svc = FakeService(clock, service_s)
    seen = []
    d = load.Driver(svc, lambda i: (i % 3, svc.submit()), 1_000_000,
                    clock=clock, sleep=clock.sleep, on_sent=seen.append)
    return d, clock, seen


def test_open_loop_latency_runs_from_due_time():
    d, clock, seen = driver(service_s=0.015)
    due = np.array([0.0, 0.001, 0.002, 0.5])
    t0 = clock()
    d.run_open(due, t0, t0 + 1.0)
    d.settle(clock() + 60)
    assert [s.index for s in seen] == [0, 1, 2, 3]
    assert [s.item for s in d.sent] == [0, 1, 2, 0]
    # request 1 was due at +1 ms but the engine turn of request 0 held
    # the thread until +15 ms: it was sent 14 ms late, answered at +30
    assert d.sent[1].lag == pytest.approx(0.014)
    lat = load.latencies_ms(d.sent)
    assert lat[0] == pytest.approx(15.0)
    assert lat[1] == pytest.approx(29.0)
    assert lat[2] == pytest.approx(43.0)
    assert lat[3] == pytest.approx(15.0)
    assert clock() >= t0 + 1.0


def test_failed_request_counts_as_missing():
    d, clock, _ = driver(service_s=0.01)
    t0 = clock()
    d.run_open(np.array([0.0, 0.1]), t0, t0 + 0.2)
    d.settle(clock() + 60)
    d.sent[1].ticket.error = RuntimeError("lost")
    lat = load.latencies_ms(d.sent)
    assert lat[1] == math.inf
    assert load.percentile(lat, 50) == math.inf
    assert load.percentile(lat, 0) == pytest.approx(10.0)


def test_closed_loop_keeps_requests_in_flight_and_counts_window():
    d, clock, _ = driver(service_s=0.3)
    t0 = clock()
    d.run_closed(2, t0 + 1.0)
    d.settle(clock() + 60)
    # each answer frees its caller, who sends at once
    done = [s.ticket.t_done - t0 for s in d.sent]
    assert done[:4] == pytest.approx([0.3, 0.6, 0.9, 1.2])
    assert load.completed_mpx(d.sent, t0 + 1.0) == pytest.approx(3.0)


def test_settle_gives_up_at_deadline():
    d, clock, _ = driver(service_s=0.01)
    d.service.pump = lambda: False
    t0 = clock()
    d.run_open(np.array([0.0]), t0, t0 + 0.1)
    d.settle(t0 + 60)
    assert clock() == pytest.approx(t0 + 60)
    assert not d.sent[0].done
    assert load.latencies_ms(d.sent) == [math.inf]
