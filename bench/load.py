"""Traffic: the arrival schedule a cell's parameters give, the loops that
drive the service through the measured window, and the arithmetic of
latency and rate.

One generator serves every cell.  A cell file names its loop:

``open``
    independent clients: requests are due on a fixed schedule whatever
    the service does, ``rate_hz`` on average, with exponential gaps
    (``arrivals: "poisson"``).  Every seed gets the same set of gaps in
    its own order, so the load is the same from seed to seed.  Latency
    runs from when a request was due, so a stall that delays later
    submissions counts against them.
``closed``
    ``in_flight`` callers that each send their next request when their
    last one is answered.

The loops are single-threaded: the service is a cooperative engine that
is pumped by its caller.  Each phase of a loop runs inside a named host
span (``bench.submit``, ``bench.pump``, ``bench.wait``), so that a
trace can say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

#: How long past the window's close the loops wait for answers that are
#: due; an answer that has not come by then counts as failed.
LATE_S = 60.0


@dataclasses.dataclass
class Sent:
    """One request as the generator saw it (clock seconds)."""

    index: int
    item: int            # pool index of its inputs
    due: float           # when it was due to be sent
    sent: float          # when ``submit`` was called
    ticket: object
    pixels: int

    @property
    def lag(self) -> float:
        return self.sent - self.due

    @property
    def done(self) -> bool:
        return bool(self.ticket.done)

    @property
    def ok(self) -> bool:
        t = self.ticket
        return t.done and t.error is None and not t.degraded


def open_gaps(rate_hz: float, seconds: float, rng) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop.

    The gaps are the midpoint quantiles of an exponential distribution
    of mean ``1 / rate_hz``, shuffled by ``rng``: a Poisson stream in
    which every seed offers the same load in its own order."""
    n = int(math.ceil(rate_hz * seconds)) + 1
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_hz
    due = np.cumsum(rng.permutation(gaps))
    return due[due < seconds]


def item_order(pool: int, count: int, rng) -> np.ndarray:
    """Pool indices for ``count`` requests: every item equally often,
    in shuffled rounds."""
    rounds = -(-count // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(rounds)])


def _noop(_name):
    return contextlib.nullcontext()


class Driver:
    """Pumps one service through a window of traffic.

    ``submit(i)`` sends request ``i`` and returns ``(pool item,
    ticket)``; ``pixels`` is the size of every request.  ``span(name)``
    opens a host span; ``on_sent(sent)`` sees each request as it goes."""

    def __init__(self, service, submit, pixels: int, *, span=_noop,
                 on_sent=None, clock=time.monotonic, sleep=time.sleep):
        self.service = service
        self.on_sent = on_sent
        self.submit = submit
        self.pixels = pixels
        self.span = span
        self.clock = clock
        self.sleep = sleep
        self.sent: list[Sent] = []

    def _send(self, due: float) -> Sent:
        i = len(self.sent)
        with self.span("bench.submit"):
            now = self.clock()
            item, ticket = self.submit(i)
        s = Sent(i, item, due, now, ticket, self.pixels)
        self.sent.append(s)
        if self.on_sent is not None:
            self.on_sent(s)
        return s

    def _pump_or_wait(self, until: float) -> None:
        """One engine turn; when it makes no progress, sleep until the
        earlier of ``until`` and the service's next timer."""
        svc = self.service
        if svc.work_pending():
            with self.span("bench.pump"):
                if svc.pump():
                    return
        nxt = svc.next_deadline()
        wake = until if nxt is None else min(until, nxt)
        wait = wake - self.clock()
        if wait > 0:
            with self.span("bench.wait"):
                self.sleep(wait)

    def run_open(self, due: np.ndarray, t0: float, t_end: float) -> None:
        """Send request ``i`` at ``t0 + due[i]``, pumping in between,
        until every due request is sent and the window has closed."""
        due_abs = t0 + np.asarray(due, dtype=np.float64)
        i = 0
        while True:
            now = self.clock()
            if i < len(due_abs) and now >= due_abs[i]:
                self._send(float(due_abs[i]))
                i += 1
            elif i >= len(due_abs) and now >= t_end:
                return
            else:
                nxt = due_abs[i] if i < len(due_abs) else t_end
                self._pump_or_wait(float(nxt))

    def run_closed(self, in_flight: int, t_end: float) -> None:
        """Keep ``in_flight`` requests outstanding until the window
        closes; each is sent the moment the one before it is answered."""
        waiting = [self._send(self.clock()) for _ in range(in_flight)]
        while True:
            now = self.clock()
            if now >= t_end:
                return
            done = [s for s in waiting if s.done]
            if done:
                waiting = [s for s in waiting if not s.done]
                waiting += [self._send(now) for _ in done]
                continue
            self._pump_or_wait(t_end)

    def settle(self, deadline: float) -> None:
        """Pump until every sent request is answered or ``deadline``."""
        while (self.clock() < deadline
               and not all(s.done for s in self.sent)):
            self._pump_or_wait(deadline)


def percentile(values, q: float) -> float:
    """``q``-th percentile, interpolated linearly between the two values
    around its rank; a missing value (``inf``) ranks above every answer,
    and a percentile that reaches one is ``inf``."""
    a = np.sort(np.asarray(values, dtype=np.float64))
    pos = q / 100.0 * (len(a) - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(a[hi]):
        return math.inf
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def latencies_ms(sent) -> list[float]:
    """Due-to-answer latency of every request, ``inf`` where it failed
    or never came."""
    return [(s.ticket.t_done - s.due) * 1e3 if s.ok else math.inf
            for s in sent]


def completed_mpx(sent, t_end: float) -> float:
    """Megapixels of the requests answered correctly by ``t_end``."""
    return sum(s.pixels for s in sent
               if s.ok and s.ticket.t_done <= t_end) / 1e6
