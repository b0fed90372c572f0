"""Continuous batching: refill converged slots mid-flight.

The batch executor retires a bucket only when *every* image in the
batch has converged — under the requeue scheduler a batch of mixed
images runs at the speed of its slowest member, and every early
finisher parks as dead capacity until the straggler lands.  The
:class:`SlotEngine` removes that coupling: it owns one resident
:class:`~repro.api.executable.SlotSession` per bucket (a persistent
padded device stack whose row blocks are independent images), advances
it in *rounds* of ``refill_quantum`` scheduler chunks, and the moment
the per-image converged vector marks a slot finished it harvests that
slot and admits the next queued request into it — while the other
slots keep iterating.

Correctness leans on two established invariants:

* **per-slot independence** — the plan pins band halos inside each
  image's row block, so one slot's values never leak into another's,
  and a slot admitted mid-flight starts from exactly the state a solo
  run would stage (same absorbing pads, all-active rows, zero chunk
  counter).  Harvested outputs are therefore bit-exact with solo
  execution (asserted by ``tests/test_serve_async.py``).
* **budget truncation** — each slot carries the same per-image chunk
  budget a solo run compiles with; a budget-cut slot is harvested as a
  degraded partial fixpoint identical to a solo run truncated at the
  same budget (``Ticket.degraded``), so the watchdog contract survives
  refill.

Fault sites thread through the same grammar as the batch path
(``serve/faults.py``): ``dispatch`` fires per admit wave, ``drain``
per round, and a ``poison``-marked occupant kills its *session* — the
engine evicts every occupant into the executor's recovery ladder
(retry → bisect quarantine), which isolates the poisoned request and
re-runs the healthy ones bit-exactly, then re-initializes the session
state.  Faults arriving mid-refill (after some harvests) therefore
never corrupt later occupants.  No exception escapes
:meth:`SlotEngine.step`.

Accounting: each round reports ``busy/total`` slots plus the
chunk-counter deltas (``busy_chunks``/``cap_chunks``) to
``ServeMetrics.record_round`` — the time-weighted occupancy and the
chunk-weighted ``work_occupancy`` the batch fill counter cannot
express — and every admit into a session that already has live
occupants bumps the ``refills`` counter.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.serve import faults as F
from repro.serve.bucketer import BucketKey, pad_plane
from repro.serve.metrics import span


class SlotEngine:
    """Resident continuous-batching session for one bucket key."""

    def __init__(self, service, key: BucketKey, info, entry):
        self.service = service
        self.key = key
        self.info = info
        self.entry = entry
        self.session = entry.exe.slot_session(service.refill_quantum)
        self.state = None                       # lazy: built on first admit
        self.slots: list = [None] * self.session.n_slots
        self._t_admit = [0.0] * self.session.n_slots
        self._prev_chunks = np.zeros(self.session.n_slots, np.int64)
        self.rounds = 0

    # -- occupancy ---------------------------------------------------------

    @property
    def n_occupied(self) -> int:
        return sum(1 for r in self.slots if r is not None)

    @property
    def occupied(self) -> bool:
        return any(r is not None for r in self.slots)

    # -- admission ---------------------------------------------------------

    def pull(self) -> int:
        """Admit queued requests into free slots; returns how many.

        Pops only what fits (surplus stays queued with its expiry
        timers intact) and sheds expired requests *after* the pop —
        this runs post-compile, so a deadline that lapsed during
        trace/compile is caught here instead of being dispatched (the
        race the poll-only check had).
        """
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free:
            return 0
        svc = self.service
        batch = svc._pop(self.key, limit=len(free))
        if not batch:
            return 0
        batch = svc._shed_expired(batch)
        if not batch:
            return 0
        return self._admit(batch, free)

    def _admit(self, batch, free) -> int:
        """One admit wave: a batch of its own in the ``serve.launch``
        span, each request staged (``serve.stage``) and written into its
        slot on the device (``serve.dispatch``)."""
        svc = self.service
        batch_id = svc._new_batch()
        with span("serve.launch", batch=batch_id, n=len(batch)):
            if self.state is None:
                self.state = self.session.init()
            try:
                svc.faults.check("dispatch", self.key.label())
            except Exception as exc:
                runner = functools.partial(svc._run_sync, self.key,
                                           self.info)
                svc.executor.recover(self.key, batch, runner, exc)
                return 0
            refill = self.occupied  # others still iterating → refills
            for req, slot in zip(batch, free):
                staged = self._staged(req, batch_id)
                with span("serve.dispatch", batch=batch_id):
                    self.state = self.session.admit(self.state, slot,
                                                    *staged)
                self.slots[slot] = req
                now = svc.clock()
                self._t_admit[slot] = now
                req.ticket.t_dispatch = now
                req.ticket.batch_id = batch_id
                self._prev_chunks[slot] = 0  # admit re-arms the counter
                if refill:
                    svc.metrics.count("refills")
        return len(batch)

    def _staged(self, req, batch_id: int):
        """Pad one request's canonical inputs on the device to the bucket
        (H, W) with the program's absorbing fills — byte-identical to
        the slice of the batch path's ``_stage`` stack this request
        would occupy."""
        to_device = self.service.metrics.to_device
        with span("serve.stage", batch=batch_id):
            return [pad_plane(to_device(x), self.key.hw, self.key.dtype,
                              fill)
                    for x, fill in zip(req.inputs, self.info.fills)]

    # -- rounds ------------------------------------------------------------

    def step(self) -> bool:
        """One scheduler round: advance every occupied slot by up to
        ``refill_quantum`` chunks, harvest finished slots, refill from
        the queue.  Returns True when any work happened; never raises
        (failures evict the session into the recovery ladder).  The
        round is a ``serve.drain`` span holding ``serve.dispatch`` (the
        round enqueued), ``serve.wait`` and the harvest's
        ``serve.demux``."""
        occupied = [i for i, r in enumerate(self.slots) if r is not None]
        if not occupied:
            return False
        with span("serve.drain", round=self.rounds):
            return self._round(occupied)

    def _round(self, occupied) -> bool:
        svc = self.service
        try:
            for i in occupied:
                if self.slots[i].poisoned:
                    raise F.InjectedFault(
                        "poison",
                        f"request {self.slots[i].ticket.request_id}")
            with span("serve.dispatch", round=self.rounds):
                self.state, finished, exhausted = self.session.round(
                    self.state)
            svc.faults.check("drain", self.key.label())
            t_ready = svc.executor.wait(self.key, -1, self.state)
        except Exception as exc:
            self._fail_session(exc)
            return True
        self.rounds += 1
        # chunk-weighted utilization: counter deltas are exactly the
        # chunks each slot ran this round; the device was held for the
        # longest slot's chunks across every slot
        chunks = np.asarray(self.session.chunks_of(self.state),
                            dtype=np.int64)
        delta = chunks - self._prev_chunks
        self._prev_chunks = chunks
        svc.metrics.record_round(self.key.label(), n_busy=len(occupied),
                                 n_slots=self.session.n_slots,
                                 t=svc.clock(),
                                 busy_chunks=int(delta.sum()),
                                 cap_chunks=(int(delta.max())
                                             * self.session.n_slots))
        fin = np.asarray(finished)
        exh = np.asarray(exhausted)
        done = [i for i in occupied if fin[i]]
        for i in done:
            self.slots[i].ticket.t_ready = t_ready
        if done:
            self._harvest(done, exh)
        self.pull()
        return True

    def _harvest(self, done, exh) -> None:
        """Deliver finished slots through the executor's demux (crop to
        request shape, finalize, fulfill) and free them."""
        svc = self.service
        outputs = self.session.extract(self.state)
        outs = tuple(svc.metrics.to_host(o)[done] for o in outputs)
        conv = ~exh[done]  # exhausted slot → degraded partial fixpoint
        requests = [self.slots[i] for i in done]
        t0 = min(self._t_admit[i] for i in done)
        svc.executor._demux(self.key, requests, len(done), outs, conv,
                            t_dispatch=t0)
        for i in done:
            self.slots[i] = None  # parked: no active rows → zero cost

    def _fail_session(self, exc: Exception) -> None:
        """A round failed (injected or real): evict every occupant into
        the recovery ladder and reset the session state.  Retry re-runs
        the eviction as a solo batch; bisect isolates poisoned
        requests while healthy occupants complete bit-exactly."""
        svc = self.service
        evicted = [r for r in self.slots if r is not None]
        self.slots = [None] * self.session.n_slots
        self.state = self.session.init()
        self._prev_chunks[:] = 0
        runner = functools.partial(svc._run_sync, self.key, self.info)
        svc.executor.recover(self.key, evicted, runner, exc)
