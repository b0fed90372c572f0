"""Async double-buffered executor: overlap staging with device
compute, demux per-request results — and keep serving through failures.

JAX dispatch is asynchronous — calling a compiled program enqueues the
device work and returns device buffers immediately — so the pipeline
falls out of bounded in-flight tracking: the service stages (pads and
stacks, on the device) the *next* batch while the device crunches the
current one, and the executor only blocks when ``depth`` batches are
already in flight (``depth=2`` is classic double buffering).

Draining a batch demuxes it: each real request slot is cropped back to
its original (H, W) (dropping the pad-to-bucket canonicalization), the
*request's own* finalize stage runs (requests in one bucket may come
from different ops under cross-op packing — e.g. DOME's ``f - hmax``
residual next to plain HMAX requests), the ticket is fulfilled, and
sentinel slots (batch padding up to the canonical size) are discarded.
Slots whose convergence watchdog tripped (the per-image vector from
``Executable.run_batch_stats``) are delivered with
``Ticket.degraded = True`` — partial convergence is a degraded result,
not an error.

Fault tolerance (the recovery ladder, ``docs/ROBUSTNESS.md``):

1. **retry with backoff** — a failed batch (trace, dispatch, or the
   asynchronous error surfacing at ``block_until_ready``) is re-run
   synchronously up to ``max_retries`` times via the service-provided
   ``runner`` closure; transient errors clear here and only cost a
   ``retried`` counter bump.
2. **bisect quarantine** — a batch that keeps failing is split in
   halves and each half re-run recursively, so a single poisoned
   request converges to a singleton that fails alone: *it* gets a typed
   :class:`~repro.serve.errors.PoisonedRequestError` while every
   healthy co-batched request completes bit-exactly (sub-batch
   execution is bit-exact by the bucketer's absorbing-pad/sentinel
   invariance).

No exception escapes the executor's public surface: every failure ends
as a typed error on the affected tickets.  Injected faults
(``serve/faults.py`` sites ``dispatch``/``drain``) enter exactly where
the real failures would.

Host spans (``serve.metrics.span``): ``serve.dispatch`` around the
call that enqueues a batch, ``serve.drain`` around retiring one, with
``serve.wait`` (the host blocked in ``block_until_ready``, summed into
``host_blocked_s``) and ``serve.demux`` inside it.  Each carries the
batch's id, which its tickets keep as ``Ticket.batch_id``.

Where this sits in the pipeline (registry → bucketer → cache →
executor) is mapped in ``docs/ARCHITECTURE.md``.
"""
from __future__ import annotations

import collections
import time
from typing import Any, NamedTuple

import jax
import numpy as np

from repro.serve import faults as F
from repro.serve.bucketer import BucketKey, PendingRequest
from repro.serve.errors import ExecutorError, PoisonedRequestError
from repro.serve.metrics import ServeMetrics, span


class InflightBatch(NamedTuple):
    outputs: tuple           # device buffers, one per run output
    converged: Any           # (n_slots,) bool device buffer, or None
    requests: list           # real PendingRequests (sentinel slots excluded)
    key: BucketKey
    n_slots: int
    t_dispatch: float
    runner: Any              # sync re-execution closure (recovery ladder)
    util: Any = None         # (busy, cap, compact, gathers) scalars, or None
    batch_id: int = -1       # the batch's id in its spans and tickets


class Executor:
    def __init__(self, metrics: ServeMetrics, depth: int = 2,
                 clock=time.monotonic, faults: F.FaultInjector = F.NULL,
                 max_retries: int = 2, backoff_s: float = 0.0,
                 sleep=time.sleep):
        if depth < 1:
            raise ValueError("pipeline depth must be >= 1")
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        self.depth = depth
        self.metrics = metrics
        self.clock = clock
        self.faults = faults
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.sleep = sleep
        self._inflight: collections.deque[InflightBatch] = collections.deque()

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    # -- dispatch ----------------------------------------------------------

    def dispatch(self, entry, key: BucketKey,
                 requests: list[PendingRequest], n_slots: int,
                 stacked_inputs: tuple, runner=None,
                 batch_id: int = -1) -> None:
        """Launch one batch (async) and retire the oldest if the
        pipeline is full.  Never raises: a trace/compile failure at the
        call enters the recovery ladder instead."""
        try:
            outputs, conv, util, t_dispatch = self.enqueue(
                entry, stacked_inputs, requests, batch_id)
        except Exception as exc:
            self.recover(key, requests, runner, exc)
            return
        self._inflight.append(InflightBatch(
            outputs=outputs, converged=conv, requests=requests, key=key,
            n_slots=n_slots, t_dispatch=t_dispatch, runner=runner,
            util=util, batch_id=batch_id,
        ))
        while len(self._inflight) > self.depth:
            self.drain_one()

    def enqueue(self, entry, stacked_inputs, requests, batch_id: int):
        """Call the entry's program (the ``serve.dispatch`` span) and
        stamp the requests' ``t_dispatch`` and ``batch_id`` →
        ``(outputs, conv|None, util|None, t_dispatch)``."""
        with span("serve.dispatch", batch=batch_id):
            outputs, conv, util = self._call_entry(entry, stacked_inputs)
        now = self.clock()
        for req in requests:
            req.ticket.t_dispatch = now
            req.ticket.batch_id = batch_id
        return outputs, conv, util, now

    def wait(self, key: BucketKey, batch_id: int, tree) -> float:
        """Block until ``tree`` is ready (the ``serve.wait`` span, summed
        into the bucket's ``host_blocked_s``); returns the clock when it
        was."""
        t0 = self.clock()
        try:
            with span("serve.wait", batch=batch_id):
                jax.block_until_ready(tree)
        finally:
            now = self.clock()
            self.metrics.record_wait(key.label(), now - t0)
        return now

    @staticmethod
    def _call_entry(entry, stacked_inputs):
        """Run a cache entry's primary callable →
        ``(outputs, conv|None, util|None)`` where ``util`` is the
        ``(busy_chunks, cap_chunks, compact_chunks, mask_gathers)``
        scalars of ``run_batch_stats``."""
        if entry.stats_fn is not None:
            outputs, conv, *util = entry.stats_fn(*stacked_inputs)
            return outputs, conv, tuple(util)
        out = entry.fn(*stacked_inputs)
        return (out if isinstance(out, tuple) else (out,)), None, None

    # -- drain + demux -----------------------------------------------------

    def drain_one(self) -> bool:
        """Block on the oldest in-flight batch and demux it."""
        if not self._inflight:
            return False
        batch = self._inflight.popleft()
        with span("serve.drain", batch=batch.batch_id):
            try:
                self.faults.check("drain", batch.key.label())
                now = self.wait(batch.key, batch.batch_id,
                                (batch.outputs, batch.converged, batch.util))
            except Exception as exc:  # async execution error surfaces here
                self.recover(batch.key, batch.requests, batch.runner, exc)
                return True
            for req in batch.requests:
                req.ticket.t_ready = now
            self._demux(batch.key, batch.requests, batch.n_slots,
                        batch.outputs, batch.converged, batch.t_dispatch,
                        util=batch.util)
        return True

    def drain_all(self) -> None:
        while self.drain_one():
            pass

    def _demux(self, key: BucketKey, requests, n_slots: int, outputs,
               converged, t_dispatch: float, util=None) -> None:
        """Crop, finalize and deliver per-request results (shared by the
        async drain path, the continuous engine's harvest, and the
        synchronous recovery re-runs), in the ``serve.demux`` span."""
        batch_id = requests[0].ticket.batch_id if requests else None
        with span("serve.demux", batch=-1 if batch_id is None else batch_id,
                  n=len(requests)):
            self._deliver(key, requests, n_slots, outputs, converged,
                          t_dispatch, util)

    def _deliver(self, key: BucketKey, requests, n_slots: int, outputs,
                 converged, t_dispatch: float, util) -> None:
        now = self.clock()
        conv = None if converged is None else np.asarray(converged)
        latencies = []
        pixels = 0
        n_errors = 0
        n_degraded = 0
        for slot, req in enumerate(requests):
            h, w = req.shape
            cropped = tuple(o[slot, :h, :w] for o in outputs)
            try:
                if req.finalize is not None:
                    cropped = tuple(req.finalize(
                        cropped,
                        tuple(map(self.metrics.to_device, req.images))))
                # arity per request: co-batched ops share a run phase
                # but may fan their finalize into different output counts
                req.ticket.value = (
                    cropped[0] if req.info.n_outputs == 1 else cropped
                )
                if conv is not None and not conv[slot]:
                    req.ticket.degraded = True
                    n_degraded += 1
                    self.metrics.count("degraded")
            except Exception as exc:  # surface per-request, keep serving
                req.ticket.error = ExecutorError(
                    f"finalize failed for request {req.ticket.request_id} "
                    f"({req.ticket.op})", cause=exc)
                n_errors += 1
            req.ticket._fulfill(now)
            latencies.append(now - req.ticket.t_enqueue)
            pixels += h * w

        # the scheduler's four scalars come to the host in one fetch
        busy, cap, compact, gathers = (
            (int(u) for u in jax.device_get(util)) if util is not None
            else (0, 0, 0, 0))
        self.metrics.record_batch(
            key.label(),
            n_real=len(requests),
            n_slots=n_slots,
            pixels=pixels,
            t_dispatch=t_dispatch,
            t_done=now,
            latencies_s=latencies,
            n_errors=n_errors,
            n_degraded=n_degraded,
            busy_chunks=busy,
            cap_chunks=cap,
            compact_chunks=compact,
            mask_gathers=gathers,
        )

    # -- recovery ladder: retry with backoff, then bisect quarantine -------

    def recover(self, key: BucketKey, requests, runner,
                exc: Exception) -> None:
        """A batch failed: retry whole, then bisect-quarantine.

        Every request ends with a typed outcome — value, degraded
        value, or :class:`PoisonedRequestError`/:class:`ExecutorError`
        — and nothing is raised to the caller.
        """
        self.metrics.count("batch_failures")
        if runner is None:
            # no re-execution path (direct executor use): typed failure
            self._fail_batch(requests, ExecutorError(
                f"batch {key.label()} failed with no runner to retry",
                cause=exc))
            return
        for attempt in range(self.max_retries):
            if self.backoff_s > 0.0:
                self.sleep(self.backoff_s * (2 ** attempt))
            self.metrics.count("retried")
            try:
                outputs, n_slots, conv = runner(requests)
            except Exception as exc2:
                exc = exc2
                continue
            self._demux(key, requests, n_slots, outputs, conv,
                        t_dispatch=self.clock())
            return
        self._quarantine(key, requests, runner, exc)

    def _quarantine(self, key: BucketKey, requests, runner,
                    cause: Exception) -> None:
        """Bisect-retry: isolate poisoned request(s) so healthy
        co-batched requests still complete bit-exactly."""
        if len(requests) == 1:
            req = requests[0]
            req.ticket.error = PoisonedRequestError(
                f"request {req.ticket.request_id} ({req.ticket.op}) "
                "poisoned its batch: every containing subset failed",
                cause=cause)
            req.ticket._fulfill(self.clock())
            self.metrics.count("poisoned")
            return
        mid = len(requests) // 2
        for part in (requests[:mid], requests[mid:]):
            try:
                outputs, n_slots, conv = runner(part)
            except Exception as exc:
                self._quarantine(key, part, runner, exc)
            else:
                self.metrics.count("quarantine_reruns")
                self._demux(key, part, n_slots, outputs, conv,
                            t_dispatch=self.clock())

    def _fail_batch(self, requests, exc: Exception) -> None:
        now = self.clock()
        for req in requests:
            req.ticket.error = exc
            req.ticket._fulfill(now)
