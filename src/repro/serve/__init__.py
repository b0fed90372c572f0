"""repro.serve — shape-bucketed micro-batching service for the geodesic
operators, with a compiled-plan cache and an async double-buffered
pipeline.

Mapping onto the paper's stream-processing pipeline (§3.6)
----------------------------------------------------------

The paper's CPU implementation reaches real-time throughput (>30 FPS on
1024×1024 frames through chains of up to 1500 elementary 3×3 filters)
by treating the operator chain as a *stream pipeline*: a run-time
topology examination picks the thread/window schedule, T elementary
filters stay in flight at once, row-window synchronized, and the
per-frame work is overlapped so the cores never idle between filters.
This package is the serving-side analogue of that pipeline for the
TPU/Pallas port, one stage per module:

``registry``
    The paper examines the machine topology at run time and schedules
    the chain accordingly; here every public operator of
    ``core.operators`` / ``kernels.ops`` is declared as data (string
    name + param schema + *expression builder* via their ``SERVE_OPS``
    hooks).  The registry lowers each expression through
    ``repro.api.lower`` and derives the prepare/run/finalize pipeline
    stages, pad fills and bucket identity mechanically from the lowered
    program; the per-bucket :class:`~repro.core.chain.ChainPlan` — the
    TPU analogue of that topology examination — is bound per compiled
    program by ``repro.api.compile``.
``bucketer``
    The paper feeds same-shaped row windows through a fixed pipeline;
    heterogeneous request traffic is coalesced into ``(N, H, W)``
    stacks per (run-signature, padded-shape, dtype) bucket — cross-op
    packing: different operators whose run phases compile identically
    (HMAX/DOME/RAOBJ) co-batch — with absorbing-identity padding (the
    kernels' own border contract) and a ``max_delay_ms`` deadline so
    stragglers never wait for co-batched traffic that may never arrive.
``cache``
    The paper amortizes schedule construction across the stream; the
    LRU compiled-program cache amortizes trace+compile across requests,
    keyed on ``Executable.key`` (lowered run signature + bucket shape/
    dtype/backend + plan key — the same identity the ``repro.api``
    compile cache uses), each entry carrying the ChainPlan it embeds.
``executor``
    The paper overlaps the filters of a chain across cores; the
    executor overlaps *staging* of the next stack with *device
    compute* of the current one (JAX async dispatch, bounded in-flight
    depth = double buffering) and demuxes per-request results, cropping
    bucket padding and dropping sentinel slots.
``metrics``
    The paper reports FPS per operator chain; ``ServeMetrics`` reports
    per-bucket latency percentiles, batch occupancy, cache hit-rate and
    FPS / MPx-per-s in the same JSON schema as ``benchmarks/run.py
    --json``.

The convergence-driven operators routed through ``kernels.ops`` all run
on the shared active-tile requeue driver (``_drive_scheduler``; the
scheduler lifecycle and the ChainPlan contract it schedules against are
documented in ``docs/ARCHITECTURE.md``), so a converged image in a
served stack stops costing tile work while its batch-mates iterate —
the serving-level payoff of the paper's Alg. 4 requeue mechanism.

Fault-tolerant lifecycle (PR 7, full contract in ``docs/ROBUSTNESS.md``)
------------------------------------------------------------------------

``errors``
    the typed error taxonomy: admission rejections
    (:class:`RequestRejected` and subclasses, :class:`QueueFullError`)
    raised synchronously from ``submit``; execution outcomes
    (:class:`DeadlineExceededError`, :class:`ExecutorError`,
    :class:`PoisonedRequestError`) recorded on tickets — no
    unstructured exception escapes ``Service.poll()``.
``faults``
    the deterministic fault-injection harness (:class:`FaultInjector`,
    seeded via ``REPRO_FAULTS``) driving the chaos suite and the CI
    ``chaos`` job through the named sites
    dispatch/drain/poison/deadline/budget.

Event-driven engine (PR 9)
--------------------------

``loop``
    the deterministic timer core: :class:`EventLoop` (a pumped
    ``(when, seq)``-ordered callback heap on an injectable clock) and
    :class:`VirtualClock` — the seam that makes every flush, expiry,
    refill and backpressure decision replayable in tests
    (``tests/serve_sim.py``).
``continuous``
    continuous batching: :class:`SlotEngine` keeps a resident
    :class:`~repro.api.executable.SlotSession` per refillable bucket
    and refills slots the moment their image converges, while
    stragglers keep iterating (``Service(continuous=True)``).
``service.AsyncService``
    the asyncio front-end — service timers trampolined onto the
    running asyncio loop so deadline flushes fire with no caller, and
    tickets awaitable as futures.
"""
from repro.serve import errors, faults, registry
from repro.serve.bucketer import BucketKey, Ticket, bucket_hw, canonical_batch
from repro.serve.cache import CacheEntry, CompiledProgramCache
from repro.serve.continuous import SlotEngine
from repro.serve.errors import (DeadlineExceededError, ExecutorError,
                                InvalidRequestError, NonFiniteInputError,
                                PoisonedRequestError, QueueFullError,
                                RequestRejected, ServeError,
                                ServiceClosedError, UnsupportedDtypeError)
from repro.serve.executor import Executor
from repro.serve.faults import FaultInjector, FaultSpec, InjectedFault
from repro.serve.loop import EventLoop, VirtualClock
from repro.serve.metrics import ServeMetrics
from repro.serve.service import AsyncService, Service, serve_stream

__all__ = [
    "AsyncService",
    "BucketKey",
    "CacheEntry",
    "CompiledProgramCache",
    "DeadlineExceededError",
    "EventLoop",
    "Executor",
    "ExecutorError",
    "FaultInjector",
    "FaultSpec",
    "InjectedFault",
    "InvalidRequestError",
    "NonFiniteInputError",
    "PoisonedRequestError",
    "QueueFullError",
    "RequestRejected",
    "ServeError",
    "ServeMetrics",
    "Service",
    "ServiceClosedError",
    "SlotEngine",
    "Ticket",
    "UnsupportedDtypeError",
    "VirtualClock",
    "bucket_hw",
    "canonical_batch",
    "errors",
    "faults",
    "registry",
    "serve_stream",
]
