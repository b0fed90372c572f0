"""Chaos suite: the fault-tolerant request lifecycle under injected
failures (PR 7 robustness contract, ``docs/ROBUSTNESS.md``).

The core invariants asserted here:

* typed admission — malformed/non-finite/over-capacity requests are
  rejected synchronously with :mod:`repro.serve.errors` classes;
* the chaos matrix — for every injection site and both backends,
  healthy requests co-batched with a poisoned/failing one complete
  **bit-exactly** (assert_array_equal vs the direct operator call)
  while only the poisoned request gets a typed error;
* no unstructured exception escapes ``Service.poll()``/``flush()``/
  ``submit()``-launch — every injected failure resolves into a ticket
  outcome;
* partial convergence (the ``budget`` site) is a *degraded result*,
  not an error.

The suite runs both with and without ``REPRO_FAULTS`` set: tests pin
their own injectors, and the env-driven test uses the ambient schedule
when present (the CI ``chaos`` job pins one).
"""
import numpy as np
import jax.numpy as jnp
import pytest
from numpy.testing import assert_array_equal

from repro.core import operators as OPS
from repro.kernels import ops as K
from repro.serve import Service, VirtualClock
from repro.serve import registry  # noqa: F401 (registry: op hooks)
from repro.serve import faults as F
from repro.serve.errors import (DeadlineExceededError, NonFiniteInputError,
                                PoisonedRequestError, QueueFullError,
                                RequestRejected, ServeError,
                                UnsupportedDtypeError)

pytestmark = pytest.mark.serve

BACKENDS = ("pallas", "xla")


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture
def rng():
    return np.random.default_rng(1702)


def _image(rng, shape=(16, 16), dtype=np.uint8):
    return rng.integers(0, 255, shape).astype(dtype)


def _service(backend, spec="", **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay_ms", 1e9)
    kw.setdefault("pad_quantum", 16)
    kw.setdefault("max_retries", 1)
    kw.setdefault("sleep", lambda s: None)
    return Service(backend=backend, faults=F.parse(spec), **kw)


# ---------------------------------------------------------------------------
# spec grammar + injector determinism
# ---------------------------------------------------------------------------


def test_parse_grammar():
    inj = F.parse("seed=7; dispatch:p=0.5,n=2 ;budget:value=1;poison")
    assert inj.seed == 7
    assert inj.specs["dispatch"] == F.FaultSpec("dispatch", n=2, p=0.5)
    assert inj.specs["budget"].value == 1.0
    assert inj.specs["poison"] == F.FaultSpec("poison")
    assert not F.parse("").armed("dispatch")


@pytest.mark.parametrize("bad", [
    "unknown_site", "dispatch:q=1", "dispatch:p=x", "seed=x",
    "dispatch:p=2", "dispatch:n=-1", "poison;poison",
])
def test_parse_rejects_malformed(bad):
    with pytest.raises(F.FaultSpecError):
        F.parse(bad)


def test_injector_is_deterministic():
    spec = "seed=42;dispatch:p=0.3;poison:p=0.5,n=3"
    a, b = F.parse(spec), F.parse(spec)
    seq = lambda inj: [inj.should_fire(s)  # noqa: E731
                       for s in ("dispatch", "poison") * 50]
    assert seq(a) == seq(b)
    assert a.fired == b.fired
    assert a.specs["poison"].n == 3 and a.fired["poison"] <= 3


def test_from_env():
    inj = F.from_env({"REPRO_FAULTS": "seed=3;drain:n=1"})
    assert inj.seed == 3 and inj.armed("drain")
    assert F.from_env({}) is F.NULL
    assert F.from_env({"REPRO_FAULTS": "  "}) is F.NULL


# ---------------------------------------------------------------------------
# typed admission
# ---------------------------------------------------------------------------


def test_nonfinite_payload_rejected(rng):
    svc = _service("xla")
    f = rng.uniform(0.0, 1.0, (16, 16)).astype(np.float32)
    f[3, 4] = np.nan
    with pytest.raises(NonFiniteInputError, match="NaN/Inf"):
        svc.submit("hmax", f, params={"h": 0.1})
    f[3, 4] = np.inf
    with pytest.raises(NonFiniteInputError):
        svc.submit("hmax", f, params={"h": 0.1})
    # typed rejections are ValueErrors too (pre-robustness contract)
    with pytest.raises(ValueError):
        svc.submit("hmax", f, params={"h": 0.1})
    assert svc.stats()["counters"]["rejected"] == 3
    assert svc.pending() == 0  # nothing entered a bucket


def test_unsupported_dtype_rejected(rng):
    svc = _service("xla")
    f = np.zeros((8, 8), np.complex64)
    with pytest.raises(UnsupportedDtypeError, match="lattice"):
        svc.submit("hfill", f)
    with pytest.raises(RequestRejected):
        svc.submit("hfill", np.zeros((8, 8), bool))
    assert svc.stats()["counters"]["rejected"] == 2


def test_queue_full_sheds(rng):
    svc = _service("xla", max_batch=8, max_queue=2)
    svc.submit("hfill", _image(rng))
    svc.submit("hfill", _image(rng))
    with pytest.raises(QueueFullError, match="load-shed"):
        svc.submit("hfill", _image(rng))
    assert svc.stats()["counters"]["shed"] == 1
    svc.flush()  # the two admitted requests still complete
    assert svc.stats()["totals"]["requests"] == 2


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------


def test_deadline_expiry_sheds_at_launch(rng):
    clock = FakeClock()
    svc = _service("xla", clock=clock, default_deadline_ms=10.0)
    t_doomed = svc.submit("hfill", _image(rng))
    clock.advance(0.05)  # 50ms > 10ms deadline
    t_fresh = svc.submit("hfill", _image(rng), deadline_ms=1e6)
    svc.flush()
    assert t_doomed.outcome == "deadline"
    with pytest.raises(DeadlineExceededError):
        t_doomed.result()
    assert t_fresh.outcome == "ok"
    assert_array_equal(np.asarray(t_fresh.result()),
                       np.asarray(OPS.hfill(jnp.asarray(t_fresh.value))))
    assert svc.stats()["counters"]["expired"] == 1


def test_deadline_fault_site_forces_expiry(rng):
    clock = FakeClock()
    svc = _service("xla", spec="deadline:n=1;", clock=clock)
    svc.faults.specs["deadline"] = F.FaultSpec("deadline", n=1, value=1.0)
    t = svc.submit("hfill", _image(rng))  # injected 1ms deadline
    clock.advance(0.01)
    svc.flush()
    assert t.outcome == "deadline"
    assert svc.faults.fired["deadline"] == 1


# ---------------------------------------------------------------------------
# the chaos matrix: injection sites x backends, healthy slots bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("site", ["dispatch", "drain", "poison"])
def test_chaos_matrix_healthy_requests_bit_exact(rng, site, backend):
    """One injected failure per stream; every healthy request must
    complete bit-exactly vs the direct operator call, the poisoned one
    (poison site only) must get a typed PoisonedRequestError, and
    nothing may escape submit/flush."""
    svc = _service(backend, spec=f"{site}:n=1")
    images = [_image(rng) for _ in range(4)]
    tickets = [svc.submit("hmax", im, params={"h": 10}) for im in images]
    svc.flush()

    poisoned = [t for t in tickets if t.outcome == "poisoned"]
    healthy = [t for t in tickets if t.outcome == "ok"]
    if site == "poison":
        assert len(poisoned) == 1 and len(healthy) == 3
        with pytest.raises(PoisonedRequestError):
            poisoned[0].result()
        assert svc.stats()["counters"]["poisoned"] == 1
        assert svc.stats()["counters"]["quarantine_reruns"] >= 1
    else:
        # dispatch/drain faults are transient: retry clears them
        assert len(healthy) == 4 and not poisoned
        assert svc.stats()["counters"]["retried"] >= 1
    assert svc.stats()["counters"]["batch_failures"] >= 1

    for t in healthy:
        im = images[t.request_id]
        expect = OPS.hmax(jnp.asarray(im), 10)
        assert_array_equal(np.asarray(t.result()), np.asarray(expect))
    assert svc.faults.fired[site] == 1


# ---------------------------------------------------------------------------
# budget site: partial convergence is degraded, not an error
# ---------------------------------------------------------------------------


def test_budget_watchdog_degrades_pallas(rng):
    """A 1-chunk budget trips the scheduler watchdog on a propagation
    that needs several chunks: the ticket resolves with a value and
    ``degraded=True`` (the degraded-mode contract)."""
    svc = _service("pallas", spec="budget:value=1", max_batch=1)
    marker = np.zeros((64, 64), np.uint8)
    marker[0, 0] = 255
    mask = np.full((64, 64), 255, np.uint8)
    # the spike must flood the whole mask: ~(H+W)/fuse_k chunks of work
    t = svc.submit("reconstruct", marker, mask)
    svc.flush()
    assert t.error is None and t.done
    assert t.degraded and t.outcome == "degraded"
    assert t.result() is not None  # partial fixpoint, still delivered
    assert svc.stats()["counters"]["degraded"] == 1
    label = next(iter(svc.stats()["buckets"]))
    assert svc.stats()["buckets"][label]["degraded"] == 1


def test_budget_clean_run_not_degraded(rng):
    svc = _service("pallas", max_batch=1)
    t = svc.submit("hmax", _image(rng), params={"h": 10})
    svc.flush()
    assert t.outcome == "ok" and not t.degraded
    assert svc.stats()["counters"]["degraded"] == 0


# ---------------------------------------------------------------------------
# the umbrella invariant: nothing unstructured escapes poll()
# ---------------------------------------------------------------------------


def test_no_unstructured_exception_escapes_poll(rng):
    """Drive a request stream under an aggressive ambient fault
    schedule (REPRO_FAULTS when set — the CI chaos job pins one — else
    a local pinned spec): every ticket must end in a typed outcome."""
    import os
    spec = os.environ.get(
        "REPRO_FAULTS",
        "seed=1702;dispatch:p=0.3;drain:p=0.3;poison:p=0.2",
    )
    svc = _service("xla", spec=spec, max_batch=2, max_delay_ms=0.0)
    tickets = []
    for i in range(10):
        im = _image(rng, (16 + 16 * (i % 2), 16))
        try:
            tickets.append(svc.submit("hfill", im))
        except ServeError:
            pass  # typed admission rejection: allowed
        svc.poll()
    svc.flush()
    for t in tickets:
        assert t.done
        assert t.error is None or isinstance(t.error, ServeError)
        assert t.outcome != "pending"
    snap = svc.stats()["faults"]
    assert set(snap["fired"]) <= set(F.SITES)


# ---------------------------------------------------------------------------
# PR 9: the chaos matrix under the event-driven continuous engine
# ---------------------------------------------------------------------------


def _recon_pair(rng, shape=(24, 24), slow=False):
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


def _continuous_service(spec="", **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay_ms", 1e9)
    kw.setdefault("pad_quantum", 16)
    kw.setdefault("refill_quantum", 2)
    kw.setdefault("max_retries", 1)
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("clock", VirtualClock())
    return Service(backend="pallas", continuous=True,
                   faults=F.parse(spec), **kw)


def _drive(svc, tickets, clock, max_steps=2000):
    for _ in range(max_steps):
        if all(t.done for t in tickets):
            return
        clock.advance(1e-3)
        svc.poll()
        svc.executor.drain_all()
    raise AssertionError("continuous engine failed to complete tickets")


@pytest.mark.parametrize("site", ["dispatch", "drain", "poison"])
def test_chaos_matrix_continuous_engine(rng, site):
    """The PR 7 chaos matrix re-run on the stepped continuous engine:
    an injected failure at any site resolves through the recovery
    ladder with every healthy request completing bit-exactly and only
    a poisoned request getting a typed error — the session eviction
    path must not lose or corrupt occupants."""
    clock = VirtualClock()
    svc = _continuous_service(spec=f"{site}:n=1", clock=clock)
    cases = [_recon_pair(rng) for _ in range(4)]
    tickets = [svc.submit("reconstruct", m, f) for m, f in cases]
    svc.flush()
    _drive(svc, tickets, clock)

    poisoned = [t for t in tickets if t.outcome == "poisoned"]
    healthy = [t for t in tickets if t.outcome == "ok"]
    if site == "poison":
        assert len(poisoned) == 1 and len(healthy) == 3
        with pytest.raises(PoisonedRequestError):
            poisoned[0].result()
        assert svc.stats()["counters"]["poisoned"] == 1
    else:
        assert len(healthy) == 4 and not poisoned
        assert svc.stats()["counters"]["retried"] >= 1
    assert svc.stats()["counters"]["batch_failures"] >= 1
    for t in healthy:
        m, f = cases[t.request_id]
        ref = np.asarray(K.reconstruct(m, f, op="dilate"))
        assert_array_equal(np.asarray(t.result()), ref)
    assert svc.faults.fired[site] == 1


def test_poison_mid_refill_preserves_healthy_and_straggler(rng):
    """A poisoned request arriving in a *refill wave* (admitted while
    a straggler slot is still iterating) kills the session — eviction
    plus bisect-quarantine must isolate it while the straggler and
    every other occupant still complete bit-exactly."""
    clock = VirtualClock()
    svc = _continuous_service(clock=clock, refill_quantum=1)
    slow = _recon_pair(rng, slow=True)
    fast = [_recon_pair(rng) for _ in range(3)]
    cases = [slow] + fast
    tickets = [svc.submit("reconstruct", m, f) for m, f in cases]
    for key in list(svc._queue.keys()):
        svc._launch(key)  # engine spawned, first wave resident
    eng = next(iter(svc._engines.values()))
    assert eng.occupied
    for _ in range(3):
        svc.poll()  # free the fast slots while the straggler runs
    # next submission is poison, admitted into a freed slot mid-flight
    svc.faults.specs["poison"] = F.FaultSpec("poison", n=1)
    bad_pair = _recon_pair(rng)
    cases.append(bad_pair)
    tickets.append(svc.submit("reconstruct", *bad_pair))
    for key in list(svc._queue.keys()):
        svc._launch(key)
    _drive(svc, tickets, clock)

    assert tickets[-1].outcome == "poisoned"
    for t in tickets[:-1]:
        assert t.outcome == "ok"
        m, f = cases[t.request_id]
        ref = np.asarray(K.reconstruct(m, f, op="dilate"))
        assert_array_equal(np.asarray(t.result()), ref)
    assert svc.stats()["counters"]["refills"] >= 1


def test_budget_degrades_continuous_engine(rng):
    """The budget site under continuous batching: a 1-chunk budget
    truncates the slot, which is harvested as a degraded partial
    fixpoint (never an error) — same contract as the batch path."""
    clock = VirtualClock()
    svc = _continuous_service(spec="budget:value=1", clock=clock)
    marker = np.zeros((32, 32), np.float32)
    marker[0, 0] = 1.0
    mask = np.ones((32, 32), np.float32)
    t = svc.submit("reconstruct", marker, mask)
    svc.flush()
    _drive(svc, [t], clock)
    assert t.error is None and t.degraded and t.outcome == "degraded"
    assert t.result() is not None
    assert svc.stats()["counters"]["degraded"] == 1


def test_deadline_fault_expires_under_stepped_loop(rng):
    clock = VirtualClock()
    svc = _continuous_service(spec="deadline:n=1", clock=clock)
    svc.faults.specs["deadline"] = F.FaultSpec("deadline", n=1, value=1.0)
    t = svc.submit("reconstruct", *_recon_pair(rng))
    clock.advance(0.01)
    svc.poll()  # the expiry timer fires from the stepped loop
    assert t.outcome == "deadline"
    assert svc.stats()["counters"]["expired"] == 1


def test_no_unstructured_escape_continuous(rng):
    """The umbrella invariant on the async path: an aggressive ambient
    schedule (REPRO_FAULTS when set, as in CI) over the stepped
    continuous engine still resolves every ticket into a typed
    outcome, with no exception escaping submit/poll/flush."""
    import os
    spec = os.environ.get(
        "REPRO_FAULTS",
        "seed=1702;dispatch:p=0.3;drain:p=0.3;poison:p=0.2",
    )
    clock = VirtualClock()
    svc = _continuous_service(spec=spec, clock=clock, max_delay_ms=2.0)
    tickets = []
    for i in range(8):
        try:
            tickets.append(svc.submit("reconstruct",
                                      *_recon_pair(rng, slow=(i == 0))))
        except ServeError:
            pass
        clock.advance(1e-3)
        svc.poll()
    svc.flush()
    _drive(svc, tickets, clock)
    for t in tickets:
        assert t.done and t.outcome != "pending"
        assert t.error is None or isinstance(t.error, ServeError)
    assert set(svc.stats()["faults"]["fired"]) <= set(F.SITES)


def test_stats_surface_faults_and_counters(rng):
    svc = _service("xla", spec="seed=9;poison:n=1")
    t = svc.submit("hfill", _image(rng))
    svc.flush()
    assert t.outcome == "poisoned"
    s = svc.stats()
    assert s["faults"]["seed"] == 9
    assert s["faults"]["armed"] == ["poison"]
    assert s["counters"]["poisoned"] == 1
    assert s["counters"]["shed"] == 0  # schema stable at zero
