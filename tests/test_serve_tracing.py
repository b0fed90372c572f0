"""What the served path records about itself: the stamps each ticket
carries through a batch, the scheduler's compaction counts, and the
scopes its compiled programs name.

Stamps are read under a virtual clock that ticks on every read, so each
stamp taken later reads strictly later and an out-of-order stamp shows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.serve import Service, VirtualClock
from repro.serve import executor as X
from repro.serve import faults as F
from repro.serve.service import hlo_scopes

pytestmark = pytest.mark.serve


class TickingClock(VirtualClock):
    """A virtual clock that moves 1 ms on every read."""

    def __call__(self):
        return self.advance(1e-3)


def _service(backend="xla", spec="", **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_delay_ms", 1e9)
    kw.setdefault("pad_quantum", 16)
    kw.setdefault("max_retries", 1)
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("clock", TickingClock())
    return Service(backend=backend, faults=F.parse(spec), **kw)


def _images(n, shape=(16, 16)):
    rng = np.random.default_rng(1313)
    return [rng.integers(0, 255, shape).astype(np.uint8) for _ in range(n)]


def _assert_ordered(t):
    assert t.outcome == "ok", t.outcome
    assert (t.t_enqueue < t.t_launch < t.t_dispatch < t.t_ready
            <= t.t_done), t


def _batches(tickets) -> dict:
    out: dict = {}
    for t in tickets:
        out.setdefault(t.batch_id, []).append(t)
    return out


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_ticket_stamps_ordered_batch_path(backend):
    svc = _service(backend)
    tickets = [svc.submit("hmax", im, params={"h": 10})
               for im in _images(5)]
    svc.flush()
    for t in tickets:
        _assert_ordered(t)
    batches = _batches(tickets)
    assert sorted(len(b) for b in batches.values()) == [1, 2, 2]
    for members in batches.values():
        # one batch: popped, enqueued and drained together
        assert len({(t.t_launch, t.t_dispatch, t.t_ready)
                    for t in members}) == 1
    assert svc.stats()["totals"]["host_blocked_s"] > 0


@pytest.mark.parametrize("site", ["dispatch", "drain", "poison"])
def test_ticket_stamps_ordered_recovery_rerun(site):
    """A failed batch is re-run by the recovery ladder, which stamps its
    requests again under a batch id of its own."""
    svc = _service(spec=f"{site}:n=1")
    tickets = [svc.submit("hmax", im, params={"h": 10})
               for im in _images(2)]
    svc.flush()
    healthy = [t for t in tickets if t.outcome == "ok"]
    assert len(healthy) == (1 if site == "poison" else 2)
    for t in healthy:
        _assert_ordered(t)
        assert t.batch_id > 0   # batch 0 failed; a re-run stamped it
    if site != "poison":
        assert len(_batches(healthy)) == 1
    else:
        (poisoned,) = [t for t in tickets if t.outcome == "poisoned"]
        assert poisoned.t_ready is None


def test_ticket_stamps_ordered_continuous_engine():
    clock = TickingClock()
    svc = _service("pallas", continuous=True, refill_quantum=2,
                   clock=clock)
    rng = np.random.default_rng(7)
    tickets = []
    for _ in range(3):
        f = rng.random((16, 16)).astype(np.float32)
        tickets.append(svc.submit("reconstruct", np.minimum(0.9 * f, f), f))
    for _ in range(500):
        if all(t.done for t in tickets):
            break
        svc.poll()
        svc.executor.drain_all()
    for t in tickets:
        _assert_ordered(t)
    # the engine admitted the first two in one wave, the third alone
    assert len(_batches(tickets)) == 2


def _corridor(h=128, w=256):
    """A serpentine corridor inside the first row band, seeded at one
    end: the wavefront stays in a few cells for many chunks, so the
    scheduler compacts and its mask-patch cache keeps hitting."""
    mask = np.zeros((h, w), np.uint8)
    rows = list(range(2, 28, 4))
    for row in rows:
        mask[row:row + 2, 2:w - 2] = 200
    for j, row in enumerate(rows[:-1]):
        col = w - 4 if j % 2 == 0 else 2
        mask[row:row + 6, col:col + 2] = 200
    marker = np.zeros((h, w), np.uint8)
    marker[2, 4] = 200
    return np.minimum(marker, mask), mask


@pytest.fixture(scope="module")
def corridor_service():
    marker, mask = _corridor()
    svc = Service(backend="pallas", max_batch=1)
    t = svc.submit("reconstruct", marker, mask, params={"op": "dilate"})
    svc.flush()
    assert t.outcome == "ok"
    return svc, marker, mask


def test_scheduler_counts_match_active_per_chunk(corridor_service):
    """``compact_chunks`` counts the chunks whose active cells fit the
    compact workspace; ``mask_gathers`` those of them whose active set
    moved, so that the cached mask patches were gathered again."""
    svc, marker, mask = corridor_service
    (entry,) = svc.cache.entries()
    plan = entry.plan
    _, st = ops.reconstruct_with_stats(jnp.asarray(marker),
                                       jnp.asarray(mask), "dilate",
                                       "pallas", plan=plan)
    per_chunk = np.asarray(st.active_per_chunk)[:int(st.chunks)]
    compact = [int(c) for c in per_chunk if c <= plan.compact_capacity]
    # a gather wherever the active count moved between compact chunks,
    # and at most one per compact chunk
    moved = sum(1 for i, c in enumerate(compact)
                if i == 0 or c != compact[i - 1])
    stats = svc.stats()
    tot = stats["totals"]
    assert tot["compact_chunks"] == len(compact) > 8
    assert moved <= tot["mask_gathers"] <= len(compact)
    assert tot["mask_gathers"] < tot["compact_chunks"]  # the cache hit
    (bucket,) = stats["buckets"].values()
    assert bucket["compact_chunks"] == tot["compact_chunks"]
    assert bucket["mask_gathers"] == tot["mask_gathers"]


def test_op_scopes_names_compact_gather(corridor_service):
    svc, _, _ = corridor_service
    scopes = svc.op_scopes()
    assert "compact_gather" in scopes.values()
    assert "schedule" in scopes.values()
    assert all(h.startswith("%") for h in scopes)


def test_hlo_scopes_reads_op_name_paths():
    text = "\n".join([
        "HloModule jit_f",
        "  %fusion.3 = u8[64,128]{1,0} fusion(u8[8]{0} %p), kind=kLoop, "
        'calls=%fc, metadata={op_name="jit(f)/while/body/compact_gather/'
        'take"}',
        "  ROOT %fusion.4 = s32[8]{0} fusion(s32[8]{0} %q), kind=kLoop, "
        'metadata={op_name="jit(f)/compact_gather/schedule/nonzero"}',
        '  %add.1 = s32[] add(s32[] %a, s32[] %b), metadata={op_name="x"}',
    ])
    assert hlo_scopes(text) == {
        "%fusion.3 = u8[64,128] fusion kLoop": "compact_gather",
        "%fusion.4 = s32[8] fusion kLoop": "schedule",
    }


def test_demux_fetches_scheduler_scalars_once(monkeypatch):
    """The four scheduler scalars of a batch reach the host in one
    ``jax.device_get``."""
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(X.jax, "device_get", counting)
    svc = _service()
    svc.submit("hmax", _images(1)[0], params={"h": 10})
    svc.flush()
    assert len(calls) == 1 and len(calls[0]) == 4
