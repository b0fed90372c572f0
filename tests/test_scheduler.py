"""Active-band requeue scheduler: bit-exactness under band skipping and
compaction, stats accounting, and the batched (N, H, W) front-end.

The scheduler must be invisible in the outputs — every test here pins
the Pallas driver against the pure-jnp ``core.morphology`` references —
while the stats must show it actually skipped work on sparse markers.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import morphology as M
from repro.core import operators as OPS
from repro.core.chain import plan_chain
from repro.kernels import ops


def _sparse_marker(shape, dtype, seeds, value):
    m = np.zeros(shape, dtype)
    for (y, x) in seeds:
        m[y, x] = value
    return m


def _reference(marker, mask, op):
    if op == "erode":
        return M.erode_reconstruct(marker, mask)
    return M.dilate_reconstruct(marker, mask)


# ---------------------------------------------------------------------------
# bit-exactness on sparse single-seed markers (most bands converge early)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("op", ["erode", "dilate"])
def test_reconstruct_sparse_seed_exact(rng, dtype, op):
    shape = (320, 130)
    hi = 200 if dtype == np.uint8 else 1.5
    mask = rng.integers(20, 180, shape).astype(dtype) if dtype == np.uint8 \
        else rng.uniform(0.1, 1.2, shape).astype(dtype)
    if op == "erode":
        # erosion reconstructs downwards: marker >= mask, sparse "hole"
        marker = np.full(shape, np.iinfo(dtype).max if dtype == np.uint8
                         else 2.0, dtype)
        marker[37, 61] = mask[37, 61]
    else:
        marker = _sparse_marker(shape, dtype, [(37, 61)], hi)
        marker = np.minimum(marker, mask)
    out = ops.reconstruct(jnp.asarray(marker), jnp.asarray(mask), op, "pallas")
    want = _reference(jnp.asarray(marker), jnp.asarray(mask), op)
    assert out.dtype == jnp.asarray(marker).dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("op", ["erode", "dilate"])
def test_reconstruct_compaction_branch_exact(op):
    """Tall image + single seed: the active fraction drops below the
    compaction threshold, so the compacted grid path must run and stay
    bit-exact."""
    H, W = 512, 96
    fill = 180
    mask = np.full((H, W), fill, np.uint8)
    if op == "erode":
        marker = np.full((H, W), 255, np.uint8)
        marker[500, 48] = fill
    else:
        marker = np.zeros((H, W), np.uint8)
        marker[4, 48] = fill
    plan = plan_chain(H, W, np.uint8, None, n_images_resident=2,
                      convergent=True)
    out, stats = ops.reconstruct_with_stats(
        jnp.asarray(marker), jnp.asarray(mask), op, "pallas", plan=plan)
    want = _reference(jnp.asarray(marker), jnp.asarray(mask), op)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    per_chunk = np.asarray(stats.active_per_chunk)[: int(stats.chunks)]
    # the wavefront localizes: compaction-eligible chunks must exist
    assert (per_chunk <= plan.compact_capacity).any()


def test_reconstruct_512_sparse_band_work():
    """Acceptance criterion: on a 512×512 sparse-marker image the summed
    active-band count stays below 50% of total_bands × chunks while the
    output matches the reference exactly.

    The mask holds one horizontally extended object; the rest of the
    image is background the reconstruction never touches, so most bands
    converge after the first chunk and must stop being requeued."""
    H = W = 512
    mask = np.zeros((H, W), np.uint8)
    mask[224:288, 40:472] = 200  # object spanning 2 of 16 bands
    marker = _sparse_marker((H, W), np.uint8, [(240, 48)], 200)
    marker = np.minimum(marker, mask)
    out, stats = ops.reconstruct_with_stats(
        jnp.asarray(marker), jnp.asarray(mask), "dilate", "pallas")
    want = M.dilate_reconstruct(jnp.asarray(marker), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    total = int(stats.total_bands) * int(stats.chunks)
    assert int(stats.active_band_sum) < 0.5 * total, (
        f"scheduler did not skip enough: {int(stats.active_band_sum)} of "
        f"{total} band-chunks ran")


def test_active_bands_monotone_after_wavefront():
    """Once the geodesic wavefront has passed (peak activity), the
    per-chunk active-band count must be non-increasing: converged bands
    are never requeued."""
    H, W = 512, 128
    mask = np.full((H, W), 200, np.uint8)
    marker = _sparse_marker((H, W), np.uint8, [(4, 64)], 200)
    _, stats = ops.reconstruct_with_stats(
        jnp.asarray(marker), jnp.asarray(mask), "dilate", "pallas")
    per_chunk = np.asarray(stats.active_per_chunk)[: int(stats.chunks)]
    assert per_chunk.sum() == int(stats.active_band_sum)
    # chunk 0 is the all-active warm-up; the wavefront has passed once
    # the steady-state activity peaks for the last time.  From there the
    # count must never regrow — converged bands are never requeued.
    steady = per_chunk[1:]
    last_peak = len(steady) - 1 - int(steady[::-1].argmax())
    tail = steady[last_peak:]
    assert (np.diff(tail) <= 0).all(), f"active counts regrew: {per_chunk}"


def test_qdt_scheduled_exact(rng):
    """QDT runs the same scheduler; sparse image converges bandwise."""
    f = np.zeros((320, 96), np.uint8)
    f[8:24, 8:24] = 255  # one object near the top: bottom bands idle early
    d, r = ops.qdt_planes(jnp.asarray(f), backend="pallas")
    dw, rw = OPS.qdt_raw(jnp.asarray(f))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dw))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rw))


# ---------------------------------------------------------------------------
# explicit plan= override (API consistency across all three chain drivers)
# ---------------------------------------------------------------------------


def test_plan_override_consistent(rng):
    f = jnp.asarray(rng.integers(0, 255, (96, 100)).astype(np.uint8))
    m = jnp.asarray(rng.integers(0, 255, (96, 100)).astype(np.uint8))
    marker = jnp.maximum(f, m)
    plan = plan_chain(96, 100, np.uint8, None, n_images_resident=2,
                      fuse_k=8, band_h=32, convergent=True)
    out_c = ops.morph_chain(f, 8, "erode", "pallas", plan=plan)
    np.testing.assert_array_equal(
        np.asarray(out_c),
        np.asarray(ops.morph_chain(f, 8, "erode", "pallas")))
    out_g = ops.geodesic_chain(marker, m, 8, "erode", "pallas", plan=plan)
    np.testing.assert_array_equal(
        np.asarray(out_g),
        np.asarray(ops.geodesic_chain(marker, m, 8, "erode", "pallas")))
    out_r = ops.reconstruct(marker, m, "erode", "pallas", plan=plan)
    np.testing.assert_array_equal(
        np.asarray(out_r), np.asarray(M.erode_reconstruct(marker, m)))


def test_plan_validation_single_place():
    with pytest.raises(ValueError, match="multiple of fuse_k"):
        plan_chain(128, 128, np.uint8, None, fuse_k=32, band_h=48)
    with pytest.raises(ValueError):
        bad = plan_chain(64, 64, np.uint8, None)
        ops.reconstruct(jnp.zeros((200, 200), jnp.uint8),
                        jnp.zeros((200, 200), jnp.uint8),
                        "erode", "pallas", plan=bad)


# ---------------------------------------------------------------------------
# batched (N, H, W) front-end vs the per-image path
# ---------------------------------------------------------------------------


def _batch(rng, n, shape, dtype=np.uint8):
    return rng.integers(0, 255, (n, *shape)).astype(dtype)


@pytest.mark.parametrize("fn,s", [(ops.erode, 5), (ops.dilate, 5),
                                  (ops.opening, 3), (ops.closing, 3)])
def test_batched_fixed_ops(rng, fn, s):
    fb = jnp.asarray(_batch(rng, 3, (70, 90)))
    out = fn(fb, s, backend="pallas")
    assert out.shape == fb.shape
    for i in range(fb.shape[0]):
        np.testing.assert_array_equal(
            np.asarray(out[i]), np.asarray(fn(fb[i], s, backend="pallas")))


def test_batched_geodesic_chain(rng):
    fb = jnp.asarray(_batch(rng, 3, (70, 90)))
    mb = jnp.asarray(_batch(rng, 3, (70, 90)))
    marker = jnp.maximum(fb, mb)
    out = ops.geodesic_chain(marker, mb, 7, "erode", "pallas")
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            np.asarray(ops.geodesic_chain(marker[i], mb[i], 7, "erode",
                                          "pallas")))


@pytest.mark.parametrize("op", ["erode", "dilate"])
def test_batched_reconstruct(rng, op):
    fb = jnp.asarray(_batch(rng, 3, (64, 96)))
    mb = jnp.asarray(_batch(rng, 3, (64, 96)))
    marker = jnp.maximum(fb, mb) if op == "erode" else jnp.minimum(fb, mb)
    out = ops.reconstruct(marker, mb, op, "pallas")
    assert out.shape == fb.shape
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            np.asarray(_reference(marker[i], mb[i], op)))


def test_batched_per_image_convergence(rng):
    """A converged image must stop contributing band work: stack a
    trivially-converged image with a slow one and compare the active-band
    total against running the slow image alone."""
    H, W = 256, 96
    mask = np.full((H, W), 200, np.uint8)
    slow = _sparse_marker((H, W), np.uint8, [(4, 48)], 200)
    done = mask.copy()  # marker == mask: converged after one pass
    stack_m = jnp.asarray(np.stack([done, slow]))
    stack_k = jnp.asarray(np.stack([mask, mask]))
    out, stats = ops.reconstruct_with_stats(stack_m, stack_k, "dilate",
                                            "pallas")
    _, solo = ops.reconstruct_with_stats(
        jnp.asarray(slow), jnp.asarray(mask), "dilate", "pallas")
    np.testing.assert_array_equal(np.asarray(out[0]), mask)
    np.testing.assert_array_equal(
        np.asarray(out[1]),
        np.asarray(M.dilate_reconstruct(jnp.asarray(slow), jnp.asarray(mask))))
    # batched total ≈ solo total + one all-active pass for the done image:
    # well under doubling the work.
    assert int(stats.active_band_sum) < 2 * int(solo.active_band_sum)


def test_batched_qdt(rng):
    fb = jnp.asarray(_batch(rng, 2, (72, 96)))
    d, r = ops.qdt_planes(fb, backend="pallas")
    for i in range(2):
        dw, rw = OPS.qdt_raw(fb[i])
        np.testing.assert_array_equal(np.asarray(d[i]), np.asarray(dw))
        np.testing.assert_array_equal(np.asarray(r[i]), np.asarray(rw))


def test_batched_qdt_ragged_convergence(rng):
    """Per-image distance offsets: a trivially-flat image (converged in
    one chunk), a deep-structure image (many chunks) and a busy one
    stacked together must each match their solo qdt_raw exactly — the
    d-plane index is per-image, not the global chunk counter."""
    H, W = 160, 96
    flat = np.zeros((H, W), np.uint8)
    deep = np.zeros((H, W), np.uint8)
    deep[8:152, 8:88] = 255  # large object: erosion iterates longest
    busy = rng.integers(0, 255, (H, W)).astype(np.uint8)
    fb = jnp.asarray(np.stack([flat, deep, busy]))
    d, r = ops.qdt_planes(fb, backend="pallas")
    for i in range(3):
        dw, rw = OPS.qdt_raw(fb[i])
        np.testing.assert_array_equal(np.asarray(d[i]), np.asarray(dw))
        np.testing.assert_array_equal(np.asarray(r[i]), np.asarray(rw))


def _corridor_wavefront(H=128, W=256):
    """A marker seeded at one end of a serpentine corridor inside band 0
    (rows 0..31): the wavefront stays in the same few cells for many
    chunks."""
    mask = np.zeros((H, W), np.uint8)
    rows = list(range(2, 28, 4))
    for row in rows:  # serpentine corridor inside band 0 (rows 0..31)
        mask[row : row + 2, 2 : W - 2] = 200
    for j, row in enumerate(rows[:-1]):  # alternating end links
        col = W - 4 if j % 2 == 0 else 2
        mask[row : row + 6, col : col + 2] = 200
    marker = np.zeros((H, W), np.uint8)
    marker[2, 4] = 200
    return np.minimum(marker, mask), mask


def test_compaction_mask_cache_exact():
    """Wavefront confined to one band for many chunks: the compact
    workspace's mask gather is reused between chunks (the shared
    driver's gather_const cache hits while the active set is static);
    the output must stay bit-exact vs the oracle."""
    marker, mask = _corridor_wavefront()
    out, stats = ops.reconstruct_with_stats(
        jnp.asarray(marker), jnp.asarray(mask), "dilate", "pallas")
    want = M.dilate_reconstruct(jnp.asarray(marker), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert int(stats.chunks) > 8  # the in-band iteration actually ran long


def test_compaction_schedule_pinned():
    """The corridor's schedule, pinned: the full grid's 8 cells once,
    then 55 compact chunks of 4 cells that gather the mask once and hit
    the cache after.  How a patch is fetched must not move it."""
    marker, mask = _corridor_wavefront()
    _, stats = ops.reconstruct_with_stats(
        jnp.asarray(marker), jnp.asarray(mask), "dilate", "pallas")
    chunks = int(stats.chunks)
    per_chunk = np.asarray(stats.active_per_chunk)
    assert chunks == 56
    assert per_chunk[:chunks].tolist() == [8] + [4] * 55
    assert not per_chunk[chunks:].any()

    plan = plan_chain(*mask.shape, np.uint8, None, n_images_resident=2,
                      n_images=1, convergent=True)
    fp = ops._stacked(ops._pad(jnp.asarray(marker)[None], plan, 0))
    mp = ops._stacked(ops._pad(jnp.asarray(mask)[None], plan, 0))
    out = ops._scheduled_reconstruct(fp, mp, plan, "dilate",
                                     mask.size // plan.fuse_k + 2, False)
    compact_chunks, mask_gathers = out[-1]
    assert (int(compact_chunks), int(mask_gathers)) == (55, 1)


def _eqns_outside_loops(jaxpr):
    """Equations of ``jaxpr`` and of the jaxprs nested in them (jit,
    cond branches), except what runs inside a ``while`` loop."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "while":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_outside_loops(sub)


def test_compaction_no_gather_before_loop():
    """The mask cache's first value is built from shapes alone: no chunk
    reads it (its key never matches), so a compacting reconstruct
    fetches patches only inside its scheduler loop.  Outside it there is
    no gather and no other loop (a patch copy is a ``scan`` of windows)."""
    marker, mask = _corridor_wavefront()
    closed = jax.make_jaxpr(
        lambda f, m: ops.reconstruct_with_stats(f, m, "dilate", "pallas")
    )(jnp.asarray(marker), jnp.asarray(mask))
    outside = list(_eqns_outside_loops(closed.jaxpr))
    names = [e.primitive.name for e in outside]
    assert "gather" not in names and "scan" not in names
    loops = [e for e in outside if e.primitive.name == "while"]
    assert len(loops) == 1
    assert "scan" in str(loops[0].params["body_jaxpr"])


def test_operators_pallas_backend(rng):
    f = jnp.asarray(rng.integers(0, 255, (96, 96)).astype(np.uint8))
    np.testing.assert_array_equal(
        np.asarray(OPS.hmax(f, 40, backend="pallas")),
        np.asarray(OPS.hmax(f, 40)))
    np.testing.assert_array_equal(
        np.asarray(OPS.hfill(f, backend="pallas")),
        np.asarray(OPS.hfill(f)))
