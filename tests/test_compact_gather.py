"""The compaction's patch gather, ``kernels.ops._gather_patches``: one
aligned window of an identity-padded per-image view (``_halo_view``) a
workspace slot, pinned against the row-then-column gather it replaced on
row-only and tiled plans, u8 and f32, across a stack of images."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.chain import ChainPlan
from repro.kernels import ops


def _row_then_column_gather(x2, idx, plan, ident):
    """The earlier gather, kept as the reference: every cell's
    band_h + 2K whole rows, then its columns one index per element, then
    the rows outside the cell's image and the columns outside the array
    pinned to ``ident``."""
    bh, k, tw = plan.band_h, plan.fuse_k, ops._cell_tile_w(plan)
    h, w = x2.shape
    bi = idx // plan.n_tiles
    tj = idx % plan.n_tiles
    rows = bi[:, None] * bh - k + jnp.arange(bh + 2 * k)[None, :]
    cols = tj[:, None] * tw - k + jnp.arange(tw + 2 * k)[None, :]
    img0 = (bi // plan.n_bands) * plan.height_pad
    row_ok = (rows >= img0[:, None]) & (rows < img0[:, None] + plan.height_pad)
    col_ok = (cols >= 0) & (cols < w)
    g = jnp.take(x2, jnp.clip(rows, 0, h - 1), axis=0)
    g = jnp.take_along_axis(
        g, jnp.broadcast_to(jnp.clip(cols, 0, w - 1)[:, None, :],
                            (idx.shape[0], bh + 2 * k, tw + 2 * k)),
        axis=2,
    )
    g = jnp.where(row_ok[:, :, None] & col_ok[:, None, :], g, ident)
    return g.reshape(-1, tw + 2 * k)


# Three images of a 3-band stack: every cell of the middle image has
# neighbours above and below, and the outer images' cells sit on the
# stack's own edges.  The tiled plan is a 3 × 3 grid an image, so its
# cells cover every edge and corner of each image and one interior cell.
PLANS = {
    "row": ChainPlan(band_h=16, fuse_k=8, width_pad=48, height_pad=48,
                     n_bands=3, n_chunks=1, n_images=3,
                     compact_threshold=0.5),
    "tiled": ChainPlan(band_h=16, fuse_k=8, width_pad=48, height_pad=48,
                       n_bands=3, n_chunks=1, n_images=3,
                       compact_threshold=0.5, tile_w=16),
}
DTYPES = {"u8": (np.uint8, 0), "f32": (np.float32, np.inf)}


def _stack(plan, dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (plan.n_images * plan.height_pad, plan.width_pad)
    if dtype == np.uint8:  # never the identity, so pinning shows
        return jnp.asarray(rng.integers(1, 255, shape).astype(np.uint8))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _slots(plan, fill, seed=0):
    """Workspace slot → flat cell map: every cell in a shuffled order
    (``full``), or half the slots holding a shuffled half of the cells
    and the rest sentinels (``half``)."""
    rng = np.random.default_rng(seed)
    total = plan.total_tiles
    cells = rng.permutation(total)
    if fill == "full":
        return jnp.asarray(cells, jnp.int32), total
    n = total // 2
    idx = np.full((total,), total)
    idx[rng.choice(total, n, replace=False)] = cells[:n]
    return jnp.asarray(idx, jnp.int32), n


@pytest.mark.parametrize("fill", ["full", "half"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("layout", sorted(PLANS))
def test_window_gather_matches_row_then_column(layout, dtype, fill):
    plan = PLANS[layout]
    dt, ident = DTYPES[dtype]
    x2 = _stack(plan, dt)
    idx, n_real = _slots(plan, fill)
    got = ops._gather_patches(ops._halo_view(x2, plan, ident), idx, plan,
                              ident)
    want = _row_then_column_gather(x2, idx, plan, ident)
    assert got.shape == want.shape and got.dtype == x2.dtype
    ph = plan.band_h + 2 * plan.fuse_k
    got3 = np.asarray(got).reshape(idx.shape[0], ph, -1)
    want3 = np.asarray(want).reshape(idx.shape[0], ph, -1)
    real = np.asarray(idx) < plan.total_tiles
    assert real.sum() == n_real
    np.testing.assert_array_equal(got3[real], want3[real])
    # sentinel slots read no cell: all identity
    assert (got3[~real] == ident).all()


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("layout", sorted(PLANS))
def test_sentinel_slots_all_ident(layout, dtype):
    """A workspace of sentinels only (idx == total_tiles, the fill of
    ``_active_indices``) gathers nothing but the identity, whatever the
    stack holds next to its last row."""
    plan = PLANS[layout]
    dt, ident = DTYPES[dtype]
    x2 = _stack(plan, dt, seed=1)
    idx = jnp.full((4,), plan.total_tiles, jnp.int32)
    got = ops._gather_patches(ops._halo_view(x2, plan, ident), idx, plan,
                              ident)
    assert got.shape == (4 * (plan.band_h + 2 * plan.fuse_k),
                         ops._cell_tile_w(plan) + 2 * plan.fuse_k)
    assert (np.asarray(got) == ident).all()


@pytest.mark.parametrize("layout", sorted(PLANS))
def test_halo_view_rings_each_image(layout):
    """The view holds each image at offset (K, K), ringed by K rows and
    columns of the identity."""
    plan = PLANS[layout]
    k = plan.fuse_k
    x2 = _stack(plan, np.uint8, seed=2)
    v = np.asarray(ops._halo_view(x2, plan, 0))
    assert v.shape == (plan.n_images, plan.height_pad + 2 * k,
                       plan.width_pad + 2 * k)
    np.testing.assert_array_equal(
        v[:, k:-k, k:-k],
        np.asarray(x2).reshape(plan.n_images, plan.height_pad, -1))
    ring = v.copy()
    ring[:, k:-k, k:-k] = 0
    assert not ring.any()
