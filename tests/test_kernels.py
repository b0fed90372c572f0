"""Per-kernel validation: shape/dtype sweeps, every Pallas kernel
(interpret=True) asserted exactly equal to its ref.py pure-jnp oracle.
Morphology on the integer lattice is exact — we use array_equal, not
allclose."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.chain import (DEFAULT_VMEM_BUDGET, SCOPED_VMEM_BYTES,
                              plan_chain, work_dtype, working_set_bytes)
from repro.kernels import erode_chain, geodesic_chain, ops, ref

DTYPES = [np.uint8, np.uint16, np.float32, np.float64]
SHAPES = [(64, 64), (100, 130), (33, 257), (128, 96)]


def _image(rng, shape, dtype):
    if np.issubdtype(dtype, np.integer):
        return rng.integers(0, np.iinfo(dtype).max, shape).astype(dtype)
    return rng.normal(size=shape).astype(dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("n", [1, 5, 16, 33])
@pytest.mark.parametrize("op", ["erode", "dilate"])
def test_chain_kernel(rng, dtype, shape, n, op):
    f = jnp.asarray(_image(rng, shape, dtype))
    out = ops.morph_chain(f, n, op, "pallas")
    want = ref.chain(f, n, op)
    assert out.dtype == f.dtype and out.shape == f.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("shape", SHAPES[2:])
def test_chain_kernel_odd_shapes(rng, shape):
    f = jnp.asarray(_image(rng, shape, np.uint8))
    out = ops.morph_chain(f, 17, "erode", "pallas")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.chain(f, 17, "erode")))


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("n", [1, 7, 32])
@pytest.mark.parametrize("op", ["erode", "dilate"])
def test_geodesic_kernel(rng, dtype, n, op):
    f = jnp.asarray(_image(rng, (96, 120), dtype))
    m = jnp.asarray(_image(rng, (96, 120), dtype))
    marker = jnp.maximum(f, m) if op == "erode" else jnp.minimum(f, m)
    out = ops.geodesic_chain(marker, m, n, op, "pallas")
    want = ref.geodesic_chain(marker, m, n, op)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.float32])
@pytest.mark.parametrize("op", ["erode", "dilate"])
def test_reconstruct_kernel(rng, dtype, op):
    f = jnp.asarray(_image(rng, (80, 100), dtype))
    m = jnp.asarray(_image(rng, (80, 100), dtype))
    marker = jnp.maximum(f, m) if op == "erode" else jnp.minimum(f, m)
    out = ops.reconstruct(marker, m, op, "pallas")
    want = (ref.erode_reconstruct(marker, m) if op == "erode"
            else ref.dilate_reconstruct(marker, m))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_qdt_kernel(rng, dtype):
    f = jnp.asarray(_image(rng, (72, 96), dtype))
    d, r = ops.qdt_planes(f, backend="pallas")
    dw, rw = ref.qdt_raw(f)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dw))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rw))


def _extreme_image(rng, shape, dtype):
    """Values over the dtype's whole range, with its minimum and maximum
    (the two identity pins) on whole rows, columns and single pixels."""
    info = np.iinfo(dtype)
    x = rng.integers(int(info.min), int(info.max) + 1, shape,
                     endpoint=False).astype(dtype)
    h, w = shape
    x[rng.integers(0, h, 3)] = info.max
    x[:, rng.integers(0, w, 3)] = info.min
    x.flat[rng.integers(0, x.size, 16)] = info.max
    x.flat[rng.integers(0, x.size, 16)] = info.min
    x[0, 0], x[-1, -1] = info.max, info.min
    return x


def _geodesic_pair(rng, shape, dtype, op):
    f = _extreme_image(rng, shape, dtype)
    m = _extreme_image(rng, shape, dtype)
    marker = np.maximum(f, m) if op == "erode" else np.minimum(f, m)
    return jnp.asarray(marker), jnp.asarray(m)


K, BAND, TILE = 8, 16, 128      # fuse_k, band_h, tile_w
VMEM = SCOPED_VMEM_BYTES


def _per_image(oracle, *planes):
    """``oracle`` on each of two stacked images of two bands each (the
    kernels pin halos at the stack's and at the images' edges)."""
    halves = (slice(0, 2 * BAND), slice(2 * BAND, None))
    return jnp.concatenate([oracle(*(p[s] for p in planes))
                            for s in halves])


def _row_geodesic(rng, dtype, op):
    f, m = _geodesic_pair(rng, (4 * BAND, 72), dtype, op)
    out, _ = geodesic_chain.geodesic_chain_step(
        f, m, op=op, fuse_k=K, band_h=BAND, interpret=True,
        vmem_limit_bytes=VMEM, bands_per_image=2)
    return out, _per_image(lambda a, b: ref.geodesic_chain(a, b, K, op),
                           f, m)


def _tile_geodesic(rng, dtype, op):
    f, m = _geodesic_pair(rng, (4 * BAND, 2 * TILE), dtype, op)
    out, _ = geodesic_chain.geodesic_tile_step(
        f, m, op=op, fuse_k=K, band_h=BAND, tile_w=TILE, interpret=True,
        vmem_limit_bytes=VMEM, bands_per_image=2)
    return out, _per_image(lambda a, b: ref.geodesic_chain(a, b, K, op),
                           f, m)


def _compact_geodesic(rng, dtype, op):
    # three patches, each a centre and its K-wide halos; the centre of
    # K steps over the whole patch is exact
    ph, pw = BAND + 2 * K, TILE + 2 * K
    f, m = _geodesic_pair(rng, (3 * ph, pw), dtype, op)
    out, _ = geodesic_chain.geodesic_compact_step(
        f, m, jnp.ones((3, 1), jnp.int32), op=op, fuse_k=K, band_h=BAND,
        tile_w=TILE, interpret=True, vmem_limit_bytes=VMEM)
    want = jnp.concatenate([
        ref.geodesic_chain(f[c * ph:(c + 1) * ph], m[c * ph:(c + 1) * ph],
                           K, op)[K:K + BAND, K:K + TILE]
        for c in range(3)])
    return out, want


def _row_chain(rng, dtype, op):
    x = jnp.asarray(_extreme_image(rng, (4 * BAND, 72), dtype))
    out = erode_chain.chain_step(x, op=op, fuse_k=K, band_h=BAND,
                                 interpret=True, vmem_limit_bytes=VMEM,
                                 bands_per_image=2)
    return out, _per_image(lambda a: ref.chain(a, K, op), x)


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int16])
@pytest.mark.parametrize("op", ["erode", "dilate"])
@pytest.mark.parametrize("kernel", [_row_geodesic, _tile_geodesic,
                                    _compact_geodesic, _row_chain],
                         ids=["geodesic_row", "geodesic_tile",
                              "geodesic_compact", "erode_row"])
def test_kernel_exact_at_dtype_extremes(rng, kernel, op, dtype):
    """The kernels compute 8- and 16-bit images in float32
    (``work_dtype``): bit-exact against the jnp reference on images
    that hold the dtype's minimum and maximum beside the pinned halos."""
    out, want = kernel(rng, dtype, op)
    assert out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("dtype,wide", [
    (np.uint8, np.float32), (np.int8, np.float32),
    (np.uint16, np.float32), (np.int16, np.float32),
    (np.int32, np.int32), (np.float32, np.float32),
    (np.float64, np.float64),
])
def test_work_dtype(dtype, wide):
    assert work_dtype(dtype) == np.dtype(wide)


def test_plan_chain_invariants():
    for dtype in DTYPES:
        for w in (128, 1024, 5000):
            p = plan_chain(777, w, dtype, 100)
            assert p.band_h % p.fuse_k == 0
            assert p.width_pad % 128 == 0 and p.width_pad >= w
            assert p.height_pad % p.band_h == 0 and p.height_pad >= 777
            assert 0 < p.redundant_compute_fraction < 1


@pytest.mark.parametrize("planes", [1, 2, 3])
@pytest.mark.parametrize("convergent", [False, True])
def test_plan_chain_sizes_vmem(planes, convergent):
    """One VMEM model: the band's working set fits the budget (unless
    the band is already one fuse_k tall) and the plan's scoped-VMEM
    limit covers it, never below Mosaic's default."""
    for dtype in (np.uint8, np.float32):
        for w in (256, 1024, 2048, 5000):
            p = plan_chain(w, w, dtype, None if convergent else 1536,
                           n_images_resident=planes, convergent=convergent)
            need = working_set_bytes(p.band_h, p.fuse_k, p.width_pad,
                                     p.tile_w, dtype, planes)
            assert need <= DEFAULT_VMEM_BUDGET or p.band_h == p.fuse_k
            assert p.vmem_limit_bytes == max(SCOPED_VMEM_BYTES, need)
            taller = working_set_bytes(p.band_h + p.fuse_k, p.fuse_k,
                                       p.width_pad, p.tile_w, dtype, planes)
            assert (taller > DEFAULT_VMEM_BUDGET or p.band_h >= 512
                    or convergent)
