"""Per-kernel validation: shape/dtype sweeps, every Pallas kernel
(interpret=True) asserted exactly equal to its ref.py pure-jnp oracle.
Morphology on the integer lattice is exact — we use array_equal, not
allclose."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.chain import (DEFAULT_VMEM_BUDGET, SCOPED_VMEM_BYTES,
                              plan_chain, working_set_bytes)
from repro.kernels import ops, ref

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
