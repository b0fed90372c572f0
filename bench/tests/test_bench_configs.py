"""Each configuration's pool and request builder, served at a tiny size
through ``repro.serve.Service`` with its default (Pallas) backend in
interpret mode, against the configuration's plain reference; and the
reference against the package's own morphology, so that the two
independent definitions agree."""
import jax
import numpy as np
import pytest

from bench import reference as R
from bench import spec
from repro.core import morphology as M
from repro.core.operators import hfill_marker, sat_sub
from repro.serve import Service

SIZES = {
    "paper_frames_u8_1024": {"height": 64, "width": 128},
    "tissue_tiles_u8_2048": {"tile_px": 128, "nuclei": 24},
}
CASES = [("paper_frames_u8_1024", "geodesic", {"n": 40, "op": "dilate"}),
         ("tissue_tiles_u8_2048", "hmax", {"h": 40}),
         ("tissue_tiles_u8_2048", "hfill", None)]


def tiny(name):
    cfg, mod = spec.config(name)
    return dict(cfg, **SIZES[name]), mod


@pytest.mark.parametrize("name,op,params", CASES)
def test_served_answers_match_reference(name, op, params):
    cfg, mod = tiny(name)
    pool = mod.make_pool(cfg, jax.random.key(2**31 + 3), 2)
    host = tuple(np.asarray(a) for a in pool)
    svc = Service()
    tickets = [svc.submit(op, *(a[i] for a in host), params=params)
               for i in range(2)]
    svc.flush()
    expected = np.asarray(mod.reference(cfg, op, params or {}, pool))
    for i, t in enumerate(tickets):
        np.testing.assert_array_equal(np.asarray(t.result()), expected[i])
    # the comparison is not trivial: the answer moved from its marker
    assert not np.array_equal(expected[0], host[0][0])


def test_pool_is_made_from_the_seed():
    cfg, mod = tiny("tissue_tiles_u8_2048")
    a = np.asarray(mod.make_pool(cfg, jax.random.key(7), 2)[0])
    b = np.asarray(mod.make_pool(cfg, jax.random.key(7), 2)[0])
    c = np.asarray(mod.make_pool(cfg, jax.random.key(8), 2)[0])
    assert a.dtype == np.uint8 and a.shape == (2, 128, 128)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert 20 < a.mean() < 200


def test_reference_agrees_with_package_morphology():
    cfg, mod = tiny("tissue_tiles_u8_2048")
    (f,) = mod.make_pool(cfg, jax.random.key(1), 2)
    np.testing.assert_array_equal(
        R.dilate_reconstruct(R.sat_sub(f, 40), f),
        M.dilate_reconstruct(sat_sub(f, 40), f))
    np.testing.assert_array_equal(
        R.erode_reconstruct(R.hfill_marker(f), f),
        M.erode_reconstruct(hfill_marker(f), f))
    cfg, mod = tiny("paper_frames_u8_1024")
    m, f = mod.make_pool(cfg, jax.random.key(1), 2)
    np.testing.assert_array_equal(R.geodesic_dilate(m, f, 30),
                                  M.geodesic_dilate(m, f, 30))


@pytest.mark.parametrize("name,op,params", CASES)
def test_control_precision_changes_the_answer(name, op, params):
    cfg, mod = tiny(name)
    pool = mod.make_pool(cfg, jax.random.key(11), 2)
    full = np.asarray(mod.reference(cfg, op, params or {}, pool))
    low = np.asarray(mod.reference(cfg, op, params or {}, pool, 4))
    assert (full != low).sum(axis=(1, 2)).min() > 0
