"""Every registered serve op, served through ``Service`` on both
backends, equals the op called directly (``test_serve.DIRECT``).

The cases come from the registry: each op, in each of uint8, uint16 and
float32 that its ``OpSpec.dtypes`` admits, on ``xla`` and ``pallas``.
The request's shape is not a bucket shape, so every case is padded to
its bucket and cropped back.  A registered op with no ``DIRECT`` entry
fails here.
"""
import numpy as np
import pytest

from repro.serve import Service, registry
from test_serve import DIRECT, FakeClock, _direct, _image

pytestmark = pytest.mark.serve

SHAPE = (40, 56)

#: Request params by name, where the schema's own sample makes a trivial
#: request: an ``h`` in the image's range, a few steps, and the dilate
#: direction that a marker ≤ mask needs.
PARAMS = {"s": 2, "n": 5, "op": "dilate"}


def _h(dtype):
    return 0.2 if np.dtype(dtype).kind == "f" else 40


def _cases():
    for op in registry.names():
        for dtype in (np.uint8, np.uint16, np.float32):
            if np.dtype(dtype).kind in registry.get(op).dtypes:
                for backend in ("xla", "pallas"):
                    yield pytest.param(
                        op, dtype, backend,
                        id=f"{op}-{np.dtype(dtype).name}-{backend}")


def _params(op, dtype):
    out = {}
    for name, spec in registry.get(op).params.items():
        if name == "h":
            out[name] = _h(dtype)
        elif name in PARAMS:
            out[name] = PARAMS[name]
        elif spec.required:
            out[name] = spec.sample()
    return out


def _images(op, params, dtype, rng):
    """One image per program input, made by the input's name: a marker
    under the mask, seed planes holding small seeded squares."""
    canon = registry.get(op).canonical_params(params)
    names = registry.request_info(op, canon).program.input_names
    planes = {}
    for name in names:
        if name == "seeds":
            p = np.zeros(SHAPE, dtype)
            p[10:14, 20:24] = 1
        elif name == "scribbles":
            p = np.zeros(SHAPE, dtype)
            p[10:14, 20:24] = 1
            p[28:32, 40:44] = 2
        else:
            p = _image(rng, SHAPE, dtype)
        planes[name] = p
    if "marker" in planes:
        planes["marker"] = np.minimum(planes["marker"], planes["mask"])
    return tuple(planes[n] for n in names)


@pytest.mark.parametrize("op,dtype,backend", list(_cases()))
def test_served_op_equals_direct(op, dtype, backend, rng):
    assert op in DIRECT, f"no direct reference for served op {op!r}"
    params = _params(op, dtype)
    images = _images(op, params, dtype, rng)
    svc = Service(backend=backend, max_batch=2, max_delay_ms=1e9,
                  clock=FakeClock())
    ticket = svc.submit(op, *images, params=params)
    svc.flush()
    got, want = ticket.result(), _direct(op, images, params)
    if registry.get(op).n_outputs == 1:
        got, want = (got,), (want,)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{op} {backend}")
