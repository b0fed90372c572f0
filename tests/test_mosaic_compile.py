"""Every Pallas kernel compiles natively (``interpret=False``) for a
described TPU v5e at the paper's 1024² frame size.

No chip is needed: the TPU compiler is installed with JAX and compiles
for a topology that is described, not attached.  Each test lowers one
``pallas_call`` at the plan ``repro.api.compile`` makes for the served
expression, compiles it, and checks that the program holds the Mosaic
kernel (``tpu_custom_call``) — so a block layout, dtype or VMEM budget
the chip's compiler refuses fails here, not on the chip — and that the
launch names its own kernel in ``kernel_metadata``, the one name a TPU
profiler trace keeps for it.

The topology is described inside a module-scoped fixture (never while
the module is imported): only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import dataclasses
import functools
import importlib.util
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.api import E
from repro.kernels import erode_chain, gdt_chain, geodesic_chain, qdt_chain
from repro.kernels.common import qdt_acc_dtype

SIZE = 1024

#: A kernel's name as the compiled program prints ``kernel_metadata``.
KERNEL_NAME = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"(\w+)"')


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A described device cannot read the persistent compilation cache
    # back, so keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _plan(kind, dtype, n, size):
    """The plan ``api.compile`` makes for the served expression."""
    f, m = E.input("f"), E.input("m")
    expr = {
        "erode": E.erode(1536, f),
        "geodesic": E.geodesic(f, m, 64, "erode"),
        "reconstruct": E.reconstruct(f, m, op="dilate"),
        "qdt": E.qdt(f),
        "gdt": E.gdt(f, m, lamb=1.0, nu=1e6),
    }[kind]
    return api.compile(expr, (n, size, size), dtype, "pallas").plan


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _planes(plan):
    return (plan.n_images * plan.height_pad, plan.width_pad)


def _patches(plan):
    tw = plan.tile_w or plan.width_pad
    cap = plan.compact_capacity
    return ((cap * (plan.band_h + 2 * plan.fuse_k), tw + 2 * plan.fuse_k),
            (cap * plan.band_h, tw), (cap,), tw)


def _row(plan):
    return dataclasses.replace(plan, tile_w=0)


def erode_row(one_chip, dtype, n, size):
    p = _plan("erode", dtype, n, size)
    fn = functools.partial(erode_chain.chain_step, op="erode",
                           fuse_k=p.fuse_k, band_h=p.band_h, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes,
                           bands_per_image=p.n_bands)
    return _compile(one_chip, fn, (_planes(p), dtype))


def geodesic_row(one_chip, dtype, n, size):
    p = _plan("geodesic", dtype, n, size)
    fn = functools.partial(geodesic_chain.geodesic_chain_step, op="erode",
                           fuse_k=p.fuse_k, band_h=p.band_h, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes,
                           bands_per_image=p.n_bands)
    return _compile(one_chip, fn, (_planes(p), dtype), (_planes(p), dtype))


def geodesic_tile(one_chip, dtype, n, size):
    p = _plan("reconstruct", dtype, n, size)
    assert p.tile_w
    fn = functools.partial(geodesic_chain.geodesic_tile_step, op="dilate",
                           fuse_k=p.fuse_k, band_h=p.band_h,
                           tile_w=p.tile_w, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes,
                           bands_per_image=p.n_bands)
    return _compile(one_chip, fn, (_planes(p), dtype), (_planes(p), dtype))


def geodesic_compact(one_chip, dtype, n, size):
    p = _plan("reconstruct", dtype, n, size)
    patch, _, flags, tw = _patches(p)
    fn = functools.partial(geodesic_chain.geodesic_compact_step,
                           op="dilate", fuse_k=p.fuse_k, band_h=p.band_h,
                           tile_w=tw, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes)
    return _compile(one_chip, fn, (patch, dtype), (patch, dtype),
                    (flags, jnp.int32))


def qdt_row(one_chip, dtype, n, size):
    p = _row(_plan("qdt", dtype, n, size))
    bands = (p.total_bands, 1)

    def fn(f, r, d, base, active):
        return qdt_chain.qdt_chain_step(
            f, r, d, base, fuse_k=p.fuse_k, band_h=p.band_h,
            interpret=False, vmem_limit_bytes=p.vmem_limit_bytes,
            active=active, bands_per_image=p.n_bands)

    return _compile(one_chip, fn, (_planes(p), dtype),
                    (_planes(p), qdt_acc_dtype(dtype)),
                    (_planes(p), jnp.int32),
                    (bands, jnp.int32), (bands, jnp.int32))


def qdt_tile(one_chip, dtype, n, size):
    p = _plan("qdt", dtype, n, size)
    assert p.tile_w
    cells = (p.total_bands, p.n_tiles)

    def fn(f, r, d, base, active):
        return qdt_chain.qdt_tile_step(
            f, r, d, base, fuse_k=p.fuse_k, band_h=p.band_h,
            tile_w=p.tile_w, interpret=False,
            vmem_limit_bytes=p.vmem_limit_bytes, active=active,
            bands_per_image=p.n_bands)

    return _compile(one_chip, fn, (_planes(p), dtype),
                    (_planes(p), qdt_acc_dtype(dtype)),
                    (_planes(p), jnp.int32),
                    (cells, jnp.int32), (cells, jnp.int32))


def qdt_compact(one_chip, dtype, n, size):
    p = _plan("qdt", dtype, n, size)
    patch, mid, flags, tw = _patches(p)
    slots = (flags[0], 1)
    fn = functools.partial(qdt_chain.qdt_compact_step, fuse_k=p.fuse_k,
                           band_h=p.band_h, tile_w=tw, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes)
    return _compile(one_chip, fn, (patch, dtype),
                    (mid, qdt_acc_dtype(dtype)), (mid, jnp.int32),
                    (slots, jnp.int32), (slots, jnp.int32))


def gdt_row(one_chip, dtype, n, size):
    p = _row(_plan("gdt", dtype, n, size))
    fn = functools.partial(gdt_chain.gdt_chain_step, lamb=1.0,
                           fuse_k=p.fuse_k, band_h=p.band_h, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes,
                           bands_per_image=p.n_bands)
    return _compile(one_chip, fn, *[(_planes(p), dtype)] * 3)


def gdt_tile(one_chip, dtype, n, size):
    p = _plan("gdt", dtype, n, size)
    assert p.tile_w
    fn = functools.partial(gdt_chain.gdt_tile_step, lamb=1.0,
                           fuse_k=p.fuse_k, band_h=p.band_h,
                           tile_w=p.tile_w, interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes,
                           bands_per_image=p.n_bands)
    return _compile(one_chip, fn, *[(_planes(p), dtype)] * 3)


def gdt_compact(one_chip, dtype, n, size):
    p = _plan("gdt", dtype, n, size)
    patch, _, flags, tw = _patches(p)
    fn = functools.partial(gdt_chain.gdt_compact_step, lamb=1.0,
                           fuse_k=p.fuse_k, band_h=p.band_h, tile_w=tw,
                           interpret=False,
                           vmem_limit_bytes=p.vmem_limit_bytes)
    return _compile(one_chip, fn, *[(patch, dtype)] * 3, (flags, jnp.int32))


#: (builder, dtypes it serves): the ten ``pallas_call``s.  gdt iterates
#: a float distance lattice, so it has no integer case.
KERNELS = {
    "erode_row": (erode_row, ("uint8", "float32")),
    "geodesic_row": (geodesic_row, ("uint8", "float32")),
    "geodesic_tile": (geodesic_tile, ("uint8", "float32")),
    "geodesic_compact": (geodesic_compact, ("uint8", "float32")),
    "qdt_row": (qdt_row, ("uint8", "float32")),
    "qdt_tile": (qdt_tile, ("uint8", "float32")),
    "qdt_compact": (qdt_compact, ("uint8", "float32")),
    "gdt_row": (gdt_row, ("float32",)),
    "gdt_tile": (gdt_tile, ("float32",)),
    "gdt_compact": (gdt_compact, ("float32",)),
}

CASES = [
    pytest.param(name, dtype, n, id=f"{name}-{dtype}-n{n}")
    for name, (_, dtypes) in KERNELS.items()
    for dtype in dtypes
    for n in (1, 4)
]


@pytest.mark.parametrize("name,dtype,n", CASES)
def test_kernel_compiles_for_v5e(one_chip, name, dtype, n):
    build, _ = KERNELS[name]
    compiled = build(one_chip, jnp.dtype(dtype), n, SIZE)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # each of the ten kernels carries its own, distinct name
    assert set(KERNEL_NAME.findall(text)) == {name}


@pytest.mark.parametrize("name,dtype", [
    pytest.param(name, dtype, id=f"{name}-{dtype}")
    for name, (_, dtypes) in KERNELS.items() if name.endswith("_row")
    for dtype in dtypes
])
def test_wide_row_kernel_fits_plan_vmem(one_chip, name, dtype):
    # 2048 px rows (a 4096² frame's shard on a 2x2 mesh): the widest
    # blocks the planner sizes, where its VMEM model has least slack
    build, _ = KERNELS[name]
    compiled = build(one_chip, jnp.dtype(dtype), 1, 2 * SIZE)
    assert "tpu_custom_call" in compiled.as_text()
