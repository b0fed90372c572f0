"""Shared in-kernel helpers for the fused morphology Pallas kernels.

Everything here executes inside a Pallas kernel body on VMEM-resident
values.  The 1-D passes mirror the paper's decomposed SIMD kernels
(Fig. 2): three displaced views min/max-ed together — on TPU the
"displaced registers" are lane/sublane shifts of a vreg tile.  The
edge/identity-pinning helpers implement the bit-exactness contract
documented in ``docs/ARCHITECTURE.md``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.chain import side_width, work_dtype


def widen(x):
    """``x`` in its :func:`work_dtype` (a no-op for 32-bit dtypes).
    Narrow integers pass through int32: Mosaic refuses a direct
    ``uint8 -> float32`` cast."""
    wide = work_dtype(x.dtype)
    if wide == x.dtype:
        return x
    return x.astype(jnp.int32).astype(wide)


def narrow(x, dtype):
    """``x``, in the work dtype of ``dtype``, back in ``dtype``: the
    inverse of :func:`widen`, through int32 for the same reason."""
    if x.dtype == dtype:
        return x
    return x.astype(jnp.int32).astype(dtype)


def smem_spec():
    """A whole-array block in SMEM: the per-cell scalar flags
    (``active``/``valid``/``base`` in, ``changed`` out) are indexed by
    grid position inside the kernel instead of riding (1, 1) VMEM
    blocks, which Mosaic refuses."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def kernel_name(name: str) -> dict:
    """``pallas_call`` keyword arguments that name a kernel: ``name=``
    titles the Mosaic kernel, and ``metadata={"kernel": name}`` lands in
    the custom call's ``kernel_metadata`` frontend attribute, which a
    TPU profiler trace keeps in each launch's event text (the trace has
    no other name for a kernel)."""
    return {"name": name, "metadata": {"kernel": name}}


def changed_flag(new, old):
    """1 iff any element of ``new`` differs from ``old`` (int32 scalar)."""
    return jnp.max((new != old).astype(jnp.int32))


def fused_steps(step, x, fuse_k: int):
    """``step`` applied ``fuse_k`` times.  A loop, not an unrolled
    Python ``for``: the kernel's code (and its compile time) then does
    not grow with K."""
    return jax.lax.fori_loop(0, fuse_k, lambda _, y: step(y), x)


def image_edges(i, bands_per_image: int):
    """(at_top, at_bot) for grid step ``i`` of a vertically stacked batch.

    The drivers lay N images out as one (N·H_pad, W) array; band
    ``i`` is the ``i % bands_per_image``-th band of its image, and halo
    pinning must happen at *image* edges (not stack edges) so values
    never propagate between images.
    """
    j = i % bands_per_image
    return j == 0, j == bands_per_image - 1


def tile_edges(j, n_tiles: int):
    """(at_left, at_right) for column-tile ``j`` of a ``n_tiles``-wide
    activity grid.  Images are only ever stacked *vertically*, so the
    horizontal image edges coincide with the array edges — the first and
    last tile pin their column halos to the identity exactly like the
    row axis pins at image edges."""
    return j == 0, j == n_tiles - 1


def row_specs(band_h: int, fuse_k: int, h: int, w: int):
    """The three BlockSpecs feeding one full-width row band of a 1-D
    grid its K-row top halo, centre band and K-row bottom halo, in
    (top, mid, bot) order.  Halo index maps clamp at the array border;
    the kernels pin clamped out-of-image reads to the lattice identity
    (``image_edges``).  Shared by every row-band kernel
    (``erode_chain``, ``geodesic_chain``, ``qdt_chain``) and evaluated
    symbolically by ``repro.analysis.indexmaps`` — the bounds the
    verifier proves are the bounds the kernels run with.
    """
    r = band_h // fuse_k   # fuse_k-row halo blocks per band
    last_k_block = h // fuse_k - 1
    return [
        # K-row halo above the band (clamped at the stack top)
        pl.BlockSpec((fuse_k, w), lambda i: (jnp.maximum(i * r - 1, 0), 0)),
        # the band itself
        pl.BlockSpec((band_h, w), lambda i: (i, 0)),
        # K-row halo below the band (clamped at the stack bottom)
        pl.BlockSpec(
            (fuse_k, w),
            lambda i: (jnp.minimum((i + 1) * r, last_k_block), 0),
        ),
    ]


def tile_specs(band_h: int, tile_w: int, fuse_k: int, h: int, w: int):
    """The nine BlockSpecs feeding one (band_h, tile_w) cell of a 2-D
    grid its centre block and eight clamped neighbour halos, in
    ``assemble_tile`` order (tl, top, tr, left, mid, right, bl, bot,
    br).  Clamped edge reads are pinned in-kernel.  The corner and side
    halos are ``side_width(tile_w)`` lanes wide, so every block is
    lane-aligned; ``assemble_tile`` slices their ``fuse_k`` lanes.
    """
    sw = side_width(tile_w)
    r = band_h // fuse_k   # fuse_k-row blocks per band
    c = tile_w // sw       # side-width blocks per tile
    last_r = h // fuse_k - 1
    last_c = w // sw - 1

    def up(i):
        return jnp.maximum(i * r - 1, 0)

    def dn(i):
        return jnp.minimum((i + 1) * r, last_r)

    def lf(j):
        return jnp.maximum(j * c - 1, 0)

    def rt(j):
        return jnp.minimum((j + 1) * c, last_c)

    kk, kw, bk = (fuse_k, sw), (fuse_k, tile_w), (band_h, sw)
    return [
        pl.BlockSpec(kk, lambda i, j: (up(i), lf(j))),
        pl.BlockSpec(kw, lambda i, j: (up(i), j)),
        pl.BlockSpec(kk, lambda i, j: (up(i), rt(j))),
        pl.BlockSpec(bk, lambda i, j: (i, lf(j))),
        pl.BlockSpec((band_h, tile_w), lambda i, j: (i, j)),
        pl.BlockSpec(bk, lambda i, j: (i, rt(j))),
        pl.BlockSpec(kk, lambda i, j: (dn(i), lf(j))),
        pl.BlockSpec(kw, lambda i, j: (dn(i), j)),
        pl.BlockSpec(kk, lambda i, j: (dn(i), rt(j))),
    ]


def assemble_tile(parts, edges, ident):
    """Assemble one (band_h + 2K, tile_w + 2K) working stack, in the
    work dtype, from the nine blocks of a 2-D tiled grid step, pinning
    out-of-image halos.

    ``parts`` are the (tl, top, tr, left, mid, right, bl, bot, br)
    kernel refs; ``edges`` the (at_top, at_bot, at_left, at_right)
    scalars for this grid step; ``ident`` the pin value in the work
    dtype.  The left-hand blocks contribute their last K lanes and the
    right-hand ones their first K (see ``tile_specs``).  Edge halos
    read *clamped* blocks (the BlockSpec index maps clip at the array
    border), so every block whose true source lies outside the image is
    replaced with ``ident`` here — corners pin when either of their two
    axes is at an edge.  The result is the 2-D analogue of the row
    kernels' top/mid/bot concatenation: after K elementary steps the
    centre (band_h, tile_w) window is exact.
    """
    tl, top, tr, lf, mid, rt, bl, bot, br = parts
    at_top, at_bot, at_lf, at_rt = edges
    k = top.shape[0]

    def left(ref):
        return widen(ref[...][:, ref.shape[1] - k:])

    def right(ref):
        return widen(ref[...][:, :k])

    row_t = jnp.concatenate([
        jnp.where(jnp.logical_or(at_top, at_lf), ident, left(tl)),
        jnp.where(at_top, ident, widen(top[...])),
        jnp.where(jnp.logical_or(at_top, at_rt), ident, right(tr)),
    ], axis=1)
    row_m = jnp.concatenate([
        jnp.where(at_lf, ident, left(lf)),
        widen(mid[...]),
        jnp.where(at_rt, ident, right(rt)),
    ], axis=1)
    row_b = jnp.concatenate([
        jnp.where(jnp.logical_or(at_bot, at_lf), ident, left(bl)),
        jnp.where(at_bot, ident, widen(bot[...])),
        jnp.where(jnp.logical_or(at_bot, at_rt), ident, right(br)),
    ], axis=1)
    return jnp.concatenate([row_t, row_m, row_b], axis=0)


def qdt_acc_dtype(dtype):
    """Residual-accumulator dtype of the quasi-distance transform: the
    paper's convention is float32 for floating images and int32
    otherwise.  This is the single source of truth — the Pallas QDT
    kernels, the requeue driver and the jnp oracle (``operators.qdt_raw``)
    all call it, which is what keeps the two engines' accumulation
    bit-identical (and what ``repro.analysis.dtypes`` audits for
    overflow headroom per supported dtype).
    """
    return (jnp.float32 if jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
            else jnp.int32)


def ident_for(op: str, dtype):
    """Lattice identity: +max for erosion (min-op), -max for dilation."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        hi, lo = jnp.array(jnp.inf, dtype), jnp.array(-jnp.inf, dtype)
    else:
        info = jnp.iinfo(dtype)
        hi, lo = jnp.array(info.max, dtype), jnp.array(info.min, dtype)
    return hi if op == "erode" else lo


def shift_minmax_1d(x: jnp.ndarray, axis: int, op: str) -> jnp.ndarray:
    """min/max(x, x<<1, x>>1) along ``axis`` with identity fill.

    This is the paper's Algorithm-1 inner step: registers A/B/C are the
    three displaced views; on TPU the displacement is a concat-shift on
    the sublane (axis 0) or lane (axis 1) dimension of the VMEM tile.
    """
    fill_shape = list(x.shape)
    fill_shape[axis] = 1
    fill = jnp.full(fill_shape, ident_for(op, x.dtype), x.dtype)

    idx_fwd = [slice(None)] * x.ndim
    idx_fwd[axis] = slice(1, None)
    idx_bwd = [slice(None)] * x.ndim
    idx_bwd[axis] = slice(0, -1)
    left = jnp.concatenate([x[tuple(idx_fwd)], fill], axis=axis)
    right = jnp.concatenate([fill, x[tuple(idx_bwd)]], axis=axis)

    f = jnp.minimum if op == "erode" else jnp.maximum
    return f(x, f(left, right))


def elementary_3x3(x: jnp.ndarray, op: str) -> jnp.ndarray:
    """ε₁ / δ₁ on a VMEM tile: horizontal then vertical decomposed pass."""
    return shift_minmax_1d(shift_minmax_1d(x, 1, op), 0, op)
