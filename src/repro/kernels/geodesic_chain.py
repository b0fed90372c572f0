"""Fused K-step elementary geodesic erosion/dilation with convergence
flag — Algorithm 4 of the paper as a Pallas kernel.

Each of the K fused steps applies ε₁ then clamps with the mask
(max(·, m) for erosion, min(·, m) for dilation) — the geodesic clamp is
pointwise, so halo recompute stays exact as long as the mask halo is
available too.

Padding contract (enforced by kernels.ops): the *mask* padding pins the
pad region to the lattice identity of the marker (mask = +max for
geodesic erosion ⇒ padded marker rows stay +max forever), so no value
can propagate through the padding and the border-clipped semantics of
the paper are preserved exactly — including for geodesic paths, where
the convexity argument alone would not suffice (a path through padding
would dodge intermediate mask clamps).

Convergence: the per-tile flag is 1 iff any centre pixel changed during
the chunk.  Because the geodesic sequence is pointwise monotone, "no
centre pixel anywhere changed across K steps" ⇔ global fixpoint of ε₁ᵐ
— this is the kernel-level version of the paper's ``converged`` flag +
requeue mechanism.

Requeue scheduling (this file's side of it — the driver's side lives in
``kernels.ops`` and is documented in ``docs/ARCHITECTURE.md``): each
scheduling cell carries an ``active`` scalar.  When 0, the kernel
early-outs under ``pl.when`` and writes the input through unchanged
with a zero flag — the skipped cell costs one VMEM copy instead of K
elementary filters.  Three grid shapes share the one kernel body:

* ``geodesic_chain_step`` — 1-D grid of full-width row bands (the
  paper's Alg. 4 granularity); cells are bands.
* ``geodesic_tile_step`` — 2-D grid of (row band × column tile) cells;
  each grid step assembles a (band_h + 2K, tile_w + 2K) stack from the
  nine neighbouring blocks so a narrow *vertical* wavefront can skip
  the quiet column strips too.  Exact for
  ``fuse_k <= min(band_h, tile_w)``.
* ``geodesic_compact_step`` — 1-D grid over driver-gathered patches of
  the active cells (compaction; halos pre-pinned by the gather).

Batching: the driver stacks N images vertically into one
(N·H_pad, W) array; ``bands_per_image`` makes the halo pinning happen
at *image* edges so nothing leaks between stacked images.  Horizontal
image edges coincide with the array edges (images never stack
sideways), so column-halo pinning is per-tile-row (``tile_edges``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (assemble_tile, changed_flag, elementary_3x3,
                                  fused_steps, ident_for, image_edges,
                                  kernel_name, narrow, row_specs, smem_spec,
                                  tile_edges, tile_specs, widen)

#: Names of the row-band, tile and compact kernels (``pallas_call``
#: ``name=`` and ``kernel_metadata``).
ROW_KERNEL = "geodesic_row"
TILE_KERNEL = "geodesic_tile"
COMPACT_KERNEL = "geodesic_compact"


def _geodesic_steps(stack, mask, op: str, fuse_k: int):
    """K elementary geodesic steps: ε₁/δ₁, then the mask clamp."""
    clamp = jnp.maximum if op == "erode" else jnp.minimum
    return fused_steps(lambda x: clamp(elementary_3x3(x, op), mask), stack,
                       fuse_k)


def _geodesic_kernel(
    active, f_top, f_mid, f_bot, m_top, m_mid, m_bot, out, changed,
    *, op: str, fuse_k: int, band_h: int, bands_per_image: int,
):
    # program_id must be read outside the pl.when bodies (the branches
    # are compiled as plain cond branches in interpret mode, where the
    # primitive has no lowering).
    i = pl.program_id(0)
    at_top, at_bot = image_edges(i, bands_per_image)

    @pl.when(active[i] == 0)
    def _passthrough():
        # converged band: pass the input through, report no change.
        out[...] = f_mid[...]
        changed[i] = 0

    @pl.when(active[i] > 0)
    def _compute():
        ident = widen(ident_for(op, f_mid.dtype))
        # Pin the out-of-image halo: marker ← identity, mask ←
        # identity, so the pad region is absorbing and transmits
        # nothing (also between stacked batch images).
        ftop = jnp.where(at_top, ident, widen(f_top[...]))
        fbot = jnp.where(at_bot, ident, widen(f_bot[...]))
        mtop = jnp.where(at_top, ident, widen(m_top[...]))
        mbot = jnp.where(at_bot, ident, widen(m_bot[...]))

        f0 = widen(f_mid[...])
        stack = jnp.concatenate([ftop, f0, fbot], axis=0)
        mask = jnp.concatenate([mtop, widen(m_mid[...]), mbot], axis=0)
        stack = _geodesic_steps(stack, mask, op, fuse_k)

        centre = stack[fuse_k : fuse_k + band_h, :]
        out[...] = narrow(centre, out.dtype)
        changed[i] = changed_flag(centre, f0)


def geodesic_chain_step(
    f: jnp.ndarray,
    m: jnp.ndarray,
    *,
    op: str,
    fuse_k: int,
    band_h: int,
    interpret: bool,
    vmem_limit_bytes: int,
    active: jnp.ndarray | None = None,
    bands_per_image: int | None = None,
):
    """K fused geodesic steps on a pre-padded marker/mask (stack).

    ``f``/``m`` are (H, W) with H a multiple of ``band_h`` — possibly a
    vertical stack of ``H // (bands_per_image · band_h)`` images.
    ``active`` is an optional (n_bands, 1) int32 activity vector; bands
    with 0 are skipped (input copied through, flag 0).  ``interpret``
    runs the kernel in the Pallas interpreter (off-TPU validation).

    Returns (new_marker, changed) with changed an (n_bands, 1) int32.
    """
    h, w = f.shape
    assert f.shape == m.shape
    assert h % band_h == 0 and band_h % fuse_k == 0
    n_bands = h // band_h
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0
    if active is None:
        active = jnp.ones((n_bands, 1), jnp.int32)

    plane = row_specs(band_h, fuse_k, h, w)
    out_spec = pl.BlockSpec((band_h, w), lambda i: (i, 0))

    kern = functools.partial(
        _geodesic_kernel, op=op, fuse_k=fuse_k, band_h=band_h,
        bands_per_image=bands_per_image,
    )
    out, changed = pl.pallas_call(
        kern,
        grid=(n_bands,),
        in_specs=[smem_spec()] + plane + plane,
        out_specs=[out_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), f.dtype),
            jax.ShapeDtypeStruct((n_bands,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(ROW_KERNEL),
    )(active.reshape(n_bands), f, f, f, m, m, m)
    return out, changed.reshape(n_bands, 1)


def _geodesic_tile_kernel(
    active, *refs,
    op: str, fuse_k: int, band_h: int, tile_w: int,
    bands_per_image: int, n_tiles: int,
):
    """2-D grid body: ``refs`` are 9 marker blocks, 9 mask blocks, then
    the (out, changed) outputs."""
    f_parts, m_parts = refs[:9], refs[9:18]
    out, changed = refs[18], refs[19]
    f_mid = f_parts[4]
    bi, tj = pl.program_id(0), pl.program_id(1)
    cell = bi * n_tiles + tj
    at_top, at_bot = image_edges(bi, bands_per_image)
    at_lf, at_rt = tile_edges(tj, n_tiles)
    edges = (at_top, at_bot, at_lf, at_rt)

    @pl.when(active[cell] == 0)
    def _passthrough():
        out[...] = f_mid[...]
        changed[cell] = 0

    @pl.when(active[cell] > 0)
    def _compute():
        ident = widen(ident_for(op, f_mid.dtype))
        stack = assemble_tile(f_parts, edges, ident)
        mask = assemble_tile(m_parts, edges, ident)
        stack = _geodesic_steps(stack, mask, op, fuse_k)

        centre = stack[fuse_k : fuse_k + band_h, fuse_k : fuse_k + tile_w]
        out[...] = narrow(centre, out.dtype)
        changed[cell] = changed_flag(centre, widen(f_mid[...]))


def geodesic_tile_step(
    f: jnp.ndarray,
    m: jnp.ndarray,
    *,
    op: str,
    fuse_k: int,
    band_h: int,
    tile_w: int,
    interpret: bool,
    vmem_limit_bytes: int,
    active: jnp.ndarray | None = None,
    bands_per_image: int | None = None,
):
    """K fused geodesic steps on the 2-D (band × column-tile) grid.

    Same contract as :func:`geodesic_chain_step` with the width split
    into ``W // tile_w`` column tiles: ``active``/``changed`` are
    (n_bands, n_tiles) int32 grids and inactive *tiles* (not just
    bands) early-out.  Requires ``tile_w % fuse_k == 0`` and
    ``W % tile_w == 0`` (``ChainPlan`` validates the same).
    """
    h, w = f.shape
    assert f.shape == m.shape
    assert h % band_h == 0 and band_h % fuse_k == 0
    assert w % tile_w == 0 and tile_w % fuse_k == 0
    n_bands = h // band_h
    n_tiles = w // tile_w
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0
    if active is None:
        active = jnp.ones((n_bands, n_tiles), jnp.int32)

    plane = tile_specs(band_h, tile_w, fuse_k, h, w)
    out_spec = pl.BlockSpec((band_h, tile_w), lambda i, j: (i, j))
    kern = functools.partial(
        _geodesic_tile_kernel, op=op, fuse_k=fuse_k, band_h=band_h,
        tile_w=tile_w, bands_per_image=bands_per_image, n_tiles=n_tiles,
    )
    out, changed = pl.pallas_call(
        kern,
        grid=(n_bands, n_tiles),
        in_specs=[smem_spec()] + plane + plane,
        out_specs=[out_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), f.dtype),
            jax.ShapeDtypeStruct((n_bands * n_tiles,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(TILE_KERNEL),
    )(active.reshape(n_bands * n_tiles), *([f] * 9), *([m] * 9))
    return out, changed.reshape(n_bands, n_tiles)


def _geodesic_compact_kernel(
    valid, f_patch, m_patch, out, changed,
    *, op: str, fuse_k: int, band_h: int, tile_w: int,
):
    lo, hi = fuse_k, fuse_k + band_h
    cl, cr = fuse_k, fuse_k + tile_w
    c = pl.program_id(0)

    @pl.when(valid[c] == 0)
    def _passthrough():
        out[...] = f_patch[lo:hi, cl:cr]
        changed[c] = 0

    @pl.when(valid[c] > 0)
    def _compute():
        stack = widen(f_patch[...])
        centre0 = stack[lo:hi, cl:cr]
        stack = _geodesic_steps(stack, widen(m_patch[...]), op, fuse_k)
        centre = stack[lo:hi, cl:cr]
        out[...] = narrow(centre, out.dtype)
        changed[c] = changed_flag(centre, centre0)


def geodesic_compact_step(
    f_patch: jnp.ndarray,
    m_patch: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    op: str,
    fuse_k: int,
    band_h: int,
    tile_w: int,
    interpret: bool,
    vmem_limit_bytes: int,
):
    """Compacted-grid variant: the driver has already gathered each
    active cell into a (band_h + 2K, tile_w + 2K) *patch* — centre plus
    halos on all four sides, image-edge pinning applied by the gather
    (the kernel cannot know slot → image geometry).  Block ``i`` reads
    slot ``i``; ``valid`` masks workspace slots past the true active
    count (their output is dropped at scatter time anyway).

    Shapes: f_patch/m_patch (C·(band_h+2K), tile_w+2K); valid (C, 1)
    int32.  Returns (new_mid (C·band_h, tile_w), changed (C, 1)).
    Row-only plans use this too, with ``tile_w = width_pad``.
    """
    ph = band_h + 2 * fuse_k
    pw = tile_w + 2 * fuse_k
    assert f_patch.shape == m_patch.shape and f_patch.shape[1] == pw
    assert f_patch.shape[0] % ph == 0
    cap = f_patch.shape[0] // ph

    patch_spec = pl.BlockSpec((ph, pw), lambda i: (i, 0))
    mid_spec = pl.BlockSpec((band_h, tile_w), lambda i: (i, 0))

    kern = functools.partial(
        _geodesic_compact_kernel, op=op, fuse_k=fuse_k, band_h=band_h,
        tile_w=tile_w,
    )
    out, changed = pl.pallas_call(
        kern,
        grid=(cap,),
        in_specs=[smem_spec(), patch_spec, patch_spec],
        out_specs=[mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((cap * band_h, tile_w), f_patch.dtype),
            jax.ShapeDtypeStruct((cap,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(COMPACT_KERNEL),
    )(valid.reshape(cap), f_patch, m_patch)
    return out, changed.reshape(cap, 1)
