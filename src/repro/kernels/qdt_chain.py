"""Fused quasi-distance-transform chunk — Algorithm 5 of the paper.

Each of the K fused steps computes ε₁, the residual B = f − ε₁(f), and
performs the *masked store* update of the residual plane r(f) and the
distance plane d(f) (update only where the new residual exceeds the
stored one).  The paper uses AVX2 masked stores for this; on TPU the
masked store is a vectorized ``jnp.where`` on the VMEM tile.

r/d only need the centre window (their update is pointwise), so they
are blocked without halo — only the eroding image carries the K-pixel
halo.

Like the geodesic kernel, each scheduling cell carries an ``active``
scalar: once a cell's erosion has reached the lattice bottom everywhere
(no pixel changed, nor in its neighbours), the driver stops requeueing
it and the kernel passes f/r/d through unchanged under ``pl.when``.
The same three grid shapes exist as in ``geodesic_chain``:
``qdt_chain_step`` (full-width row bands), ``qdt_tile_step`` (2-D
band × column-tile grid) and ``qdt_compact_step`` (dense workspace of
driver-gathered patches).  The scheduler lifecycle these plug into is
documented in ``docs/ARCHITECTURE.md``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (assemble_tile, changed_flag, elementary_3x3,
                                  ident_for, image_edges, kernel_name,
                                  narrow, qdt_acc_dtype, row_specs, smem_spec,
                                  tile_edges, tile_specs, widen)

#: Names of the row-band, tile and compact kernels (``pallas_call``
#: ``name=`` and ``kernel_metadata``).
ROW_KERNEL = "qdt_row"
TILE_KERNEL = "qdt_tile"
COMPACT_KERNEL = "qdt_compact"


def _qdt_update(stack, r, d, j0, window, *, fuse_k: int, acc_dtype):
    """The K-step masked-store loop shared by every QDT grid shape.

    ``window`` slices the centre (band_h, tile_w) region out of the
    halo-extended ``stack`` (already in the work dtype); r/d are
    centre-only.  Returns the final centre, r, d."""
    (lo, hi), (cl, cr) = window

    def step(k, carry):
        stack, r, d = carry
        nxt = elementary_3x3(stack, "erode")
        res = (stack[lo:hi, cl:cr].astype(acc_dtype)
               - nxt[lo:hi, cl:cr].astype(acc_dtype))
        upd = res > r
        return nxt, jnp.where(upd, res, r), jnp.where(upd, j0 + k + 1, d)

    stack, r, d = jax.lax.fori_loop(0, fuse_k, step, (stack, r, d))
    return stack[lo:hi, cl:cr], r, d


def _qdt_kernel(
    base, active, f_top, f_mid, f_bot, r_in, d_in, f_out, r_out, d_out,
    changed,
    *, fuse_k: int, band_h: int, acc_dtype, bands_per_image: int,
):
    # ``base`` holds one entry per band: each band reads the
    # elementary-erosion count already applied to *its image*, so
    # ragged-converged stacks keep per-image distance indices (a
    # finished image's counter stops advancing with the rest of the
    # batch).
    # program_id is not available inside pl.when branches in interpret
    # mode — read it at kernel top level.
    i = pl.program_id(0)
    at_top, at_bot = image_edges(i, bands_per_image)

    @pl.when(active[i] == 0)
    def _passthrough():
        # converged band: pass all planes through, report no change.
        f_out[...] = f_mid[...]
        r_out[...] = r_in[...]
        d_out[...] = d_in[...]
        changed[i] = 0

    @pl.when(active[i] > 0)
    def _compute():
        ident = widen(ident_for("erode", f_mid.dtype))
        top = jnp.where(at_top, ident, widen(f_top[...]))
        bot = jnp.where(at_bot, ident, widen(f_bot[...]))
        f0 = widen(f_mid[...])
        stack = jnp.concatenate([top, f0, bot], axis=0)

        w = f_mid.shape[1]
        centre, r, d = _qdt_update(
            stack, r_in[...], d_in[...], base[i],
            ((fuse_k, fuse_k + band_h), (0, w)),
            fuse_k=fuse_k, acc_dtype=acc_dtype,
        )
        f_out[...] = narrow(centre, f_out.dtype)
        r_out[...] = r
        d_out[...] = d
        changed[i] = changed_flag(centre, f0)


def qdt_chain_step(
    f: jnp.ndarray,
    r: jnp.ndarray,
    d: jnp.ndarray,
    base: jnp.ndarray,
    *,
    fuse_k: int,
    band_h: int,
    interpret: bool,
    vmem_limit_bytes: int,
    active: jnp.ndarray | None = None,
    bands_per_image: int | None = None,
):
    """One K-step QDT chunk on pre-padded planes.

    ``base`` is an (n_bands, 1) int32 with the number of elementary
    erosions already applied to each band's image — per *band* so the
    batched driver can give every stacked image its own distance offset
    (a (1, 1) array is broadcast for the unbatched callers).
    ``active`` optionally skips converged bands (see module docstring);
    ``interpret`` runs the kernel in the Pallas interpreter.
    Returns (f', r', d', changed) — changed is (n_bands, 1) int32.
    """
    h, w = f.shape
    assert h % band_h == 0 and band_h % fuse_k == 0
    n_bands = h // band_h
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0
    if active is None:
        active = jnp.ones((n_bands, 1), jnp.int32)
    if base.shape == (1, 1):
        base = jnp.broadcast_to(base, (n_bands, 1))
    assert base.shape == (n_bands, 1)
    acc_dtype = qdt_acc_dtype(f.dtype)
    assert r.dtype == acc_dtype and d.dtype == jnp.int32

    top_spec, mid_spec, bot_spec = row_specs(band_h, fuse_k, h, w)

    kern = functools.partial(
        _qdt_kernel, fuse_k=fuse_k, band_h=band_h, acc_dtype=acc_dtype,
        bands_per_image=bands_per_image,
    )
    f2, r2, d2, changed = pl.pallas_call(
        kern,
        grid=(n_bands,),
        in_specs=[smem_spec(), smem_spec(), top_spec, mid_spec, bot_spec,
                  mid_spec, mid_spec],
        out_specs=[mid_spec, mid_spec, mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), f.dtype),
            jax.ShapeDtypeStruct((h, w), acc_dtype),
            jax.ShapeDtypeStruct((h, w), jnp.int32),
            jax.ShapeDtypeStruct((n_bands,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(ROW_KERNEL),
    )(base.reshape(n_bands), active.reshape(n_bands), f, f, f, r, d)
    return f2, r2, d2, changed.reshape(n_bands, 1)


def _qdt_tile_kernel(
    base, active, *refs,
    fuse_k: int, band_h: int, tile_w: int, acc_dtype,
    bands_per_image: int, n_tiles: int,
):
    """2-D grid body: ``refs`` are 9 f blocks, r_in, d_in, then the
    (f_out, r_out, d_out, changed) outputs."""
    f_parts = refs[:9]
    r_in, d_in = refs[9], refs[10]
    f_out, r_out, d_out, changed = refs[11:]
    f_mid = f_parts[4]
    bi, tj = pl.program_id(0), pl.program_id(1)
    cell = bi * n_tiles + tj
    at_top, at_bot = image_edges(bi, bands_per_image)
    at_lf, at_rt = tile_edges(tj, n_tiles)

    @pl.when(active[cell] == 0)
    def _passthrough():
        f_out[...] = f_mid[...]
        r_out[...] = r_in[...]
        d_out[...] = d_in[...]
        changed[cell] = 0

    @pl.when(active[cell] > 0)
    def _compute():
        ident = widen(ident_for("erode", f_mid.dtype))
        stack = assemble_tile(f_parts, (at_top, at_bot, at_lf, at_rt), ident)
        centre, r, d = _qdt_update(
            stack, r_in[...], d_in[...], base[cell],
            ((fuse_k, fuse_k + band_h), (fuse_k, fuse_k + tile_w)),
            fuse_k=fuse_k, acc_dtype=acc_dtype,
        )
        f_out[...] = narrow(centre, f_out.dtype)
        r_out[...] = r
        d_out[...] = d
        changed[cell] = changed_flag(centre, widen(f_mid[...]))


def qdt_tile_step(
    f: jnp.ndarray,
    r: jnp.ndarray,
    d: jnp.ndarray,
    base: jnp.ndarray,
    *,
    fuse_k: int,
    band_h: int,
    tile_w: int,
    interpret: bool,
    vmem_limit_bytes: int,
    active: jnp.ndarray | None = None,
    bands_per_image: int | None = None,
):
    """One K-step QDT chunk on the 2-D (band × column-tile) grid.

    Same contract as :func:`qdt_chain_step` with the width split into
    ``W // tile_w`` column tiles: ``base``/``active``/``changed`` are
    (n_bands, n_tiles) int32 grids (``base`` stays per-*image*; the
    driver broadcasts it across each band's tiles).
    """
    h, w = f.shape
    assert h % band_h == 0 and band_h % fuse_k == 0
    assert w % tile_w == 0 and tile_w % fuse_k == 0
    n_bands = h // band_h
    n_tiles = w // tile_w
    n_cells = n_bands * n_tiles
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0
    if active is None:
        active = jnp.ones((n_bands, n_tiles), jnp.int32)
    if base.shape == (1, 1):
        base = jnp.broadcast_to(base, (n_bands, n_tiles))
    assert base.shape == (n_bands, n_tiles)
    acc_dtype = qdt_acc_dtype(f.dtype)
    assert r.dtype == acc_dtype and d.dtype == jnp.int32

    mid_spec = pl.BlockSpec((band_h, tile_w), lambda i, j: (i, j))
    plane = tile_specs(band_h, tile_w, fuse_k, h, w)
    kern = functools.partial(
        _qdt_tile_kernel, fuse_k=fuse_k, band_h=band_h, tile_w=tile_w,
        acc_dtype=acc_dtype, bands_per_image=bands_per_image,
        n_tiles=n_tiles,
    )
    f2, r2, d2, changed = pl.pallas_call(
        kern,
        grid=(n_bands, n_tiles),
        in_specs=[smem_spec(), smem_spec()] + plane + [mid_spec, mid_spec],
        out_specs=[mid_spec, mid_spec, mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), f.dtype),
            jax.ShapeDtypeStruct((h, w), acc_dtype),
            jax.ShapeDtypeStruct((h, w), jnp.int32),
            jax.ShapeDtypeStruct((n_cells,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(TILE_KERNEL),
    )(base.reshape(n_cells), active.reshape(n_cells), *([f] * 9), r, d)
    return f2, r2, d2, changed.reshape(n_bands, n_tiles)


def _qdt_compact_kernel(
    base, valid, f_patch, r_in, d_in, f_out, r_out, d_out, changed,
    *, fuse_k: int, band_h: int, tile_w: int, acc_dtype,
):
    lo, hi = fuse_k, fuse_k + band_h
    cl, cr = fuse_k, fuse_k + tile_w
    c = pl.program_id(0)

    @pl.when(valid[c] == 0)
    def _passthrough():
        f_out[...] = f_patch[lo:hi, cl:cr]
        r_out[...] = r_in[...]
        d_out[...] = d_in[...]
        changed[c] = 0

    @pl.when(valid[c] > 0)
    def _compute():
        stack = widen(f_patch[...])
        centre0 = stack[lo:hi, cl:cr]
        centre, r, d = _qdt_update(
            stack, r_in[...], d_in[...], base[c],
            ((lo, hi), (cl, cr)), fuse_k=fuse_k, acc_dtype=acc_dtype,
        )
        f_out[...] = narrow(centre, f_out.dtype)
        r_out[...] = r
        d_out[...] = d
        changed[c] = changed_flag(centre, centre0)


def qdt_compact_step(
    f_patch: jnp.ndarray,
    r_mid: jnp.ndarray,
    d_mid: jnp.ndarray,
    valid: jnp.ndarray,
    base: jnp.ndarray,
    *,
    fuse_k: int,
    band_h: int,
    tile_w: int,
    interpret: bool,
    vmem_limit_bytes: int,
):
    """Compacted-grid QDT chunk on driver-gathered active cells.

    Shapes mirror ``geodesic_compact_step``: f_patch
    (C·(band_h+2K), tile_w+2K) with halos pre-pinned by the gather,
    r_mid/d_mid (C·band_h, tile_w) centre-only, valid/base (C, 1) int32
    — the driver gathers each active cell's per-image erosion count
    into its workspace slot (a (1, 1) array is broadcast).  Returns
    (f', r', d', changed); row-only plans use ``tile_w = width_pad``.
    """
    ph = band_h + 2 * fuse_k
    assert f_patch.shape[1] == tile_w + 2 * fuse_k
    assert f_patch.shape[0] % ph == 0
    cap = f_patch.shape[0] // ph
    acc_dtype = qdt_acc_dtype(f_patch.dtype)
    assert r_mid.dtype == acc_dtype and d_mid.dtype == jnp.int32
    assert r_mid.shape == d_mid.shape == (cap * band_h, tile_w)
    if base.shape == (1, 1):
        base = jnp.broadcast_to(base, (cap, 1))
    assert base.shape == (cap, 1)

    patch_spec = pl.BlockSpec((ph, tile_w + 2 * fuse_k), lambda i: (i, 0))
    mid_spec = pl.BlockSpec((band_h, tile_w), lambda i: (i, 0))

    kern = functools.partial(
        _qdt_compact_kernel, fuse_k=fuse_k, band_h=band_h, tile_w=tile_w,
        acc_dtype=acc_dtype,
    )
    f2, r2, d2, changed = pl.pallas_call(
        kern,
        grid=(cap,),
        in_specs=[smem_spec(), smem_spec(), patch_spec, mid_spec, mid_spec],
        out_specs=[mid_spec, mid_spec, mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((cap * band_h, tile_w), f_patch.dtype),
            jax.ShapeDtypeStruct((cap * band_h, tile_w), acc_dtype),
            jax.ShapeDtypeStruct((cap * band_h, tile_w), jnp.int32),
            jax.ShapeDtypeStruct((cap,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(COMPACT_KERNEL),
    )(base.reshape(cap), valid.reshape(cap), f_patch, r_mid, d_mid)
    return f2, r2, d2, changed.reshape(cap, 1)
