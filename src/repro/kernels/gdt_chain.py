"""Fused generalised-geodesic-distance chunk (the ``gdt`` kernel op).

Each of the K fused steps relaxes the distance plane over the
8-connected neighbourhood with the grey-weighted additive cost

    w(p, q) = 1 + lamb * |I(p) - I(q)|

    D'(p)   = min(D(p), min_q D(q) + w(p, q))

— the FastGeodis/DTOCS generalisation of the paper's elementary
geodesic step (see ``repro.gdt.reference`` for the shared fixpoint
contract and the bit-exactness argument).

Three resident planes ride each scheduling cell:

``d``   the evolving distance plane (the only written plane);
``i``   the grey-weight image (constant; supplies the edge costs);
``s``   the seed plane doubling as the pad marker (constant): the
        driver stages ``s = -1`` on every padded cell, and the kernel
        re-clamps ``d = +inf`` wherever ``s < 0`` after *every*
        elementary step — padding can never propagate finite distances
        into the real region, which is what makes a lone gdt segment
        pad-safe under the usual absorbing-fill argument.

All three planes carry the K-pixel halo (neighbour distances *and*
neighbour grey values feed the relaxation), pinned at image edges to
their absorbing identities: ``d -> +inf``, ``i -> 0``, ``s -> -1``.
``lamb`` is a *static* kernel parameter: ``lamb == 0`` compiles the
constant-weight branch (pure Chebyshev propagation) with no multiply —
and, crucially, no ``0 * inf`` NaN hazard against pinned halos.

The same three grid shapes exist as for reconstruction and the QDT:
``gdt_chain_step`` (full-width row bands), ``gdt_tile_step`` (2-D
band × column-tile grid) and ``gdt_compact_step`` (dense workspace of
driver-gathered patches).  They plug into the same
``_drive_scheduler`` lifecycle (``kernels/ops.py``); the raster-scan
alternative schedule lives in the driver, not here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (assemble_tile, changed_flag, fused_steps,
                                  image_edges, kernel_name, row_specs,
                                  smem_spec, tile_edges, tile_specs)

#: Names of the row-band, tile and compact kernels (``pallas_call``
#: ``name=`` and ``kernel_metadata``).
ROW_KERNEL = "gdt_row"
TILE_KERNEL = "gdt_tile"
COMPACT_KERNEL = "gdt_compact"

#: Absorbing halo/pad identities per plane.
D_IDENT = jnp.inf    # distance: +inf never wins a min
I_IDENT = 0.0        # image: any finite value (weight stays finite)
S_IDENT = -1.0       # seeds: the pad marker the kernel clamps on


def _shift2(x, dy, dx, fill):
    """x translated by (dy, dx) with vacated cells set to ``fill``."""
    h, w = x.shape
    if dy > 0:
        x = jnp.concatenate(
            [jnp.full((dy, w), fill, x.dtype), x[:-dy]], axis=0)
    elif dy < 0:
        x = jnp.concatenate(
            [x[-dy:], jnp.full((-dy, w), fill, x.dtype)], axis=0)
    if dx > 0:
        x = jnp.concatenate(
            [jnp.full((h, dx), fill, x.dtype), x[:, :-dx]], axis=1)
    elif dx < 0:
        x = jnp.concatenate(
            [x[:, -dx:], jnp.full((h, -dx), fill, x.dtype)], axis=1)
    return x



#: The 8-connected neighbourhood.
_OFFSETS = tuple(
    (dy, dx)
    for dy in (-1, 0, 1)
    for dx in (-1, 0, 1)
    if (dy, dx) != (0, 0)
)


def elementary_gdt(d, i, s, lamb: float):
    """One grey-weighted relaxation on a halo-extended stack.

    Shift fills are absorbing (``d`` pulls +inf candidates, ``i`` a
    finite 0), so the outer ring degrades by one valid pixel per step —
    the same halo-shrinkage contract as ``elementary_3x3``.  The final
    ``where`` re-pins every pad cell (``s < 0``) to +inf.
    """
    best = d
    for dy, dx in _OFFSETS:
        dq = _shift2(d, dy, dx, D_IDENT)
        if lamb == 0.0:
            cand = dq + 1.0
        else:
            iq = _shift2(i, dy, dx, I_IDENT)
            # The outer abs is a no-op on the non-negative product but
            # blocks XLA's fmul+fadd→fma contraction, keeping the
            # jitted weight bit-identical to the two-rounding NumPy
            # reference (mul rounds, then add rounds).
            cand = dq + (1.0 + jnp.abs(lamb * jnp.abs(i - iq)))
        best = jnp.minimum(best, cand)
    return jnp.where(s < 0, jnp.asarray(D_IDENT, d.dtype), best)


def _gdt_update(d, i, s, window, *, fuse_k: int, lamb: float):
    """The K-step relaxation loop shared by every gdt grid shape."""
    (lo, hi), (cl, cr) = window
    d = fused_steps(lambda x: elementary_gdt(x, i, s, lamb), d, fuse_k)
    return d[lo:hi, cl:cr]


def _gdt_kernel(
    active, d_top, d_mid, d_bot, i_top, i_mid, i_bot, s_top, s_mid, s_bot,
    d_out, changed,
    *, fuse_k: int, band_h: int, lamb: float, bands_per_image: int,
):
    # program_id is not available inside pl.when branches in interpret
    # mode — read it at kernel top level.
    band = pl.program_id(0)
    at_top, at_bot = image_edges(band, bands_per_image)

    @pl.when(active[band] == 0)
    def _passthrough():
        d_out[...] = d_mid[...]
        changed[band] = 0

    @pl.when(active[band] > 0)
    def _compute():
        def stack3(top, mid, bot, ident):
            t = jnp.where(at_top, jnp.asarray(ident, mid.dtype), top[...])
            b = jnp.where(at_bot, jnp.asarray(ident, mid.dtype), bot[...])
            return jnp.concatenate([t, mid[...], b], axis=0)

        d = stack3(d_top, d_mid, d_bot, D_IDENT)
        i = stack3(i_top, i_mid, i_bot, I_IDENT)
        s = stack3(s_top, s_mid, s_bot, S_IDENT)
        w = d_mid.shape[1]
        centre = _gdt_update(
            d, i, s, ((fuse_k, fuse_k + band_h), (0, w)),
            fuse_k=fuse_k, lamb=lamb,
        )
        d_out[...] = centre
        changed[band] = changed_flag(centre, d_mid[...])


def gdt_chain_step(
    d: jnp.ndarray,
    i: jnp.ndarray,
    s: jnp.ndarray,
    *,
    lamb: float,
    fuse_k: int,
    band_h: int,
    interpret: bool,
    vmem_limit_bytes: int,
    active: jnp.ndarray | None = None,
    bands_per_image: int | None = None,
):
    """One K-step gdt chunk on pre-padded planes (full-width row bands).

    ``d``/``i``/``s`` are same-shaped float planes (see the module
    docstring for their roles); ``active`` optionally skips converged
    bands; ``interpret`` runs the kernel in the Pallas interpreter.
    Returns (d', changed) — changed is (n_bands, 1) int32.
    """
    h, w = d.shape
    assert h % band_h == 0 and band_h % fuse_k == 0
    assert i.shape == s.shape == d.shape
    n_bands = h // band_h
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0
    if active is None:
        active = jnp.ones((n_bands, 1), jnp.int32)

    top_spec, mid_spec, bot_spec = row_specs(band_h, fuse_k, h, w)

    kern = functools.partial(
        _gdt_kernel, fuse_k=fuse_k, band_h=band_h, lamb=float(lamb),
        bands_per_image=bands_per_image,
    )
    d2, changed = pl.pallas_call(
        kern,
        grid=(n_bands,),
        in_specs=[smem_spec(),
                  top_spec, mid_spec, bot_spec,
                  top_spec, mid_spec, bot_spec,
                  top_spec, mid_spec, bot_spec],
        out_specs=[mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), d.dtype),
            jax.ShapeDtypeStruct((n_bands,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(ROW_KERNEL),
    )(active.reshape(n_bands), d, d, d, i, i, i, s, s, s)
    return d2, changed.reshape(n_bands, 1)


def _gdt_tile_kernel(
    active, *refs,
    fuse_k: int, band_h: int, tile_w: int, lamb: float,
    bands_per_image: int, n_tiles: int,
):
    """2-D grid body: ``refs`` are 9 d blocks, 9 i blocks, 9 s blocks,
    then the (d_out, changed) outputs."""
    d_parts, i_parts, s_parts = refs[:9], refs[9:18], refs[18:27]
    d_out, changed = refs[27:]
    d_mid = d_parts[4]
    bi, tj = pl.program_id(0), pl.program_id(1)
    cell = bi * n_tiles + tj
    at_top, at_bot = image_edges(bi, bands_per_image)
    at_lf, at_rt = tile_edges(tj, n_tiles)
    edges = (at_top, at_bot, at_lf, at_rt)

    @pl.when(active[cell] == 0)
    def _passthrough():
        d_out[...] = d_mid[...]
        changed[cell] = 0

    @pl.when(active[cell] > 0)
    def _compute():
        d = assemble_tile(d_parts, edges, jnp.asarray(D_IDENT, d_mid.dtype))
        i = assemble_tile(i_parts, edges, jnp.asarray(I_IDENT, d_mid.dtype))
        s = assemble_tile(s_parts, edges, jnp.asarray(S_IDENT, d_mid.dtype))
        centre = _gdt_update(
            d, i, s,
            ((fuse_k, fuse_k + band_h), (fuse_k, fuse_k + tile_w)),
            fuse_k=fuse_k, lamb=lamb,
        )
        d_out[...] = centre
        changed[cell] = changed_flag(centre, d_mid[...])


def gdt_tile_step(
    d: jnp.ndarray,
    i: jnp.ndarray,
    s: jnp.ndarray,
    *,
    lamb: float,
    fuse_k: int,
    band_h: int,
    tile_w: int,
    interpret: bool,
    vmem_limit_bytes: int,
    active: jnp.ndarray | None = None,
    bands_per_image: int | None = None,
):
    """One K-step gdt chunk on the 2-D (band × column-tile) grid.

    Same contract as :func:`gdt_chain_step` with the width split into
    ``W // tile_w`` column tiles; ``active``/``changed`` are
    (n_bands, n_tiles) int32 grids.
    """
    h, w = d.shape
    assert h % band_h == 0 and band_h % fuse_k == 0
    assert w % tile_w == 0 and tile_w % fuse_k == 0
    assert i.shape == s.shape == d.shape
    n_bands = h // band_h
    n_tiles = w // tile_w
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0
    if active is None:
        active = jnp.ones((n_bands, n_tiles), jnp.int32)

    mid_spec = pl.BlockSpec((band_h, tile_w), lambda b, t: (b, t))
    plane = tile_specs(band_h, tile_w, fuse_k, h, w)
    kern = functools.partial(
        _gdt_tile_kernel, fuse_k=fuse_k, band_h=band_h, tile_w=tile_w,
        lamb=float(lamb), bands_per_image=bands_per_image, n_tiles=n_tiles,
    )
    d2, changed = pl.pallas_call(
        kern,
        grid=(n_bands, n_tiles),
        in_specs=[smem_spec()] + plane + plane + plane,
        out_specs=[mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), d.dtype),
            jax.ShapeDtypeStruct((n_bands * n_tiles,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(TILE_KERNEL),
    )(active.reshape(n_bands * n_tiles), *([d] * 9), *([i] * 9), *([s] * 9))
    return d2, changed.reshape(n_bands, n_tiles)


def _gdt_compact_kernel(
    valid, d_patch, i_patch, s_patch, d_out, changed,
    *, fuse_k: int, band_h: int, tile_w: int, lamb: float,
):
    lo, hi = fuse_k, fuse_k + band_h
    cl, cr = fuse_k, fuse_k + tile_w
    c = pl.program_id(0)

    @pl.when(valid[c] == 0)
    def _passthrough():
        d_out[...] = d_patch[lo:hi, cl:cr]
        changed[c] = 0

    @pl.when(valid[c] > 0)
    def _compute():
        centre0 = d_patch[lo:hi, cl:cr]
        centre = _gdt_update(
            d_patch[...], i_patch[...], s_patch[...],
            ((lo, hi), (cl, cr)), fuse_k=fuse_k, lamb=lamb,
        )
        d_out[...] = centre
        changed[c] = changed_flag(centre, centre0)


def gdt_compact_step(
    d_patch: jnp.ndarray,
    i_patch: jnp.ndarray,
    s_patch: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    lamb: float,
    fuse_k: int,
    band_h: int,
    tile_w: int,
    interpret: bool,
    vmem_limit_bytes: int,
):
    """Compacted-grid gdt chunk on driver-gathered active cells.

    All three planes arrive as (C·(band_h+2K), tile_w+2K) patches with
    halos pre-pinned by the gather (``d -> +inf``, ``i -> 0``,
    ``s -> -1``); ``valid`` is (C, 1) int32.  Returns (d', changed)
    with d' centre-only (C·band_h, tile_w); row-only plans use
    ``tile_w = width_pad``.
    """
    ph = band_h + 2 * fuse_k
    pw = tile_w + 2 * fuse_k
    assert d_patch.shape[1] == pw and d_patch.shape[0] % ph == 0
    assert i_patch.shape == s_patch.shape == d_patch.shape
    cap = d_patch.shape[0] // ph

    patch_spec = pl.BlockSpec((ph, pw), lambda c: (c, 0))
    mid_spec = pl.BlockSpec((band_h, tile_w), lambda c: (c, 0))

    kern = functools.partial(
        _gdt_compact_kernel, fuse_k=fuse_k, band_h=band_h, tile_w=tile_w,
        lamb=float(lamb),
    )
    d2, changed = pl.pallas_call(
        kern,
        grid=(cap,),
        in_specs=[smem_spec(), patch_spec, patch_spec, patch_spec],
        out_specs=[mid_spec, smem_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((cap * band_h, tile_w), d_patch.dtype),
            jax.ShapeDtypeStruct((cap,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(COMPACT_KERNEL),
    )(valid.reshape(cap), d_patch, i_patch, s_patch)
    return d2, changed.reshape(cap, 1)
