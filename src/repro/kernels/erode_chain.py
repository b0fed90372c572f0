"""Fused K-step 3×3 erosion/dilation chain — the paper's core, as a
Pallas TPU kernel.

Layout (one grid step = one row band of TH useful rows):

        ┌──────────────┐   top halo   (K rows, block of the same array)
        │  K rows      │
        ├──────────────┤
        │  TH rows     │   band i     (useful output)
        ├──────────────┤
        │  K rows      │   bottom halo
        └──────────────┘

The stacked (TH+2K, W) tile lives in VMEM for all K elementary filter
applications; validity shrinks one row per application from each stack
edge, so after K steps the centre TH rows are exact.  This replaces the
paper's per-row atomic synchronization between pipelined threads with
redundant halo compute — the TPU-idiomatic trade (the bit-exactness
argument lives in ``docs/ARCHITECTURE.md``).

Fixed-length chains have no convergence flag, so this kernel stays on
the 1-D row-band grid; the 2-D tiled grids exist only on the
convergence-driven kernels the requeue scheduler drives
(``geodesic_chain``, ``qdt_chain``).

Border semantics: the wrapper pads the image to (H_pad, W_pad) with the
lattice identity; for a convex (rectangular) domain, iterated erosion
with identity padding restricted to the original domain equals the
paper's border-clipped erosion (projection argument — any 8-connected
path through the padding can be clamped coordinate-wise back into the
rectangle without growing its length).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (elementary_3x3, fused_steps, ident_for,
                                  image_edges, kernel_name, row_specs, widen)

#: Name of the kernel (``pallas_call`` ``name=`` and ``kernel_metadata``).
ROW_KERNEL = "erode_row"


def _chain_kernel(x_top, x_mid, x_bot, out, *, op: str, fuse_k: int,
                  band_h: int, bands_per_image: int):
    ident = widen(ident_for(op, x_mid.dtype))

    at_top, at_bot = image_edges(pl.program_id(0), bands_per_image)
    top = jnp.where(at_top, ident, widen(x_top[...]))
    bot = jnp.where(at_bot, ident, widen(x_bot[...]))
    stack = jnp.concatenate([top, widen(x_mid[...]), bot], axis=0)

    stack = fused_steps(lambda x: elementary_3x3(x, op), stack, fuse_k)

    out[...] = stack[fuse_k : fuse_k + band_h, :].astype(out.dtype)


def chain_step(
    x: jnp.ndarray,
    *,
    op: str,
    fuse_k: int,
    band_h: int,
    interpret: bool,
    vmem_limit_bytes: int,
    bands_per_image: int | None = None,
) -> jnp.ndarray:
    """Apply K fused elementary filters to a pre-padded image (stack).

    ``x``: (H_pad, W_pad) with H_pad % band_h == 0, band_h % fuse_k == 0,
    padding filled with the lattice identity for ``op``.  For a vertical
    stack of N images pass ``bands_per_image`` so the halo is pinned at
    each image's edges rather than only the stack's.  ``interpret``
    runs the kernel in the Pallas interpreter (off-TPU validation).
    """
    h, w = x.shape
    assert h % band_h == 0 and band_h % fuse_k == 0, (h, band_h, fuse_k)
    n_bands = h // band_h
    if bands_per_image is None:
        bands_per_image = n_bands
    assert n_bands % bands_per_image == 0

    kern = functools.partial(_chain_kernel, op=op, fuse_k=fuse_k,
                             band_h=band_h, bands_per_image=bands_per_image)

    in_specs = row_specs(band_h, fuse_k, h, w)
    out_spec = pl.BlockSpec((band_h, w), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(n_bands,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((h, w), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        **kernel_name(ROW_KERNEL),
    )(x, x, x)
