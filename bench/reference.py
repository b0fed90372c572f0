"""Plain grey-level morphology: the yardstick the served outputs are
compared against.

Written from the definitions (Zlaus & Mongus 2019, Sec. 2: the 3x3
structuring element is clipped at the image border) in plain
``jax.numpy``, independent of the package under test: it imports
nothing from ``repro`` and takes nothing the program made.  Every
function takes a stack ``(N, H, W)`` of unsigned 8-bit images and works
on each image alone.

``keep_bits`` is the control's lower precision: inputs are cut to their
top ``keep_bits`` bits before the computation, the int4 step below the
configuration's 8 bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TOP = 255
BOTTOM = 0


def quantize(x, keep_bits: int = 8):
    """``x`` with all but its top ``keep_bits`` of 8 bits cleared."""
    if keep_bits >= 8:
        return x
    return x & jnp.uint8((0xFF << (8 - keep_bits)) & 0xFF)


def _neighbours(x, fill):
    """The eight shifted copies of ``x`` (border filled with ``fill``)."""
    p = jnp.pad(x, ((0, 0), (1, 1), (1, 1)), constant_values=fill)
    h, w = x.shape[-2:]
    return [p[:, 1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
            for dy in (-1, 0, 1) for dx in (-1, 0, 1) if dy or dx]


def erode3(x):
    """3x3 erosion, clipped at the border."""
    out = x
    for n in _neighbours(x, TOP):
        out = jnp.minimum(out, n)
    return out


def dilate3(x):
    """3x3 dilation, clipped at the border."""
    out = x
    for n in _neighbours(x, BOTTOM):
        out = jnp.maximum(out, n)
    return out


def sat_sub(x, h: int):
    """x - h, saturating at 0."""
    h = jnp.uint8(h)
    return jnp.where(x > h, x - h, jnp.uint8(0))


def hfill_marker(x):
    """Fill-holes marker: the border keeps the image, the interior is
    the image's maximum."""
    h, w = x.shape[-2:]
    rows = jnp.arange(h)[:, None]
    cols = jnp.arange(w)[None, :]
    border = (rows == 0) | (rows == h - 1) | (cols == 0) | (cols == w - 1)
    return jnp.where(border, x, jnp.max(x, axis=(-2, -1), keepdims=True))


@functools.partial(jax.jit, static_argnames=("n",))
def geodesic_dilate(marker, mask, n: int):
    """n elementary geodesic dilations: x <- min(dilate3(x), mask)."""
    return jax.lax.fori_loop(
        0, n, lambda _, x: jnp.minimum(dilate3(x), mask), marker)


def _to_fixpoint(step, x0):
    def cond(s):
        return s[1]

    def body(s):
        x = step(s[0])
        return x, jnp.any(x != s[0])

    return jax.lax.while_loop(cond, body, (x0, jnp.asarray(True)))[0]


@jax.jit
def dilate_reconstruct(marker, mask):
    """Reconstruction by dilation of ``marker`` under ``mask``."""
    return _to_fixpoint(lambda x: jnp.minimum(dilate3(x), mask), marker)


@jax.jit
def erode_reconstruct(marker, mask):
    """Reconstruction by erosion of ``marker`` above ``mask``."""
    return _to_fixpoint(lambda x: jnp.maximum(erode3(x), mask), marker)
