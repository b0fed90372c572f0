"""Inputs and plain reference of ``paper_frames_u8_1024``: the source
paper's real-time case, 1024x1024 8-bit frames through long chains of
elementary 3x3 geodesic filters.

Each frame is a smooth background with Gaussian blobs (assumed content:
the paper's test images are not public).  A request carries the frame
as the mask and the frame lowered by ``marker_h`` grey levels,
saturated, as the marker.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as R

OPS = ("geodesic",)


def _frame(key, q, *, h: int, w: int, blobs: int):
    ky, kx, kperm = jax.random.split(key, 3)
    perm = jax.random.permutation(kperm, blobs)
    sig = (1.5 + q * (min(h, w) / 12 - 1.5))[perm]
    amp = (0.1 + 0.5 * q)[::-1][perm]
    cy = jax.random.uniform(ky, (blobs,)) * h
    cx = jax.random.uniform(kx, (blobs,)) * w
    yy = jnp.arange(h, dtype=jnp.float32)
    xx = jnp.arange(w, dtype=jnp.float32)
    gy = jnp.exp(-(yy[None] - cy[:, None]) ** 2 / (2 * sig[:, None] ** 2))
    gx = jnp.exp(-(xx[None] - cx[:, None]) ** 2 / (2 * sig[:, None] ** 2))
    bumps = jnp.einsum("nh,nw->hw", amp[:, None] * gy, gx,
                       precision=jax.lax.Precision.HIGHEST)
    img = 0.3 + 0.2 * (jnp.cos(2 * jnp.pi * yy / h)[:, None]
                       * jnp.sin(2 * jnp.pi * xx / w)[None, :]) + bumps
    img = (img - img.min()) / (img.max() - img.min())
    return jnp.clip(img * 255.0, 0, 255).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("count", "h", "w", "blobs",
                                             "marker_h"))
def _pool(key, *, count: int, h: int, w: int, blobs: int, marker_h: int):
    # every frame gets the same blob sizes and strengths, in its own
    # order and at its own places; one frame at a time
    q = (jnp.arange(blobs, dtype=jnp.float32) + 0.5) / blobs
    one = functools.partial(_frame, q=q, h=h, w=w, blobs=blobs)
    frames = jax.lax.map(one, jax.random.split(key, count))
    return R.sat_sub(frames, marker_h), frames


def make_pool(cfg: dict, key, count: int) -> tuple:
    """``count`` (marker, mask) pairs on the device, in one call."""
    return _pool(key, count=count, h=cfg["height"], w=cfg["width"],
                 blobs=cfg["blobs"], marker_h=cfg["marker_h"])


def reference(cfg: dict, op: str, params: dict, pool: tuple,
              keep_bits: int = 8):
    """The expected output for every pool item, ``(count, H, W)``."""
    if op != "geodesic" or params.get("op") != "dilate":
        raise ValueError(f"{cfg['name']}: no reference for {op} {params}")
    marker, mask = (R.quantize(a, keep_bits) for a in pool)
    return R.geodesic_dilate(marker, mask, int(params["n"]))
