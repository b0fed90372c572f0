"""Inputs and plain reference of ``tissue_tiles_u8_2048``: 8-bit tiles
of a whole-slide tissue image, as nuclei segmentation reconstructs them
(Teodoro et al., arXiv:1209.3314).

A tile is a slowly varying tissue background with many small bright
nuclei and a little sensor noise (assumed content: the slides are not
public).  The work of a reconstruction depends on where the nuclei and
the noise fall: over 2048x2048 tiles made from different keys the
steps to converge ranged over a factor of 1.5, and two tiles in flight are
batched, so a batch runs as long as its slower tile.  So every pool
item is the one tile that ``tile_key`` makes, turned or mirrored by one
of the eight symmetries of the square, drawn from the seed.  The 3x3
elementary step and the scheduler's square cells are unchanged by these
symmetries, so every item and every seed needs the same work, and the
answers still differ from item to item.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as R

OPS = ("hmax", "hfill")


def _tile(key, q, *, side: int, nuclei: int, radius: tuple, noise: int):
    ky, kx, kperm, kby, kbx, kn = jax.random.split(key, 6)
    perm = jax.random.permutation(kperm, nuclei)
    # a nucleus' radius is about two standard deviations of its bump
    sig = (radius[0] + q * (radius[1] - radius[0]))[perm] / 2
    amp = (0.3 + 0.3 * q)[::-1][perm]
    # tissue density: six broad bumps of fixed size
    cy = jnp.concatenate([jax.random.uniform(ky, (nuclei,)),
                          jax.random.uniform(kby, (6,))]) * side
    cx = jnp.concatenate([jax.random.uniform(kx, (nuclei,)),
                          jax.random.uniform(kbx, (6,))]) * side
    sig = jnp.concatenate([sig, jnp.full((6,), side / 4, jnp.float32)])
    amp = jnp.concatenate([amp, jnp.full((6,), 0.15, jnp.float32)])
    grid = jnp.arange(side, dtype=jnp.float32)
    gy = jnp.exp(-(grid[None] - cy[:, None]) ** 2 / (2 * sig[:, None] ** 2))
    gx = jnp.exp(-(grid[None] - cx[:, None]) ** 2 / (2 * sig[:, None] ** 2))
    img = 0.2 + jnp.einsum("nh,nw->hw", amp[:, None] * gy, gx,
                           precision=jax.lax.Precision.HIGHEST)
    grey = (img - img.min()) / (img.max() - img.min()) * (255.0 - 2 * noise)
    grey = grey + noise + jax.random.randint(kn, grey.shape, -noise,
                                             noise + 1)
    return jnp.clip(grey, 0, 255).astype(jnp.uint8)


def _turn(tile, d4):
    """``tile`` under symmetry ``d4`` (0..7) of the square: bit 0 flips
    the rows, bit 1 the columns, bit 2 transposes."""
    tile = jnp.where(d4 & 1, tile[::-1], tile)
    tile = jnp.where(d4 & 2, tile[:, ::-1], tile)
    return jnp.where(d4 & 4, tile.T, tile)


@functools.partial(jax.jit, static_argnames=("count", "side", "nuclei",
                                             "radius", "noise", "tile_key"))
def _pool(key, *, count: int, side: int, nuclei: int, radius: tuple,
          noise: int, tile_key: int):
    q = (jnp.arange(nuclei, dtype=jnp.float32) + 0.5) / nuclei
    tile = _tile(jax.random.key(tile_key), q, side=side, nuclei=nuclei,
                 radius=radius, noise=noise)
    d4 = jax.random.randint(key, (count,), 0, 8)
    # one item at a time, so that making the pool needs little memory
    return (jax.lax.map(functools.partial(_turn, tile), d4),)


def make_pool(cfg: dict, key, count: int) -> tuple:
    """``count`` tiles on the device, in one call."""
    return _pool(key, count=count, side=cfg["tile_px"],
                 nuclei=cfg["nuclei"],
                 radius=tuple(cfg["nucleus_radius_px"]),
                 noise=cfg["noise_grey_levels"], tile_key=cfg["tile_key"])


def reference(cfg: dict, op: str, params: dict, pool: tuple,
              keep_bits: int = 8):
    """The expected output for every pool item, ``(count, H, W)``."""
    (tile,) = pool
    f = R.quantize(tile, keep_bits)
    if op == "hmax":
        return R.dilate_reconstruct(R.sat_sub(f, int(params["h"])), f)
    if op == "hfill":
        return R.erode_reconstruct(R.hfill_marker(f), f)
    raise ValueError(f"{cfg['name']}: no reference for {op}")
