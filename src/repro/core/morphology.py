"""Elementary morphological operations (pure jnp) — the reference layer.

Semantics follow the paper (Žlaus & Mongus 2019, §2): the structuring
element is clipped at the image border (``w_s(p) ⊆ P``), i.e. min/max is
taken over the *available* neighbours only.  This is equivalent to
padding with the dtype's identity element (+inf for erosion, -inf for
dilation) before the windowed reduction.

All functions operate on 2-D images ``(H, W)`` and are dtype-polymorphic
(uint8/uint16/float32/float64 — the paper's char/short/float/double).
They are written with ``jax.lax`` primitives only, so they jit, vmap,
grad (where meaningful) and shard cleanly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# dtype lattice identities
# ---------------------------------------------------------------------------


def lattice_top(dtype) -> jnp.ndarray:
    """Identity for min (the largest representable value)."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def lattice_bottom(dtype) -> jnp.ndarray:
    """Identity for max (the smallest representable value)."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


# ---------------------------------------------------------------------------
# 1-D decomposed passes (paper Eq. 21-23): w1 = w1x ∘ w1y
# ---------------------------------------------------------------------------


def _shift(f: jnp.ndarray, offset: int, axis: int, fill) -> jnp.ndarray:
    """Shift ``f`` by ``offset`` along ``axis`` filling vacated entries."""
    pad = [(0, 0)] * f.ndim
    if offset > 0:
        pad[axis] = (offset, 0)
        sl = [slice(None)] * f.ndim
        sl[axis] = slice(0, f.shape[axis])
    else:
        pad[axis] = (0, -offset)
        sl = [slice(None)] * f.ndim
        sl[axis] = slice(-offset, f.shape[axis] - offset)
    padded = jnp.pad(f, pad, constant_values=fill)
    return padded[tuple(sl)]


def erode1d(f: jnp.ndarray, axis: int) -> jnp.ndarray:
    """ε along one axis with the 3-element SE (clipped at borders)."""
    top = lattice_top(f.dtype)
    return jnp.minimum(
        f, jnp.minimum(_shift(f, 1, axis, top), _shift(f, -1, axis, top))
    )


def dilate1d(f: jnp.ndarray, axis: int) -> jnp.ndarray:
    """δ along one axis with the 3-element SE (clipped at borders)."""
    bot = lattice_bottom(f.dtype)
    return jnp.maximum(
        f, jnp.maximum(_shift(f, 1, axis, bot), _shift(f, -1, axis, bot))
    )


# ---------------------------------------------------------------------------
# elementary 3x3 filters (Eq. 1-2 with s=1, decomposed)
# ---------------------------------------------------------------------------


def erode3(f: jnp.ndarray) -> jnp.ndarray:
    """ε₁: 3×3 erosion = ε₁ˣ ∘ ε₁ʸ (4 comparisons/pixel, Eq. 23)."""
    return erode1d(erode1d(f, axis=-1), axis=-2)


def dilate3(f: jnp.ndarray) -> jnp.ndarray:
    """δ₁: 3×3 dilation = δ₁ˣ ∘ δ₁ʸ."""
    return dilate1d(dilate1d(f, axis=-1), axis=-2)


def erode3_direct(f: jnp.ndarray) -> jnp.ndarray:
    """Non-decomposed 3×3 erosion (8 comparisons/px) — used only in tests
    to verify the decomposition identity Eq. 23."""
    top = lattice_top(f.dtype)
    out = f
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            if dy == 0 and dx == 0:
                continue
            out = jnp.minimum(out, _shift(_shift(f, dy, -2, top), dx, -1, top))
    return out


def dilate3_direct(f: jnp.ndarray) -> jnp.ndarray:
    bot = lattice_bottom(f.dtype)
    out = f
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            if dy == 0 and dx == 0:
                continue
            out = jnp.maximum(out, _shift(_shift(f, dy, -2, bot), dx, -1, bot))
    return out


# ---------------------------------------------------------------------------
# size-s erosion/dilation as chains of ε₁/δ₁ (the paper's central object)
# ---------------------------------------------------------------------------


def erode(f: jnp.ndarray, s: int) -> jnp.ndarray:
    """ε_s(f) as a chain of s elementary erosions (paper Eq. 4 analogue).

    For the square SE, chaining s 3×3 erosions equals one (2s+1)² erosion.
    """
    if s == 0:
        return f
    return jax.lax.fori_loop(0, s, lambda _, x: erode3(x), f)


def dilate(f: jnp.ndarray, s: int) -> jnp.ndarray:
    if s == 0:
        return f
    return jax.lax.fori_loop(0, s, lambda _, x: dilate3(x), f)


# ---------------------------------------------------------------------------
# elementary geodesic filters (Eq. 3) and bounded-size geodesic (Eq. 4)
# ---------------------------------------------------------------------------


def geodesic_erode1(f: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """ε₁ᵐ(f) = max(ε₁(f), m).  Requires f ≥ m for the usual semantics."""
    return jnp.maximum(erode3(f), m)


def geodesic_dilate1(f: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """δ₁ᵐ(f) = min(δ₁(f), m).  Requires f ≤ m."""
    return jnp.minimum(dilate3(f), m)


def geodesic_erode(f: jnp.ndarray, m: jnp.ndarray, s: int) -> jnp.ndarray:
    """ε_sᵐ(f): s-fold composition of ε₁ᵐ (Eq. 4)."""
    return jax.lax.fori_loop(0, s, lambda _, x: geodesic_erode1(x, m), f)


def geodesic_dilate(f: jnp.ndarray, m: jnp.ndarray, s: int) -> jnp.ndarray:
    return jax.lax.fori_loop(0, s, lambda _, x: geodesic_dilate1(x, m), f)


# ---------------------------------------------------------------------------
# reconstruction (Eq. 5): iterate to convergence
# ---------------------------------------------------------------------------


def _reconstruct(f, m, step, max_iters):
    def cond(state):
        x, prev, it, changed = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        x, _, it, _ = state
        nxt = step(x, m)
        return nxt, x, it + 1, jnp.any(nxt != x)

    x0 = step(f, m)
    init = (x0, f, jnp.asarray(1, jnp.int32), jnp.any(x0 != f))
    out, _, iters, _ = jax.lax.while_loop(cond, body, init)
    return out, iters


def erode_reconstruct(
    f: jnp.ndarray, m: jnp.ndarray, max_iters: int | None = None
) -> jnp.ndarray:
    """ε_recᵐ(f): erosion by reconstruction (Eq. 5); marker f, mask m,
    f ≥ m."""
    if max_iters is None:
        max_iters = f.shape[-1] * f.shape[-2]
    out, _ = _reconstruct(f, m, geodesic_erode1, max_iters)
    return out


def dilate_reconstruct(
    f: jnp.ndarray, m: jnp.ndarray, max_iters: int | None = None
) -> jnp.ndarray:
    """δ_recᵐ(f): dilation by reconstruction. Marker f, mask m, f ≤ m."""
    if max_iters is None:
        max_iters = f.shape[-1] * f.shape[-2]
    out, _ = _reconstruct(f, m, geodesic_dilate1, max_iters)
    return out


def erode_reconstruct_with_iters(f, m, max_iters=None):
    """Like erode_reconstruct but also returns the chain length used
    (the paper reports average chain lengths in Table 5)."""
    if max_iters is None:
        max_iters = f.shape[-1] * f.shape[-2]
    return _reconstruct(f, m, geodesic_erode1, max_iters)


def dilate_reconstruct_with_iters(f, m, max_iters=None):
    if max_iters is None:
        max_iters = f.shape[-1] * f.shape[-2]
    return _reconstruct(f, m, geodesic_dilate1, max_iters)


# ---------------------------------------------------------------------------
# opening / closing (Eq. 16, 19)
# ---------------------------------------------------------------------------


def opening(f: jnp.ndarray, s: int) -> jnp.ndarray:
    """γ_s(f) = δ_s(ε_s(f))."""
    return dilate(erode(f, s), s)


def closing(f: jnp.ndarray, s: int) -> jnp.ndarray:
    """φ_s(f) = ε_s(δ_s(f))."""
    return erode(dilate(f, s), s)
