"""Fusion planner: the paper's pipeline schedule re-derived for TPU.

The paper keeps T elementary filters in flight on T threads, row-window
synchronized, so inter-filter traffic stays in cache.  On TPU the
equivalent is *temporal fusion*: one Pallas kernel applies K elementary
filters to a VMEM-resident row band before the band is written back to
HBM.  This module picks the fusion depth K and band height TH from the
dtype, image width and VMEM budget — the analogue of the paper's
run-time topology examination (§3.6).

Bandwidth model (per K-chunk, per band of TH rows, width W, dtype b):
    HBM traffic   = (TH + 2K)·W·b read + TH·W·b write      (once)
    vs. unfused   = K · 2·TH·W·b                            (K round trips)
    amplification ≈ 2K·TH / (2TH + 2K)  → K for TH >> K
Redundant compute fraction = 2K / (TH + 2K).

Convergence-driven chains (reconstruction, QDT — the paper's Alg. 4/5
requeue mechanism) additionally carry a *scheduling policy*: once the
geodesic wavefront localizes, only bands that changed in the previous
chunk — or whose vertical neighbours changed — need to be requeued.  The
policy fields below control that scheduler:

``requeue_halo``
    how many neighbouring tiles to re-activate around a changed tile
    (per axis).  1 is exact for ``fuse_k <= min(band_h, tile_w)``
    (influence propagates at most ``fuse_k`` pixels in Chebyshev
    distance per chunk, which cannot cross a full tile).
``tile_w``
    column-tile width.  0 (the default) keeps full-width row bands —
    the paper's Alg. 4 granularity.  A positive ``tile_w`` splits each
    band into ``width_pad / tile_w`` column tiles, making the activity
    grid 2-D (``total_bands × n_tiles``) so a narrow *vertical*
    wavefront no longer re-processes full-width bands.
``compact_threshold``
    when the active fraction drops below this, the driver gathers the
    active tiles into a dense workspace and launches a smaller grid
    (the TPU analogue of the paper's work queue).  0 disables
    compaction.

For convergent plans the planner also *shrinks* the band height toward
``CONVERGENT_TARGET_BANDS`` bands per image and splits the width into
column tiles when it is at least two lane-groups wide: tile-level
requeueing is only as fine-grained as the tile, so a VMEM-maximal band
(often the whole image) would leave nothing to skip.

See ``docs/ARCHITECTURE.md`` for the full ChainPlan contract and the
scheduler lifecycle built on it.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

#: VMEM on one TPU v5e TensorCore (bytes).
VMEM_BYTES = 128 * 1024 * 1024
#: Scoped VMEM Mosaic grants one kernel on v5e unless the kernel asks
#: for more; a plan never asks for less (``ChainPlan.vmem_limit_bytes``).
SCOPED_VMEM_BYTES = 16 * 1024 * 1024
#: VMEM the planner sizes a band's working set to (bytes, counted by
#: :func:`working_set_bytes`): a quarter of the core's VMEM.
DEFAULT_VMEM_BUDGET = 32 * 1024 * 1024
#: Values a kernel keeps live per streamed plane, in the work dtype, on
#: top of its double-buffered blocks.  Fit to the scoped VMEM Mosaic
#: allocates for the ten kernels at the 1024² and 2048 px plans (v5e):
#: gdt's weight arithmetic is the high end, at about 4 per plane.
LIVE_PER_PLANE = 5

#: TPU lane count — last-dim tiles should be multiples of this.
LANES = 128
#: Sublane multiples per dtype (f32: 8, bf16: 16, int8: 32).
SUBLANES = {4: 8, 2: 16, 1: 32, 8: 8}

#: Bands per image the planner aims for on convergence-driven chains.
CONVERGENT_TARGET_BANDS = 16

#: Column tiles per band row the planner caps itself at (very wide
#: images coarsen their tiles instead of growing the activity grid).
CONVERGENT_TARGET_TILES = 16

#: Scheduling policies for convergence-driven chains.  ``wavefront`` is
#: the active-tile requeue scheduler (Teodoro-style propagation — pays
#: only for tiles the wavefront touches); ``raster`` sweeps the whole
#: image with directional forward/backward passes (FastGeodis-style —
#: wins when the wavefront is dense and activity tracking is overhead).
SCHEDULES = ("wavefront", "raster")


def work_dtype(dtype):
    """The dtype the kernels compute in: 8- and 16-bit integers widen to
    int32 (Mosaic has no narrow-integer min/max, compare or select on
    v5e); every other dtype is kept.  Widening is exact for min/max,
    the mask clamps and the change test, so results stay bit-exact."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer) and dtype.itemsize < 4:
        return jnp.dtype(jnp.int32)
    return dtype


def side_width(tile_w: int) -> int:
    """Lane width of the left/right halo blocks of the 2-D tile grid:
    one 128-lane group when ``tile_w`` is lane-aligned (Mosaic's block
    tiling), else the whole neighbouring tile.  The kernel keeps only
    the ``fuse_k`` lanes next to the centre."""
    return LANES if tile_w % LANES == 0 else tile_w


def working_set_bytes(band_h: int, fuse_k: int, width_pad: int,
                      tile_w: int, dtype, planes: int) -> int:
    """VMEM one grid step of the plan's kernels holds (bytes) — the one
    model the planner sizes bands by and sets ``vmem_limit_bytes`` from.

    Each of ``planes`` same-shaped operands streams in as a halo-stacked
    ``(band_h + 2·fuse_k)``-row block and out as a ``band_h``-row block,
    both double-buffered by the grid pipeline, and keeps
    ``LIVE_PER_PLANE`` stack-sized values live in the kernel.
    Everything is counted in the work dtype, across the widest block
    the plan drives: the full row, or a tile with its two side halos."""
    cols = width_pad
    if tile_w:
        cols = max(cols, tile_w + 2 * side_width(tile_w))
    stack = (band_h + 2 * fuse_k) * cols * work_dtype(dtype).itemsize
    band = band_h * cols * work_dtype(dtype).itemsize
    return planes * (2 * (stack + band) + LIVE_PER_PLANE * stack)


@dataclasses.dataclass(frozen=True)
class ChainPlan:
    """A schedule for a chain of S elementary filters.

    The plan covers a stack of ``n_images`` same-shaped images laid out
    vertically (batched drivers stack ``(N, H_pad, W_pad)`` into
    ``(N·H_pad, W_pad)``); ``n_bands`` is *per image*.
    """

    band_h: int          # TH: rows of useful output per grid step
    fuse_k: int          # K: elementary filters fused per kernel launch
    width_pad: int       # W rounded up to a lane multiple
    height_pad: int      # H rounded up to a band multiple (per image)
    n_bands: int         # bands per image
    n_chunks: int        # ceil(S / K) kernel launches for a fixed chain
    n_images: int = 1    # images stacked vertically in the working array
    requeue_halo: int = 1        # tiles re-activated around a changed tile
    compact_threshold: float = 0.0   # active fraction below which to compact
    tile_w: int = 0      # column-tile width; 0 = full-width row bands
    schedule: str = "wavefront"  # "wavefront" (requeue) | "raster" (sweeps)
    vmem_limit_bytes: int = SCOPED_VMEM_BYTES  # Mosaic's scoped VMEM cap

    def __post_init__(self):
        # The one place the band/fuse/tile contract is validated (the
        # kernels assert it too, but every driver goes through a
        # ChainPlan).
        if self.band_h % self.fuse_k:
            raise ValueError(
                f"band_h={self.band_h} must be a multiple of "
                f"fuse_k={self.fuse_k}"
            )
        if self.height_pad % self.band_h:
            raise ValueError(
                f"height_pad={self.height_pad} must be a multiple of "
                f"band_h={self.band_h}"
            )
        if self.requeue_halo < 1:
            raise ValueError("requeue_halo must be >= 1 (neighbour influence)")
        if not 0.0 <= self.compact_threshold <= 1.0:
            raise ValueError("compact_threshold must be in [0, 1]")
        if self.tile_w < 0:
            raise ValueError(f"tile_w={self.tile_w} must be >= 0")
        if self.tile_w:
            # Same contract as the row axis: the halo the kernels carry
            # is fuse_k wide, so a tile must be at least one fuse_k and
            # tile cleanly in both directions.
            if self.tile_w % self.fuse_k:
                raise ValueError(
                    f"tile_w={self.tile_w} must be a multiple of "
                    f"fuse_k={self.fuse_k} (or 0 for row-only bands)"
                )
            if self.width_pad % self.tile_w:
                raise ValueError(
                    f"width_pad={self.width_pad} must be a multiple of "
                    f"tile_w={self.tile_w}"
                )
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule={self.schedule!r} must be one of {SCHEDULES}"
            )
        if not 0 < self.vmem_limit_bytes <= VMEM_BYTES:
            raise ValueError(
                f"vmem_limit_bytes={self.vmem_limit_bytes} must be in "
                f"(0, {VMEM_BYTES}]"
            )

    @property
    def key(self) -> tuple:
        """Hashable compact identity for compiled-program caches
        (``repro.serve`` keys its jit entries on this together with the
        op/params/dtype/backend): exactly the fields that determine the
        compiled schedule.  ``ChainPlan`` itself is hashable (frozen
        dataclass) and usable as a ``jax.jit`` static argument; ``key``
        is the stable serialization-friendly form."""
        return (self.band_h, self.fuse_k, self.width_pad, self.height_pad,
                self.n_bands, self.n_chunks, self.n_images,
                self.requeue_halo, self.compact_threshold, self.tile_w,
                self.schedule, self.vmem_limit_bytes)

    @property
    def total_bands(self) -> int:
        """Vertical grid size for the stacked (n_images · height_pad) array."""
        return self.n_bands * self.n_images

    @property
    def n_tiles(self) -> int:
        """Column tiles per band row (1 when ``tile_w == 0``)."""
        return self.width_pad // self.tile_w if self.tile_w else 1

    @property
    def total_tiles(self) -> int:
        """Scheduling cells in the activity grid (``total_bands × n_tiles``).
        This is the unit the requeue scheduler counts work in; for
        row-only plans it equals ``total_bands``."""
        return self.total_bands * self.n_tiles

    @property
    def compact_capacity(self) -> int:
        """Static workspace size (tiles) for the compacted grid."""
        return max(1, math.ceil(self.compact_threshold * self.total_tiles))

    @property
    def redundant_compute_fraction(self) -> float:
        return 2 * self.fuse_k / (self.band_h + 2 * self.fuse_k)

    @property
    def bandwidth_amplification(self) -> float:
        th, k = self.band_h, self.fuse_k
        return (2 * k * th) / (2 * th + 2 * k)


def plan_chain(
    height: int,
    width: int,
    dtype,
    chain_len: int | None = None,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    n_images_resident: int = 1,
    fuse_k: int | None = None,
    band_h: int | None = None,
    n_images: int = 1,
    convergent: bool = False,
    requeue_halo: int = 1,
    compact_threshold: float | None = None,
    tile_w: int | None = None,
    schedule: str = "wavefront",
) -> ChainPlan:
    """Choose (TH, K) so the working set fits VMEM.

    ``n_images_resident`` counts the same-shaped planes the kernels
    stream (1 for a plain chain, 2 with the geodesic mask, 3 for QDT's
    and gdt's planes).  ``n_images`` is the batch size of the vertical
    image stack the plan will drive.  The band is the tallest whose
    :func:`working_set_bytes` fits ``vmem_budget``, and the plan's
    ``vmem_limit_bytes`` is that working set (never below the scoped
    default).  The working set is counted in the kernels' work dtype
    (8- and 16-bit integers compute in int32), while ``fuse_k`` rounds
    to the storage dtype's sublane tiling.

    ``convergent=True`` marks a convergence-driven chain (reconstruction
    / QDT): the planner caps the band height near
    ``CONVERGENT_TARGET_BANDS`` bands per image so the active-tile
    requeue scheduler has skipping granularity, enables compaction
    (``compact_threshold=0.5``) and splits the width into column tiles
    when it is wide enough — all unless overridden.

    ``tile_w`` requests a column-tile width.  ``None`` auto-tiles
    (convergent plans only), ``0`` forces full-width row bands.  A
    requested width is rounded up to a ``fuse_k`` multiple; if the
    result cannot tile the padded width, or ``fuse_k > tile_w`` (the
    1-tile requeue halo would no longer bound the per-chunk influence),
    the planner *falls back to row-only tiling* rather than produce an
    inexact schedule.
    """
    b = jnp.dtype(dtype).itemsize
    w_pad = max(LANES, math.ceil(width / LANES) * LANES)
    sub = SUBLANES.get(b, 8)

    if fuse_k is None:
        fuse_k = 16 if b >= 4 else 32
    if chain_len is not None:
        fuse_k = min(fuse_k, max(1, chain_len))
    # round K to a sublane multiple so halo blocks tile cleanly
    fuse_k = max(sub, math.ceil(fuse_k / sub) * sub)

    if compact_threshold is None:
        compact_threshold = 0.5 if convergent else 0.0

    if tile_w is None:
        tile_w = _auto_tile_w(w_pad, fuse_k) if convergent else 0
    elif tile_w > 0:
        # honour the request when it can be made exact, else fall back
        # to row-only: fuse_k > tile_w breaks the 1-tile halo bound, and
        # a non-dividing width would leave ragged cells.
        if tile_w < fuse_k:
            tile_w = 0
        else:
            tile_w = math.ceil(tile_w / fuse_k) * fuse_k
            if tile_w >= w_pad or w_pad % tile_w:
                tile_w = 0

    def working_set(th):
        return working_set_bytes(th, fuse_k, w_pad, tile_w, dtype,
                                 n_images_resident)

    if band_h is None:
        band_h = max(fuse_k, 512 // fuse_k * fuse_k)  # TH % K == 0
        if convergent:
            # requeue granularity: aim for ~CONVERGENT_TARGET_BANDS bands
            target = math.ceil(height / CONVERGENT_TARGET_BANDS)
            band_h = min(band_h, max(fuse_k, math.ceil(target / fuse_k)
                                     * fuse_k))
        while band_h > fuse_k and working_set(band_h) > vmem_budget:
            band_h -= fuse_k

    h_pad = math.ceil(height / band_h) * band_h
    n_bands = h_pad // band_h
    n_chunks = math.ceil((chain_len or fuse_k) / fuse_k)
    return ChainPlan(
        band_h, fuse_k, w_pad, h_pad, n_bands, n_chunks,
        n_images=n_images,
        requeue_halo=requeue_halo,
        compact_threshold=compact_threshold,
        tile_w=tile_w,
        schedule=schedule,
        vmem_limit_bytes=min(VMEM_BYTES, max(SCOPED_VMEM_BYTES,
                                             working_set(band_h))),
    )


def _auto_tile_w(w_pad: int, fuse_k: int) -> int:
    """Column-tile width for convergent plans: the smallest lane-aligned
    ``fuse_k``-multiple that divides ``w_pad`` while keeping at most
    ``CONVERGENT_TARGET_TILES`` tiles across the width (very wide
    images coarsen their tiles instead of growing the activity grid);
    when every divisor overshoots the target the coarsest one wins.
    0 (row-only) when no divisor yields at least two tiles."""
    base = math.lcm(LANES, fuse_k)
    divisors = [k * base for k in range(1, w_pad // (2 * base) + 1)
                if w_pad % (k * base) == 0]
    for tile_w in divisors:
        if w_pad // tile_w <= CONVERGENT_TARGET_TILES:
            return tile_w
    return divisors[-1] if divisors else 0
