"""Scheduler engine + public wrappers over the fused Pallas kernels.

This module owns the *engine*: padding/stacking layout helpers, the
fixed-chain drivers (``morph_chain``, ``geodesic_chain``), and the
active-cell requeue scheduler (``_drive_scheduler`` and the
``_scheduled_reconstruct`` / ``_scheduled_qdt`` step bundles) that
``repro.api``'s compiled executables drive.  The public operator sugar
(``erode``/``dilate``/``opening``/``closing``/``reconstruct``/
``qdt_planes``) is now thin: each call builds an expression and routes
through ``repro.api.compile``, which

  1. plans one fusion schedule for the whole program
     (``core.chain.plan_chain``),
  2. pads every input once with the correct absorbing values (lattice
     identity / mask pinning — see the kernel docstrings for why this
     preserves border-clipped semantics),
  3. drives the kernels with ``lax.scan`` (fixed chains) or the requeue
     scheduler (reconstruction — the paper's convergence detection,
     Alg. 4),
  4. crops back once.

``backend``:
  * ``"pallas"``  — the fused kernels (interpret=True off TPU, decided
    when the program is traced; on TPU the same code path compiles
    natively with interpret=False).
  * ``"xla"``     — the pure-jnp oracle bodies; what the framework runs
    when Pallas is unavailable.  Still one compiled program per chain
    (unlike the per-filter "naive" baseline).
  * ``None``      — the platform policy default
    (``core.backend.default_backend``).  Passing ``backend=`` to the
    operator sugar is deprecated (it still works, with a
    ``DeprecationWarning``); bind the backend at ``repro.api.compile``
    time instead.  ``morph_chain``/``geodesic_chain``/
    ``reconstruct_with_stats`` are engine entry points where
    ``backend``/``plan``/``max_chunks`` remain first-class arguments.

Batching: every public op accepts either a single (H, W) image or an
(N, H, W) stack.  The stack is laid out vertically as one
(N·H_pad, W_pad) working array so a single kernel grid covers all
images; halo pinning at image edges (``bands_per_image``) keeps the
images independent.

Active-tile requeue scheduling (the paper's Alg. 4 requeue mechanism,
extended to 2-D): the convergence-driven drivers (``reconstruct``,
``qdt_planes``) keep the per-cell ``changed`` flags as a live activity
grid instead of collapsing them into one global bit.  A *cell* is one
row band (``plan.tile_w == 0``) or one band × column tile
(``plan.tile_w > 0`` — ``total_bands × n_tiles`` grid); the 2-D grid is
what lets a narrow vertical wavefront skip the quiet column strips a
full-width band scheduler would re-process every chunk.  A cell is
requeued for the next K-chunk iff it *or a Chebyshev neighbour*
changed — influence propagates at most ``fuse_k`` pixels per chunk in
any direction, so a one-cell halo (``plan.requeue_halo``) is exact for
``fuse_k <= min(band_h, tile_w)`` (``plan_chain`` falls back to
row-only tiling otherwise).  Inactive cells are skipped by the kernel
(``pl.when`` early-out); once the active fraction drops below
``plan.compact_threshold`` the driver additionally *compacts*: it
gathers the active cells as (band_h+2K, tile_w+2K) patches (halos
pre-pinned at image edges) into a dense workspace of
``plan.compact_capacity`` cells and launches the smaller grid,
scattering centre windows back.  Per-image convergence in batched mode
falls out for free: a finished image's cells all go inactive and stop
contributing work while the remaining images iterate.

The full lifecycle (activity vector → halo dilation → compaction →
scatter) and the ChainPlan contract it hangs off are documented in
``docs/ARCHITECTURE.md``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import morphology as M
from repro.core.backend import (Backend, canonicalize_backend,
                                warn_legacy_kwargs)
from repro.core.chain import ChainPlan, plan_chain
from repro.kernels.common import ident_for, qdt_acc_dtype
from repro.kernels.erode_chain import chain_step
from repro.kernels.gdt_chain import (D_IDENT, I_IDENT, S_IDENT,
                                     gdt_chain_step, gdt_compact_step,
                                     gdt_tile_step)
from repro.kernels.geodesic_chain import (geodesic_chain_step,
                                          geodesic_compact_step,
                                          geodesic_tile_step)
from repro.kernels.qdt_chain import (qdt_chain_step, qdt_compact_step,
                                     qdt_tile_step)


def _interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter: everywhere but
    a TPU.  Asked when a program is traced, never at import, so that
    importing ``repro`` initialises no backend."""
    return jax.default_backend() != "tpu"


def _api():
    from repro import api  # lazy: repro.api builds on this module

    return api


class ReconstructStats(NamedTuple):
    """Per-run scheduling statistics (the paper's Table 5 chain lengths,
    extended with the requeue scheduler's cell-level accounting).

    The unit is one *scheduling cell*: a full-width row band for
    row-only plans, a band × column tile for 2-D tiled plans
    (``plan.tile_w > 0``) — i.e. one kernel grid step.  The legacy
    field names say "band" because row-only cells are bands; for tiled
    plans ``total_bands`` reports ``plan.total_tiles`` so the
    ``active_band_sum / (total_bands · chunks)`` active-fraction recipe
    keeps working unchanged.

    ``converged`` is the scheduler watchdog's verdict: True iff every
    image's active set emptied before the chunk budget (``max_chunks``)
    ran out.  A False value means the result is a *partial* fixpoint —
    the degraded-mode contract (``docs/ROBUSTNESS.md``) says how the
    serving layer surfaces it (``Ticket.degraded``)."""

    chunks: jnp.ndarray           # int32: K-chunk iterations executed
    active_band_sum: jnp.ndarray  # int32: Σ scheduled cells over all chunks
    total_bands: jnp.ndarray      # int32: cells in the padded stack
    active_per_chunk: jnp.ndarray  # int32[max_chunks], 0 past ``chunks``
    converged: jnp.ndarray = True  # bool: active set emptied within budget


# ---------------------------------------------------------------------------
# layout helpers: batch promotion, padding, vertical stacking
# ---------------------------------------------------------------------------


def _as_stack(f: jnp.ndarray):
    """Promote (H, W) to (1, H, W); pass (N, H, W) through."""
    if f.ndim == 2:
        return f[None], True
    if f.ndim == 3:
        return f, False
    raise ValueError(f"expected (H, W) or (N, H, W), got shape {f.shape}")


def _pad(f3: jnp.ndarray, plan: ChainPlan, fill) -> jnp.ndarray:
    n, h, w = f3.shape
    return jnp.pad(
        f3,
        ((0, 0), (0, plan.height_pad - h), (0, plan.width_pad - w)),
        constant_values=fill,
    )


def _crop(f3: jnp.ndarray, shape, was_2d: bool) -> jnp.ndarray:
    out = f3[:, : shape[-2], : shape[-1]]
    return out[0] if was_2d else out


def _crop3(x2: jnp.ndarray, n: int, h: int, w: int) -> jnp.ndarray:
    """(N·H_pad, W_pad) stacked working array → unpadded (N, H, W).

    The re-band primitive of the multi-plan executable: a value leaving
    one plan group's band layout is cropped back to image form here,
    then ``_pad``-ed into the next group's layout with the pad identity
    its lowering expects."""
    return _unstacked(x2, n)[:, :h, :w]


def _reband(x2: jnp.ndarray, n: int, h: int, w: int, plan: ChainPlan,
            fill) -> jnp.ndarray:
    """Move a stacked working array into ``plan``'s band layout: crop
    the real image region and re-pad it with ``fill`` (one fused
    crop → pad round-trip across a plan-group boundary)."""
    return _stacked(_pad(_crop3(x2, n, h, w), plan, fill))


def _stacked(x3: jnp.ndarray) -> jnp.ndarray:
    """(N, H_pad, W_pad) → (N·H_pad, W_pad); free (row-major)."""
    return x3.reshape(x3.shape[0] * x3.shape[1], x3.shape[2])


def _unstacked(x2: jnp.ndarray, n: int) -> jnp.ndarray:
    return x2.reshape(n, x2.shape[0] // n, x2.shape[1])


def _plan_for(f3: jnp.ndarray, plan: ChainPlan | None) -> None:
    """Validate an explicitly supplied plan against the input stack."""
    if plan is None:
        return
    n, h, w = f3.shape
    if plan.n_images != n:
        raise ValueError(f"plan.n_images={plan.n_images} != batch size {n}")
    if plan.height_pad < h or plan.width_pad < w:
        raise ValueError(
            f"plan pads ({plan.height_pad}, {plan.width_pad}) smaller than "
            f"image ({h}, {w})"
        )


# ---------------------------------------------------------------------------
# active-cell bookkeeping (cell = row band × column tile; n_tiles may be 1)
# ---------------------------------------------------------------------------

#: ``jax.named_scope`` names the scheduler's XLA steps carry into the
#: compiled program's ``op_name`` metadata, so that a fusion can be told
#: apart by its scope (``Service.op_scopes``): the compaction's patch and
#: centre-window gathers, its scatters, and the activity bookkeeping.
SCOPES = ("compact_gather", "compact_scatter", "schedule")


def _scoped(scope: str):
    """Trace the decorated function under ``jax.named_scope(scope)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return inner
    return wrap


def _cell_tile_w(plan: ChainPlan) -> int:
    """Pixel width of one scheduling cell (full width for row-only)."""
    return plan.tile_w or plan.width_pad


@_scoped("schedule")
def _dilate_active(flags: jnp.ndarray, plan: ChainPlan) -> jnp.ndarray:
    """Requeue set from changed flags: a cell is active next chunk iff it
    or a Chebyshev neighbour (vertical within the same image, horizontal
    within the row, diagonals included) changed.  Diagonals matter for
    2-D tiles because influence propagates ``fuse_k`` pixels per chunk
    in *Chebyshev* distance; the separable row-then-column max over an
    already-row-dilated grid is exactly that 3×3 dilation."""
    a = flags.reshape(plan.n_images, plan.n_bands, plan.n_tiles)
    for _ in range(plan.requeue_halo):
        up = jnp.pad(a[:, 1:], ((0, 0), (0, 1), (0, 0)))
        dn = jnp.pad(a[:, :-1], ((0, 0), (1, 0), (0, 0)))
        a = jnp.maximum(a, jnp.maximum(up, dn))
        if plan.n_tiles > 1:
            lf = jnp.pad(a[:, :, 1:], ((0, 0), (0, 0), (0, 1)))
            rt = jnp.pad(a[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
            a = jnp.maximum(a, jnp.maximum(lf, rt))
    return a.reshape(plan.total_bands, plan.n_tiles)


@_scoped("compact_gather")
def _halo_view(x2: jnp.ndarray, plan: ChainPlan, ident) -> jnp.ndarray:
    """Stacked (TOTAL_H, W) → per-image (N, H_pad + 2K, W + 2K) view,
    ringed by ``fuse_k`` rows and columns of ``ident`` around each
    image.  A cell's halo patch is then one rectangle of this view whose
    corner sits on a band and tile boundary, and rows outside the cell's
    image and columns outside the array read ``ident`` by construction —
    the pinning the compact kernels cannot do, since they know nothing
    of slot → image geometry."""
    k = plan.fuse_k
    x3 = x2.reshape(plan.n_images, plan.height_pad, x2.shape[1])
    return jnp.pad(x3, ((0, 0), (k, k), (k, k)), constant_values=ident)


@_scoped("compact_gather")
def _gather_patches(view: jnp.ndarray, idx: jnp.ndarray, plan: ChainPlan,
                    ident):
    """Copy the (band_h+2K, tile_w+2K) halo patches of flat cell indices
    ``idx`` out of a :func:`_halo_view` → (C·(band_h+2K), tile_w+2K):
    one window a workspace slot, at the cell's band and tile offset in
    its image's view.  Sentinel slots (idx == total_tiles) come back
    all-``ident`` (their output is dropped at scatter).

    The copies are a ``scan`` rather than a gather: the TPU compiler
    expands a windowed gather into a loop that keeps no ``op_name``, so
    its time would escape the ``compact_gather`` scope."""
    bh, k, tw = plan.band_h, plan.fuse_k, _cell_tile_w(plan)
    real = idx < plan.total_tiles
    cell = jnp.where(real, idx, 0)
    bi = cell // plan.n_tiles        # global band index
    starts = jnp.stack([bi // plan.n_bands, (bi % plan.n_bands) * bh,
                        (cell % plan.n_tiles) * tw], axis=1)

    def window(_, start):
        win = jax.lax.dynamic_slice(view, tuple(start),
                                    (1, bh + 2 * k, tw + 2 * k))
        return None, win[0]

    _, g = jax.lax.scan(window, None, starts)
    g = jnp.where(real[:, None, None], g, jnp.asarray(ident, view.dtype))
    return g.reshape(-1, tw + 2 * k)


def _cell_view(x2: jnp.ndarray, plan: ChainPlan) -> jnp.ndarray:
    """(TOTAL_H, W) → (total_tiles, band_h, tile_w) cell-major view."""
    bh, tw, nt = plan.band_h, _cell_tile_w(plan), plan.n_tiles
    return (x2.reshape(plan.total_bands, bh, nt, tw)
            .transpose(0, 2, 1, 3).reshape(-1, bh, tw))


@_scoped("compact_gather")
def _gather_mid(x2: jnp.ndarray, idx: jnp.ndarray,
                plan: ChainPlan) -> jnp.ndarray:
    """Gather the centre windows of cells ``idx`` → (C·band_h, tile_w)."""
    cells = jnp.take(_cell_view(x2, plan), idx, axis=0, mode="clip")
    return cells.reshape(-1, _cell_tile_w(plan))


@_scoped("compact_scatter")
def _scatter_mid(
    x2: jnp.ndarray, idx: jnp.ndarray, new_mid: jnp.ndarray, plan: ChainPlan
) -> jnp.ndarray:
    """Scatter compact-workspace centre windows back; sentinel slots
    (idx == total_tiles, out of bounds) are dropped."""
    bh, tw, nt = plan.band_h, _cell_tile_w(plan), plan.n_tiles
    upd = new_mid.reshape(-1, bh, tw)
    cells = _cell_view(x2, plan).at[idx].set(upd, mode="drop")
    return (cells.reshape(plan.total_bands, nt, bh, tw)
            .transpose(0, 2, 1, 3).reshape(x2.shape))


@_scoped("compact_scatter")
def _scatter_flags(ch: jnp.ndarray, idx: jnp.ndarray, plan: ChainPlan):
    """Workspace-slot changed flags → full (total_bands, n_tiles) grid."""
    flat = jnp.zeros((plan.total_tiles,), jnp.int32)
    flat = flat.at[idx].set(ch.ravel(), mode="drop")
    return flat.reshape(plan.total_bands, plan.n_tiles)


@_scoped("schedule")
def _active_indices(active: jnp.ndarray, plan: ChainPlan):
    """Dense slot → flat cell index map for the compact workspace."""
    total = plan.total_tiles
    idx = jnp.nonzero(
        active.ravel() > 0, size=plan.compact_capacity, fill_value=total
    )[0].astype(jnp.int32)
    valid = (idx < total).astype(jnp.int32)[:, None]
    return idx, valid


# ---------------------------------------------------------------------------
# fixed-length chains: ε_s / δ_s (paper Fig. 7 workload)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "op", "backend", "plan"))
def morph_chain(
    f: jnp.ndarray,
    n: int,
    op: str = "erode",
    backend: Backend | None = None,
    plan: ChainPlan | None = None,
) -> jnp.ndarray:
    """Apply n elementary 3×3 erosions/dilations with K-step fusion.

    Accepts (H, W) or a batched (N, H, W) stack.  Engine entry point:
    ``backend`` (None = platform default) stays first-class here.
    """
    backend = canonicalize_backend(backend)
    if backend == "xla":
        body = M.erode3 if op == "erode" else M.dilate3
        return jax.lax.fori_loop(0, n, lambda _, x: body(x), f)

    f3, was_2d = _as_stack(f)
    _plan_for(f3, plan)
    if plan is None:
        plan = plan_chain(
            f3.shape[1], f3.shape[2], f.dtype, n, n_images=f3.shape[0]
        )
    k = plan.fuse_k

    x3 = _pad(f3, plan, ident_for(op, f.dtype))
    full, rem = divmod(n, k)

    def chunk(x, _):
        return chain_step(x, op=op, fuse_k=k, band_h=plan.band_h,
                          interpret=_interpret(),
                          vmem_limit_bytes=plan.vmem_limit_bytes,
                          bands_per_image=plan.n_bands), None

    if full:
        x2, _ = jax.lax.scan(chunk, _stacked(x3), None, length=full)
        x3 = _unstacked(x2, f3.shape[0])
    if rem:
        # tail chunk: fuse_k must divide band_h; run a rem-step chunk with
        # the smallest compatible fuse and finish with jnp steps if needed.
        # (on the 3-D stack — jnp bodies are axis-polymorphic and cannot
        # leak between images.)
        body = M.erode3 if op == "erode" else M.dilate3
        x3 = jax.lax.fori_loop(0, rem, lambda _, y: body(y), x3)
    return _crop(x3, f.shape, was_2d)


def _compile_unary(build, f, backend, name):
    api = _api()
    if backend is not None:
        warn_legacy_kwargs(name, "backend")
    exe = api.compile(build(api.E.input("f")), f.shape, f.dtype, backend)
    return exe(f)


def erode(f: jnp.ndarray, s: int,
          backend: Backend | None = None) -> jnp.ndarray:
    """ε_s via a chain of s elementary erosions (Eq. 4 decomposition)."""
    api = _api()
    return _compile_unary(lambda x: api.E.erode(s, x), f, backend,
                          "kernels.ops.erode")


def dilate(f: jnp.ndarray, s: int,
           backend: Backend | None = None) -> jnp.ndarray:
    api = _api()
    return _compile_unary(lambda x: api.E.dilate(s, x), f, backend,
                          "kernels.ops.dilate")


def opening(f: jnp.ndarray, s: int,
            backend: Backend | None = None) -> jnp.ndarray:
    """γ_s = δ_s ∘ ε_s — compiled as one two-segment padded program."""
    api = _api()
    return _compile_unary(lambda x: api.E.opening(s, x), f, backend,
                          "kernels.ops.opening")


def closing(f: jnp.ndarray, s: int,
            backend: Backend | None = None) -> jnp.ndarray:
    api = _api()
    return _compile_unary(lambda x: api.E.closing(s, x), f, backend,
                          "kernels.ops.closing")


# ---------------------------------------------------------------------------
# geodesic chains + reconstruction (Alg. 4)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "op", "backend", "plan"))
def geodesic_chain(
    f: jnp.ndarray,
    m: jnp.ndarray,
    n: int,
    op: str = "erode",
    backend: Backend | None = None,
    plan: ChainPlan | None = None,
) -> jnp.ndarray:
    """n elementary geodesic steps (fixed length, Eq. 4).

    Accepts (H, W) or a batched (N, H, W) marker/mask stack.  Engine
    entry point: ``backend`` (None = platform default) stays
    first-class here.
    """
    backend = canonicalize_backend(backend)
    if backend == "xla":
        step = M.geodesic_erode1 if op == "erode" else M.geodesic_dilate1
        return jax.lax.fori_loop(0, n, lambda _, x: step(x, m), f)

    f3, was_2d = _as_stack(f)
    m3, _ = _as_stack(m)
    if f3.shape != m3.shape:
        raise ValueError(f"marker shape {f.shape} != mask shape {m.shape}")
    _plan_for(f3, plan)
    if plan is None:
        plan = plan_chain(
            f3.shape[1], f3.shape[2], f.dtype, n,
            n_images_resident=2, n_images=f3.shape[0],
        )
    k = plan.fuse_k
    ident = ident_for(op, f.dtype)
    # mask pinning: pad mask with the identity so pad rows are absorbing
    fp = _stacked(_pad(f3, plan, ident))
    mp = _stacked(_pad(m3, plan, ident))

    full, rem = divmod(n, k)

    def chunk(x, _):
        y, _ = geodesic_chain_step(
            x, mp, op=op, fuse_k=k, band_h=plan.band_h,
            interpret=_interpret(),
            vmem_limit_bytes=plan.vmem_limit_bytes,
            bands_per_image=plan.n_bands,
        )
        return y, None

    if full:
        fp, _ = jax.lax.scan(chunk, fp, None, length=full)
    if rem:
        step = M.geodesic_erode1 if op == "erode" else M.geodesic_dilate1
        n_img = f3.shape[0]
        fp3 = jax.lax.fori_loop(
            0, rem, lambda _, x: step(x, _unstacked(mp, n_img)),
            _unstacked(fp, n_img),
        )
        return _crop(fp3, f.shape, was_2d)
    return _crop(_unstacked(fp, f3.shape[0]), f.shape, was_2d)


def scheduler_state0(plan: ChainPlan):
    """Fresh resumable scheduler state for :func:`_drive_scheduler`:
    ``(active, img_chunks, exhausted)`` with every cell active and no
    chunks applied.  A state with ``active`` all-zero (see
    ``Executable.slot_session``) describes a stack of parked slots that
    cost no work until a slot's rows are re-activated."""
    return (
        jnp.ones((plan.total_bands, plan.n_tiles), jnp.int32),
        jnp.zeros((plan.n_images,), jnp.int32),
        jnp.zeros((plan.n_images,), jnp.bool_),
    )


def _drive_scheduler(
    plan: ChainPlan,
    data,
    *,
    full_step,
    compact_step=None,
    gather_const=None,
    max_chunks: int,
    with_stats: bool = False,
    resume=None,
    budget: int | None = None,
):
    """Shared active-cell requeue driver loop (the paper's Alg. 4 work
    queue).  One loop serves every convergence-driven chain —
    reconstruction, QDT, and whatever ``repro.serve`` routes through
    them — and owns the full-grid/compact-grid cond, the changed-flag →
    requeue-set dilation, per-image chunk counters, and the scheduling
    statistics.  The activity state is a (total_bands, n_tiles) int32
    grid (n_tiles == 1 for row-only plans).  The chain being driven is
    supplied as a state pytree plus step functions:

    ``full_step(data, active, base) -> (data, flags)``
        one K-chunk over the full stacked grid.  ``base`` is a
        (total_bands, 1) int32 giving the number of elementary filters
        already applied to each band's *image* — counters advance
        per-image, only while the image still has active cells, so
        ragged-converged stacks stay consistent (QDT indexes its
        d-plane with it; reconstruction ignores it).  ``flags`` comes
        back (total_bands, n_tiles).
    ``compact_step(data, idx, valid, const, base) -> (data, flags)``
        one K-chunk on the compacted grid of gathered cells ``idx``
        (flat indices into the activity grid; ``valid`` masks workspace
        slots past the true active count).
    ``gather_const(idx) -> pytree``
        gathers the *chunk-invariant* compact operands (e.g. the
        geodesic mask patches).  The driver caches the result and
        reuses it while the active cell set is unchanged between
        chunks, so a localized wavefront iterating inside the same
        cells does not re-gather the mask every chunk.

    Returns (data, chunks, active_cell_sum, active_per_chunk,
    img_converged, state, counts).  ``counts`` is the int32 pair
    ``(compact_chunks, mask_gathers)``: the chunks that took the compact
    branch, and those of them whose ``gather_const`` cache missed (0
    without a cache), carried as two scalars whatever ``with_stats``
    says.  ``img_converged`` is the convergence
    watchdog's per-image verdict — a (n_images,) bool vector, True
    where the image's cells all went inactive *within the chunk
    budget*.  The loop already refuses to spin (``it < max_chunks`` in
    the cond); the vector is what turns a budget exhaustion from a
    silent partial result into a typed, per-image signal that
    ``reconstruct_with_stats`` (``ReconstructStats.converged``) and the
    serving layer's degraded-mode demux surface.  The per-chunk trace
    is only carried through the loop when ``with_stats`` — it is a
    max_chunks-sized array updated by scatter every chunk, which the
    plain paths must not pay for (XLA cannot DCE loop-carried state).

    **Resumable rounds** (the continuous-batching seam): ``resume``
    accepts a previously returned ``state = (active, img_chunks,
    exhausted)`` so the loop can run a *bounded round* of at most
    ``max_chunks`` chunks and be re-entered later exactly where it
    stopped — per-image chunk counters (and therefore the QDT distance
    base offsets, ``img_chunks * fuse_k``) carry across rounds.
    Because every kernel pins its halo at image boundaries
    (``bands_per_image``) and inactive cells are skipped, an image's
    chunk sequence depends only on its own activity rows: re-arming
    one slot's rows from a parked state replays exactly the chunk
    sequence a solo run of that image would take, which is what makes
    mid-flight slot refill bit-exact.

    ``budget`` (used with ``resume``) bounds the *per-image* chunk
    count across rounds: an image that reaches ``budget`` applied
    chunks while still active has its cells force-cleared — precisely
    the truncation a solo run under ``max_chunks=budget`` performs —
    and is flagged in ``state.exhausted`` so the caller can deliver it
    as a degraded partial fixpoint rather than mistaking the cleared
    activity for convergence.
    """
    total = plan.total_tiles
    cap = plan.compact_capacity
    use_compact = (
        compact_step is not None
        and plan.compact_threshold > 0.0
        and cap < total
    )
    with_cache = use_compact and gather_const is not None

    if with_cache:
        # A never-matching key forces a gather on the first compact
        # chunk, so no chunk reads the initial value: it is built from
        # the cache pytree's shapes alone.
        key0 = jnp.full((cap,), -1, jnp.int32)
        val0 = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(gather_const, key0),
        )
    else:
        key0, val0 = jnp.zeros((0,), jnp.int32), ()

    def img_active(active):
        return jnp.any(active.reshape(plan.n_images, -1) > 0, axis=1)

    def cond(state):
        active, it = state[1], state[2]
        return jnp.logical_and(jnp.any(active > 0), it < max_chunks)

    def body(state):
        (data, active, it, img_chunks, asum, per_chunk, ckey, cval,
         exhausted, compacted, gathers) = state
        count = jnp.sum(active)
        base = jnp.repeat(img_chunks * plan.fuse_k, plan.n_bands)[:, None]

        def do_full(data, ckey, cval):
            out, flags = full_step(data, active, base)
            return out, flags, ckey, cval, jnp.int32(0)

        def do_compact(data, ckey, cval):
            idx, valid = _active_indices(active, plan)
            missed = jnp.int32(0)
            if with_cache:
                hit = jnp.all(idx == ckey)
                cval = jax.lax.cond(
                    hit, lambda: cval, lambda: gather_const(idx),
                )
                ckey = idx
                missed = jnp.logical_not(hit).astype(jnp.int32)
            out, flags = compact_step(data, idx, valid, cval, base)
            return out, flags, ckey, cval, missed

        if use_compact:
            data, flags, ckey, cval, missed = jax.lax.cond(
                count <= cap, do_compact, do_full, data, ckey, cval
            )
            compacted = compacted + (count <= cap).astype(jnp.int32)
            gathers = gathers + missed
        else:
            data, flags, ckey, cval, _ = do_full(data, ckey, cval)
        if with_stats:
            per_chunk = per_chunk.at[it].set(count)
        next_active = _dilate_active(flags, plan)
        next_chunks = img_chunks + img_active(active).astype(jnp.int32)
        if budget is not None:
            # per-image budget truncation: an image at its chunk budget
            # stops receiving chunks — bit-exact with a solo run under
            # max_chunks=budget — and is flagged exhausted iff it was
            # cut off while still active (vs converging right at it).
            over = next_chunks >= budget
            exhausted = jnp.logical_or(
                exhausted, jnp.logical_and(over, img_active(next_active)))
            next_active = jnp.where(
                jnp.repeat(over, plan.n_bands)[:, None], 0, next_active)
        return (
            data,
            next_active,
            it + 1,
            next_chunks,
            asum + count,
            per_chunk,
            ckey,
            cval,
            exhausted,
            compacted,
            gathers,
        )

    active0, img_chunks0, exhausted0 = (
        resume if resume is not None else scheduler_state0(plan))
    init = (
        data,
        active0,
        jnp.asarray(0, jnp.int32),
        img_chunks0,
        jnp.asarray(0, jnp.int32),
        jnp.zeros((max_chunks if with_stats else 0,), jnp.int32),
        key0,
        val0,
        exhausted0,
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    (data, active, it, img_chunks, asum, per_chunk, _, _,
     exhausted, compacted, gathers) = jax.lax.while_loop(cond, body, init)
    img_converged = jnp.logical_not(img_active(active))
    return (data, it, asum, per_chunk, img_converged,
            (active, img_chunks, exhausted), (compacted, gathers))


def _scheduled_reconstruct(fp, mp, plan: ChainPlan, op: str, max_chunks: int,
                           with_stats: bool, resume=None,
                           budget: int | None = None):
    """Reconstruction's step functions for :func:`_drive_scheduler`.

    ``fp``/``mp`` are stacked (TOTAL_H, W_pad) arrays.  The mask is
    chunk-invariant, so its compact-workspace gather goes through the
    driver's ``gather_const`` cache.  Tiled plans run the 2-D grid
    kernel for full chunks; compaction is patch-based either way.
    ``resume``/``budget`` pass through to the driver (the
    continuous-batching slot-refill entry — see
    ``Executable.slot_session``).
    """
    ident = ident_for(op, fp.dtype)

    def full_step(x, active, base):
        if plan.n_tiles > 1:
            return geodesic_tile_step(
                x, mp, op=op, fuse_k=plan.fuse_k, band_h=plan.band_h,
                tile_w=plan.tile_w, interpret=_interpret(),
                vmem_limit_bytes=plan.vmem_limit_bytes, active=active,
                bands_per_image=plan.n_bands,
            )
        return geodesic_chain_step(
            x, mp, op=op, fuse_k=plan.fuse_k, band_h=plan.band_h,
            interpret=_interpret(),
            vmem_limit_bytes=plan.vmem_limit_bytes, active=active,
            bands_per_image=plan.n_bands,
        )

    m_view = _halo_view(mp, plan, ident)

    def gather_const(idx):
        return _gather_patches(m_view, idx, plan, ident)

    def compact_step(x, idx, valid, mask_patch, base):
        f_patch = _gather_patches(_halo_view(x, plan, ident), idx, plan,
                                  ident)
        new_mid, ch = geodesic_compact_step(
            f_patch, mask_patch, valid,
            op=op, fuse_k=plan.fuse_k, band_h=plan.band_h,
            tile_w=_cell_tile_w(plan), interpret=_interpret(),
            vmem_limit_bytes=plan.vmem_limit_bytes,
        )
        x = _scatter_mid(x, idx, new_mid, plan)
        return x, _scatter_flags(ch, idx, plan)

    return _drive_scheduler(
        plan, fp, full_step=full_step, compact_step=compact_step,
        gather_const=gather_const, max_chunks=max_chunks,
        with_stats=with_stats, resume=resume, budget=budget,
    )


def _reconstruct_impl(f, m, op, backend, max_chunks, plan, with_stats=False):
    f3, was_2d = _as_stack(f)
    m3, _ = _as_stack(m)
    if f3.shape != m3.shape:
        raise ValueError(f"marker shape {f.shape} != mask shape {m.shape}")
    _plan_for(f3, plan)
    if plan is None:
        plan = plan_chain(
            f3.shape[1], f3.shape[2], f.dtype, None,
            n_images_resident=2, n_images=f3.shape[0], convergent=True,
        )
    k = plan.fuse_k
    if max_chunks is None:
        # Geodesic influence follows mask-constrained paths, whose length
        # is bounded by the pixel count (serpentine masks exceed the
        # H+W Chebyshev diameter).  The cap is a safety net only: the
        # loop exits as soon as the active set empties, so the
        # conservative bound costs nothing at runtime.
        max_chunks = (f3.shape[1] * f3.shape[2]) // k + 2
    ident = ident_for(op, f.dtype)
    fp = _stacked(_pad(f3, plan, ident))
    mp = _stacked(_pad(m3, plan, ident))

    out, chunks, asum, per_chunk, img_conv, _, _ = _scheduled_reconstruct(
        fp, mp, plan, op, max_chunks, with_stats
    )
    stats = ReconstructStats(
        chunks=chunks,
        active_band_sum=asum,
        total_bands=jnp.asarray(plan.total_tiles, jnp.int32),
        active_per_chunk=per_chunk,
        converged=jnp.all(img_conv),
    )
    return _crop(_unstacked(out, f3.shape[0]), f.shape, was_2d), stats


def reconstruct(
    f: jnp.ndarray,
    m: jnp.ndarray,
    op: str = "erode",
    backend: Backend | None = None,
    max_chunks: int | None = None,
    plan: ChainPlan | None = None,
) -> jnp.ndarray:
    """ε_rec / δ_rec with kernel-fused convergence detection (Alg. 4).

    Accepts (H, W) or (N, H, W); in batched mode each image converges
    independently (its bands go inactive and stop costing work).
    Routes through ``repro.api.compile``; ``backend=``/``max_chunks=``
    are deprecated here (bind them at compile time instead).
    """
    legacy = [n for n, v in (("backend", backend),
                             ("max_chunks", max_chunks)) if v is not None]
    if legacy:
        warn_legacy_kwargs("kernels.ops.reconstruct", *legacy)
    if f.shape != m.shape:
        raise ValueError(f"marker shape {f.shape} != mask shape {m.shape}")
    api = _api()
    expr = api.E.reconstruct(api.E.input("marker"), api.E.input("mask"),
                             op=op)
    exe = api.compile(expr, f.shape, f.dtype, backend, plan=plan,
                      max_chunks=max_chunks)
    return exe(f, m)


@functools.partial(
    jax.jit, static_argnames=("op", "backend", "max_chunks", "plan")
)
def reconstruct_with_stats(
    f: jnp.ndarray,
    m: jnp.ndarray,
    op: str = "erode",
    backend: Backend | None = None,
    max_chunks: int | None = None,
    plan: ChainPlan | None = None,
):
    """Like ``reconstruct`` but also returns :class:`ReconstructStats`
    (chunk count and band-level requeue accounting — the analogue of the
    paper's Table 5 chain lengths).  Engine/diagnostic entry point:
    ``backend``/``max_chunks``/``plan`` remain first-class here."""
    backend = canonicalize_backend(backend)
    if backend == "xla":
        iter_cap = (max_chunks if max_chunks is not None
                    else f.shape[-1] * f.shape[-2])
        out, iters = (
            M.erode_reconstruct_with_iters(f, m, iter_cap) if op == "erode"
            else M.dilate_reconstruct_with_iters(f, m, iter_cap)
        )
        one = jnp.asarray(1, jnp.int32)
        return out, ReconstructStats(
            chunks=iters, active_band_sum=iters, total_bands=one,
            active_per_chunk=jnp.zeros((0,), jnp.int32),
            # the oracle loop exits early iff a fixpoint was reached;
            # hitting the cap exactly leaves convergence unproven
            converged=iters < jnp.asarray(iter_cap, jnp.int32),
        )
    return _reconstruct_impl(f, m, op, backend, max_chunks, plan,
                             with_stats=True)


# ---------------------------------------------------------------------------
# quasi-distance transform (Alg. 5)
# ---------------------------------------------------------------------------


def _scheduled_qdt(fp, plan: ChainPlan, max_chunks: int, rp=None, dp=None,
                   resume=None, budget: int | None = None):
    """QDT's step functions for :func:`_drive_scheduler`.

    ``fp`` is the stacked (TOTAL_H, W_pad) image, padded with the
    erosion identity.  Returns the final (eroded, residual, distance)
    stacked planes plus the watchdog's per-image convergence vector, the
    resumable scheduler state and the driver's ``(compact_chunks,
    mask_gathers)`` counts; the residual accumulator dtype
    follows the paper's convention (float32 for float images, int32
    otherwise).  ``rp``/``dp`` accept mid-flight residual/distance
    planes (with ``resume``/``budget``) for bounded continuous-batching
    rounds — the per-image chunk counters in the resumed state keep the
    distance base offsets consistent across rounds.
    """
    k = plan.fuse_k
    acc = qdt_acc_dtype(fp.dtype)
    ident = ident_for("erode", fp.dtype)
    if rp is None:
        rp = jnp.zeros(fp.shape, acc)
    if dp is None:
        dp = jnp.zeros(fp.shape, jnp.int32)

    def full_step(data, active, base):
        x, r, d = data
        if plan.n_tiles > 1:
            x, r, d, ch = qdt_tile_step(
                x, r, d, jnp.broadcast_to(base, (plan.total_bands,
                                                 plan.n_tiles)),
                fuse_k=k, band_h=plan.band_h, tile_w=plan.tile_w,
                interpret=_interpret(),
                vmem_limit_bytes=plan.vmem_limit_bytes, active=active,
                bands_per_image=plan.n_bands,
            )
        else:
            x, r, d, ch = qdt_chain_step(
                x, r, d, base, fuse_k=k, band_h=plan.band_h,
                interpret=_interpret(),
                vmem_limit_bytes=plan.vmem_limit_bytes, active=active,
                bands_per_image=plan.n_bands,
            )
        return (x, r, d), ch

    def compact_step(data, idx, valid, const, base):
        x, r, d = data
        f_patch = _gather_patches(_halo_view(x, plan, ident), idx, plan,
                                  ident)
        rm = _gather_mid(r, idx, plan)
        dm = _gather_mid(d, idx, plan)
        # per-slot distance offset: each gathered cell carries its own
        # image's erosion count (sentinel slots clip — dropped anyway).
        base_slots = jnp.take(base.ravel(), idx // plan.n_tiles,
                              mode="clip")[:, None]
        f2, r2, d2, ch = qdt_compact_step(
            f_patch, rm, dm, valid, base_slots,
            fuse_k=k, band_h=plan.band_h, tile_w=_cell_tile_w(plan),
            interpret=_interpret(),
            vmem_limit_bytes=plan.vmem_limit_bytes,
        )
        x = _scatter_mid(x, idx, f2, plan)
        r = _scatter_mid(r, idx, r2, plan)
        d = _scatter_mid(d, idx, d2, plan)
        return (x, r, d), _scatter_flags(ch, idx, plan)

    (x, r, d), _, _, _, img_conv, state, counts = _drive_scheduler(
        plan, (fp, rp, dp), full_step=full_step, compact_step=compact_step,
        max_chunks=max_chunks, resume=resume, budget=budget,
    )
    return x, r, d, img_conv, state, counts


def qdt_planes(
    f: jnp.ndarray,
    backend: Backend | None = None,
    max_chunks: int | None = None,
    plan: ChainPlan | None = None,
):
    """d(f), r(f) of Eq. 13 with the fused masked-store kernel.

    Accepts (H, W) or (N, H, W); runs the same active-band requeue
    scheduler as ``reconstruct``.  Routes through
    ``repro.api.compile``; ``backend=``/``max_chunks=`` are deprecated
    here (bind them at compile time instead).
    """
    legacy = [n for n, v in (("backend", backend),
                             ("max_chunks", max_chunks)) if v is not None]
    if legacy:
        warn_legacy_kwargs("kernels.ops.qdt_planes", *legacy)
    api = _api()
    exe = api.compile(api.E.qdt(api.E.input("f")), f.shape, f.dtype,
                      backend, plan=plan, max_chunks=max_chunks)
    return exe(f)


# ---------------------------------------------------------------------------
# generalised geodesic distance transform (grey-weighted, FastGeodis-style)
# ---------------------------------------------------------------------------


def gdt_stage(ip: jnp.ndarray, sp: jnp.ndarray, nu: float):
    """Derive the kernel's three resident planes from the *padded*
    image/seed operands (both arrive with the float lattice bottom,
    −inf, as their absorbing pad fill).

    Returns ``(d0, i, s)``: the initial distance plane ``d0 = nu·(1−S)``
    (+inf on pads), the sanitized image (0 on pads, so the weight term
    never computes ``|−inf − (−inf)| = NaN``) and the seed/pad-marker
    plane (clipped to [0, 1] in the real region, −1 on pads — the value
    the kernels re-clamp ``d = +inf`` on after every elementary step).
    This is the single sanitization point: the kernels and the raster
    sweeps assume the planes are already in this form.
    """
    in_pad = jnp.isneginf(sp)
    sc = jnp.clip(sp, 0.0, 1.0)  # clip(−inf) → 0.0 without NaN
    d0 = jnp.where(in_pad, jnp.asarray(D_IDENT, ip.dtype),
                   (nu * (1.0 - sc)).astype(ip.dtype))
    i = jnp.where(in_pad, jnp.asarray(I_IDENT, ip.dtype), ip)
    s = jnp.where(in_pad, jnp.asarray(S_IDENT, ip.dtype), sc)
    return d0, i, s


def _scheduled_gdt(dp, ip, sp, plan: ChainPlan, lamb: float, max_chunks: int,
                   resume=None, budget: int | None = None):
    """gdt's step functions for :func:`_drive_scheduler` (the wavefront
    schedule).

    ``dp``/``ip``/``sp`` are stacked (TOTAL_H, W_pad) planes from
    :func:`gdt_stage`.  Only the distance plane evolves; the image and
    seed planes are chunk-invariant, so their compact-workspace patches
    go through the driver's ``gather_const`` cache as one pytree.
    Returns (d, img_converged, state, counts) — the same resumable
    contract as ``_scheduled_qdt``, which is what lets
    ``Executable.slot_session`` refill gdt slots mid-flight.
    """
    k = plan.fuse_k

    def full_step(d, active, base):
        if plan.n_tiles > 1:
            return gdt_tile_step(
                d, ip, sp, lamb=lamb, fuse_k=k, band_h=plan.band_h,
                tile_w=plan.tile_w, interpret=_interpret(),
                vmem_limit_bytes=plan.vmem_limit_bytes, active=active,
                bands_per_image=plan.n_bands,
            )
        return gdt_chain_step(
            d, ip, sp, lamb=lamb, fuse_k=k, band_h=plan.band_h,
            interpret=_interpret(),
            vmem_limit_bytes=plan.vmem_limit_bytes, active=active,
            bands_per_image=plan.n_bands,
        )

    i_view = _halo_view(ip, plan, I_IDENT)
    s_view = _halo_view(sp, plan, S_IDENT)

    def gather_const(idx):
        return (_gather_patches(i_view, idx, plan, I_IDENT),
                _gather_patches(s_view, idx, plan, S_IDENT))

    def compact_step(d, idx, valid, const, base):
        i_patch, s_patch = const
        d_patch = _gather_patches(_halo_view(d, plan, D_IDENT), idx, plan,
                                  D_IDENT)
        new_mid, ch = gdt_compact_step(
            d_patch, i_patch, s_patch, valid,
            lamb=lamb, fuse_k=k, band_h=plan.band_h,
            tile_w=_cell_tile_w(plan), interpret=_interpret(),
            vmem_limit_bytes=plan.vmem_limit_bytes,
        )
        d = _scatter_mid(d, idx, new_mid, plan)
        return d, _scatter_flags(ch, idx, plan)

    d, _, _, _, img_conv, state, counts = _drive_scheduler(
        plan, dp, full_step=full_step, compact_step=compact_step,
        gather_const=gather_const, max_chunks=max_chunks,
        resume=resume, budget=budget,
    )
    return d, img_conv, state, counts


def _shift_row(x: jnp.ndarray, dx: int, fill):
    """(N, W) row batch translated along W with ``fill`` at the border."""
    if dx == 1:
        return jnp.concatenate(
            [jnp.full_like(x[:, :1], fill), x[:, :-1]], axis=1)
    if dx == -1:
        return jnp.concatenate(
            [x[:, 1:], jnp.full_like(x[:, :1], fill)], axis=1)
    return x


def _gdt_sweep(d3, i3, s3, lamb: float, reverse: bool):
    """One directional raster pass: a ``lax.scan`` over rows (axis 1)
    carrying the *updated* previous row, relaxing each row against its
    three upper (``reverse=False``) or lower (``reverse=True``)
    neighbours.  The left/right passes run this on the W↔H transposed
    planes; across the four directions the candidate sets cover the
    full 8-neighbourhood, so iterating rounds to a fixpoint lands on
    the same bits as the wavefront scheduler (see
    ``repro.gdt.reference``)."""
    inf = jnp.asarray(D_IDENT, d3.dtype)
    xs = (jnp.moveaxis(d3, 1, 0), jnp.moveaxis(i3, 1, 0),
          jnp.moveaxis(s3, 1, 0))

    def step(carry, row):
        prev_d, prev_i = carry
        d_row, i_row, s_row = row
        best = d_row
        for dx in (-1, 0, 1):
            dq = _shift_row(prev_d, dx, inf)
            if lamb == 0.0:
                cand = dq + 1.0
            else:
                iq = _shift_row(prev_i, dx, jnp.asarray(I_IDENT, d3.dtype))
                # outer abs blocks fmul+fadd→fma contraction (see
                # kernels.gdt_chain.elementary_gdt)
                cand = dq + (1.0 + jnp.abs(lamb * jnp.abs(i_row - iq)))
            best = jnp.minimum(best, cand)
        new_d = jnp.where(s_row < 0, inf, best)
        return (new_d, i_row), new_d

    init = (jnp.full_like(d3[:, 0], inf), jnp.zeros_like(d3[:, 0]))
    _, out = jax.lax.scan(step, init, xs, reverse=reverse)
    return jnp.moveaxis(out, 0, 1)


def _raster_gdt(dp, ip, sp, plan: ChainPlan, lamb: float, max_rounds: int):
    """The raster-scan schedule: FastGeodis-style down/up/left/right
    sweeps iterated to fixpoint (``plan.schedule == "raster"``).

    Runs on the *unstacked* (N, H_pad, W_pad) view — the scans walk
    rows/columns of each image separately, so batched images can never
    leak into each other (no band-halo pinning needed).  Returns
    ``(d, rounds, img_converged)`` with ``d`` re-stacked; an image
    unchanged by the last full round is at its fixpoint (the sweeps are
    deterministic per image), so the convergence vector is exact even
    when the round budget truncates the others.
    """
    n = plan.n_images
    d3, i3, s3 = (_unstacked(x, n) for x in (dp, ip, sp))
    i3t, s3t = i3.swapaxes(1, 2), s3.swapaxes(1, 2)

    def one_round(d3):
        d3 = _gdt_sweep(d3, i3, s3, lamb, reverse=False)
        d3 = _gdt_sweep(d3, i3, s3, lamb, reverse=True)
        d3t = _gdt_sweep(d3.swapaxes(1, 2), i3t, s3t, lamb, reverse=False)
        d3t = _gdt_sweep(d3t, i3t, s3t, lamb, reverse=True)
        return d3t.swapaxes(1, 2)

    def cond(state):
        _, it, changed = state
        return jnp.logical_and(jnp.any(changed), it < max_rounds)

    def body(state):
        d, it, _ = state
        new = one_round(d)
        changed = jnp.any(new != d, axis=(1, 2))
        return new, it + 1, changed

    d3, rounds, changed = jax.lax.while_loop(
        cond, body,
        (d3, jnp.asarray(0, jnp.int32), jnp.ones((n,), jnp.bool_)),
    )
    return _stacked(d3), rounds, jnp.logical_not(changed)


def gdt_fixpoint_xla(d: jnp.ndarray, img3: jnp.ndarray, seeds3: jnp.ndarray,
                     lamb: float, max_iters: int) -> jnp.ndarray:
    """Pure-jnp Jacobi oracle on the (..., H, W) planes of
    :func:`gdt_stage` — the "xla" backend body, bit-exact with
    ``repro.gdt.reference`` by the shared fold-cost argument.  Like the
    kernels, every step clamps the pad cells (seed marker < 0) back to
    +inf, so a bucket-padded stack answers its real region exactly as
    the unpadded one.  Axis-polymorphic over leading batch dims (2-D
    executables keep 2-D arrays end-to-end)."""
    dtype = img3.dtype
    inf = jnp.asarray(jnp.inf, dtype)
    in_pad = seeds3 < 0

    def shift(x, dy, dx, fill):
        pad = ([(0, 0)] * (x.ndim - 2)
               + [(max(dy, 0), max(-dy, 0)), (max(dx, 0), max(-dx, 0))])
        y = jnp.pad(x, pad, constant_values=fill)
        h, w = x.shape[-2], x.shape[-1]
        return y[..., max(-dy, 0): max(-dy, 0) + h,
                 max(-dx, 0): max(-dx, 0) + w]

    offsets = [(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)
               if (dy, dx) != (0, 0)]
    if lamb == 0.0:
        weights = [jnp.asarray(1.0, dtype)] * len(offsets)
    else:
        # outer abs blocks fmul+fadd→fma contraction (see
        # kernels.gdt_chain.elementary_gdt)
        weights = [
            1.0 + jnp.abs(lamb * jnp.abs(img3 - shift(img3, dy, dx, 0.0)))
            for dy, dx in offsets
        ]

    def cond(state):
        d, prev, it = state
        return jnp.logical_and(jnp.any(d != prev), it < max_iters)

    def body(state):
        d, _, it = state
        cand = d
        for (dy, dx), w in zip(offsets, weights):
            cand = jnp.minimum(cand, shift(d, dy, dx, inf) + w)
        return jnp.where(in_pad, inf, cand), d, it + 1

    d, _, _ = jax.lax.while_loop(
        cond, body, (d, jnp.full_like(d, -inf), jnp.asarray(0, jnp.int32)))
    return d


def gdt(
    image: jnp.ndarray,
    seeds: jnp.ndarray,
    lamb: float = 1.0,
    nu: float = 1e6,
    backend: Backend | None = None,
    max_chunks: int | None = None,
    plan: ChainPlan | None = None,
) -> jnp.ndarray:
    """Generalised geodesic distance transform (see ``E.gdt``).

    Accepts (H, W) or (N, H, W) image/seed stacks; float dtypes only.
    Routes through ``repro.api.compile``; pass a ``plan`` with
    ``schedule="raster"`` to select the sweep schedule.
    ``backend=``/``max_chunks=`` are deprecated here (bind them at
    compile time instead).
    """
    legacy = [n for n, v in (("backend", backend),
                             ("max_chunks", max_chunks)) if v is not None]
    if legacy:
        warn_legacy_kwargs("kernels.ops.gdt", *legacy)
    # resolve dtypes the way execution will: without x64, jnp downcasts
    # a NumPy float64 to float32 — compile at the post-cast dtype
    image = jnp.asarray(image)
    seeds = jnp.asarray(seeds)
    if jnp.dtype(image.dtype).kind != "f":
        raise TypeError(
            f"gdt: image must be a float dtype, got {image.dtype} (the "
            "distance plane is a float lattice)"
        )
    if image.shape != seeds.shape:
        raise ValueError(
            f"image shape {image.shape} != seeds shape {seeds.shape}")
    api = _api()
    expr = api.E.gdt(api.E.input("image"), api.E.input("seeds"),
                     lamb=lamb, nu=nu)
    exe = api.compile(expr, image.shape, image.dtype, backend, plan=plan,
                      max_chunks=max_chunks)
    return exe(image, seeds)


# ---------------------------------------------------------------------------
# serving registry hooks
# ---------------------------------------------------------------------------

#: Registry hooks for ``repro.serve``: every public kernel op declared
#: as data next to its implementation — a string name, a param schema
#: and an *expression builder*.  ``repro.serve.registry`` lowers the
#: expression (``repro.api.lower``) and derives the pipeline stages,
#: pad fills and bucket identity mechanically from the lowered program;
#: nothing op-specific lives in the registry anymore.
SERVE_OPS = (
    dict(name="erode",
         expr=lambda p: _api().E.erode(p["s"], _api().E.input("f")),
         params={"s": dict(type="int", required=True, min=1)}),
    dict(name="dilate",
         expr=lambda p: _api().E.dilate(p["s"], _api().E.input("f")),
         params={"s": dict(type="int", required=True, min=1)}),
    dict(name="opening",
         expr=lambda p: _api().E.opening(p["s"], _api().E.input("f")),
         params={"s": dict(type="int", required=True, min=1)}),
    dict(name="closing",
         expr=lambda p: _api().E.closing(p["s"], _api().E.input("f")),
         params={"s": dict(type="int", required=True, min=1)}),
    dict(name="reconstruct",
         expr=lambda p: _api().E.reconstruct(_api().E.input("marker"),
                                             _api().E.input("mask"),
                                             op=p["op"]),
         params={"op": dict(type="str", default="dilate",
                            choices=("erode", "dilate"))}),
    dict(name="geodesic",
         expr=lambda p: _api().E.geodesic(_api().E.input("marker"),
                                          _api().E.input("mask"),
                                          p["n"], p["op"]),
         params={"n": dict(type="int", required=True, min=1),
                 "op": dict(type="str", default="erode",
                            choices=("erode", "dilate"))}),
    dict(name="qdt",
         expr=lambda p: _api().E.qdt(_api().E.input("f")),
         params={}),
)
