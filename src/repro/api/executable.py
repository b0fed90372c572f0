"""Executable: a compiled expression bound to (shape, dtype, backend).

The run phase executes the lowered :class:`~repro.api.lower.Program`
as **one padded program per plan group**: by default every canonical
input is padded to the shared :class:`~repro.core.chain.ChainPlan`
exactly once, all kernel segments run on the vertically stacked
``(N·H_pad, W_pad)`` working arrays (chains via ``chain_step`` scans,
convergence-driven segments via the requeue scheduler in
``kernels/ops.py``), and outputs are cropped exactly once.  Between
segments that need a different absorbing identity in the pad region,
the lowered ``refill`` segments apply a masked fill in place of the
legacy crop → re-pad → re-plan round-trip.

When the compiler specializes a mixed program (``compile(...,
specialize=...)``), the segment list is partitioned into contiguous
*plan groups* — fixed-length chain groups and convergent
reconstruction/QDT groups — each with its own ``ChainPlan``
(``seg_plans``).  Values crossing a group boundary take a *re-band*
round-trip: cropped out of the producer group's band layout and
re-padded with the pad identity the consumer group's lowering expects,
so the halo-exactness argument of each group composes unchanged.

``backend="xla"`` executes the same program with the pure-jnp oracle
bodies on unpadded arrays — bit-exact with the Pallas path by the
repo's exactness convention (see ``docs/ARCHITECTURE.md``).

``Executable.key`` — the lowered run signature + bound shape/dtype/
backend + ``plan.key`` (+ the per-group plan keys when specialized) —
is simultaneously the compile-cache key and the ``repro.serve``
bucket/cache identity, which is what lets different operators with
identical run phases (HMAX vs DOME) share one compiled bucket program.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.api.lower import Program, eval_pointwise
from repro.core import morphology as M
from repro.core import operators as OPS
from repro.kernels.common import ident_for
from repro.kernels.erode_chain import chain_step
from repro.kernels.geodesic_chain import geodesic_chain_step

#: pad-fill name → the op whose lattice identity it is
_FILL_OP = {"hi": "erode", "lo": "dilate"}

#: op → the absorbing pad identity its operands need (dual of _FILL_OP)
_NEED_FILL = {"erode": "hi", "dilate": "lo"}


def _fill_value(fill: str, dtype):
    return ident_for(_FILL_OP[fill], dtype)


class SlotSession(NamedTuple):
    """Jitted entry points for continuous batching over one resident
    device-state *session* (see :meth:`Executable.slot_session`).

    The session owns a persistent padded stack whose ``n_slots`` row
    blocks are independent images under the requeue scheduler; slots
    park (activity cleared → zero work) and are re-armed in place.
    All callables are pure: they take the session state and return the
    next one.

    ``init()``
        fresh state: every slot parked, planes filled with the
        program's absorbing pad identities.
    ``admit(state, slot, *canonical) -> state``
        write one request's canonical (H, W) inputs into ``slot``'s
        row block (padded with the program's fills), re-arm its
        activity rows, and zero its chunk counter — exactly the
        initial condition a solo run of that image starts from.
    ``round(state) -> (state, finished, exhausted)``
        run at most ``n_chunks`` scheduler chunks over every active
        slot.  ``finished`` is (n_slots,) bool — the slot's active set
        is empty (converged, budget-truncated, or parked); a finished
        *occupied* slot is ready to harvest and refill.  ``exhausted``
        flags slots cut off by the per-image chunk budget (degraded
        partial fixpoints).
    ``extract(state) -> outputs``
        cropped (n_slots, H, W) run outputs (program output order).
    ``chunks_of(state) -> (n_slots,) int32``
        cumulative scheduler chunks each slot's image has consumed —
        the raw material for chunk-weighted work-occupancy accounting
        (round-over-round deltas are what a slot actually did).
    """

    n_slots: int
    n_chunks: int
    init: Any
    admit: Any
    round: Any
    extract: Any
    chunks_of: Any


def _seg_need_fill(seg) -> str:
    """Pad identity ``seg`` expects of an operand re-entering padded
    form at a group boundary."""
    if seg.kind == "refill":
        # the masked fill overwrites the pad region anyway
        return seg.param("fill")
    if seg.kind == "qdt":
        return "hi"  # the QDT iterates erosion
    if seg.kind in ("gdt", "point"):
        # gdt stages its own planes from −inf-marked operands; point
        # outputs are re-masked by a refill before any kernel consumer
        return "lo"
    return _NEED_FILL[seg.param("op")]


class Executable:
    """A lowered program bound to a concrete (N, H, W)/dtype/backend.

    Call it with the expression's input arrays (in
    ``program.input_names`` order) to run prepare → run → finalize;
    ``run_batch`` runs the run phase alone on canonical inputs (the
    serve executor's per-bucket program).  ``stats()`` reports the
    static pad/launch/refill accounting of the compiled program — the
    fusion wins of the expression API are visible there.

    ``seg_plans`` (keyword-only) activates per-segment plan
    specialization: a tuple of ``(segment_indices, ChainPlan)`` groups
    covering ``program.segments`` in order.  ``plan`` then remains the
    primary (first-group) plan for introspection; ``all_plans`` lists
    every group's.  ``rewrite_trace`` carries the optimizer's
    :class:`~repro.opt.engine.Applied` steps for this program (empty
    when compiled with ``rewrite=False`` or nothing fired) — the
    soundness hook in ``repro.analysis.rewrites`` replays it.
    """

    def __init__(self, program: Program, shape3: tuple, dtype, backend: str,
                 plan, max_chunks: int | None, was_2d: bool, *,
                 seg_plans=None, rewrite_trace=()):
        self.program = program
        self.n_images, self.height, self.width = shape3
        self.dtype = jnp.dtype(dtype)
        self.backend = backend
        self.plan = plan
        self.max_chunks = max_chunks
        self.was_2d = was_2d
        self.seg_plans = tuple(seg_plans) if seg_plans else None
        self.rewrite_trace = tuple(rewrite_trace)
        self._mask_cache: dict = {}
        self._sessions: dict = {}
        if plan is not None:
            self._max_chunks_rec = self._budget_rec(plan)
            self._max_chunks_qdt = self._budget_qdt(plan)
        # Every field that can change what a call computes or returns
        # must appear here — ``repro.analysis.cachekeys`` perturbs each
        # one and asserts the key moves (``max_chunks`` truncates
        # convergent segments; ``was_2d`` changes the output rank).
        # ``rewrite_trace`` is deliberately absent: it is provenance,
        # not behaviour — the program it produced is already keyed.
        seg_key = (tuple((idxs, p.key) for idxs, p in self.seg_plans)
                   if self.seg_plans is not None else None)
        self.key = (
            program.run_sig, shape3, str(self.dtype), backend,
            plan.key if plan is not None else None,
            max_chunks, was_2d, seg_key,
        )

    # -- public ------------------------------------------------------------

    def __call__(self, *arrays, **named):
        names = self.program.input_names
        if named:
            if arrays:
                raise TypeError("pass inputs positionally or by name, "
                                "not both")
            try:
                arrays = tuple(named.pop(n) for n in names)
            except KeyError as e:
                raise TypeError(f"missing input {e.args[0]!r}") from None
            if named:
                raise TypeError(f"unknown inputs {sorted(named)} "
                                f"(expected {list(names)})")
        if len(arrays) != len(names):
            raise TypeError(
                f"expression takes {len(names)} input(s) {list(names)}, "
                f"got {len(arrays)}"
            )
        arrays = tuple(self._check(jnp.asarray(a)) for a in arrays)
        outs = self._call_fn(*arrays)
        return outs[0] if self.program.n_outputs == 1 else outs

    def run_batch(self, *canonical):
        """Run phase only: canonical (N, H, W) inputs → cropped run
        outputs (always a tuple) — the serve bucket entry point."""
        return self._run_fn(*canonical)

    def run_batch_stats(self, *canonical):
        """Run phase plus the convergence watchdog's verdict, chunk
        utilization and scheduler counts: ``(outputs, converged,
        busy_chunks, cap_chunks, compact_chunks, mask_gathers)``.
        ``converged`` is a (N,) bool vector, False for images whose
        convergence-driven segments exhausted the chunk budget
        (``ReconstructStats.converged`` per image, AND-ed across
        segments).  The serve executor demuxes it into per-request
        degraded flags; programs without convergent segments (and the
        jnp oracle engine, which iterates to its own fixpoint) report
        all-True.  ``busy_chunks``/``cap_chunks`` are int32 scalars:
        scheduler chunks the images actually consumed vs the chunks the
        batch held every slot for (summed across convergence-driven
        segments; both 0 when there are none) — the serving layer's
        chunk-weighted work-occupancy accounting, which exposes the
        dead capacity of early-converged slots parked behind a
        straggler.  ``compact_chunks``/``mask_gathers`` are int32
        scalars too: scheduler chunks that ran on the compacted grid,
        and those of them that re-gathered the chunk-invariant operands
        (the mask patches) because the active cell set moved (summed
        across segments; see ``kernels.ops._drive_scheduler``)."""
        return self._run_stats_fn(*canonical)

    @property
    def refillable(self) -> bool:
        """True when this program can run as a continuous-batching slot
        session: a single convergence-driven segment (reconstruct/QDT/
        gdt) under one pallas plan, compiled for a 3-D batch.
        Fixed-length chains gain nothing from refill (no stragglers to
        wait behind), multi-segment/specialized programs re-band
        between plans, which has no per-slot resumable state, and the
        raster gdt schedule sweeps whole images (no per-slot activity
        grid to park and resume)."""
        prog = self.program
        return (self.plan is not None
                and self.seg_plans is None
                and not self.was_2d
                and len(prog.segments) == 1
                and prog.segments[0].kind in ("reconstruct", "qdt", "gdt")
                and self.plan.schedule == "wavefront")

    def slot_session(self, n_chunks: int) -> SlotSession:
        """Build (or fetch) the :class:`SlotSession` entry points for
        continuous batching with rounds of ``n_chunks`` scheduler
        chunks.  Requires :attr:`refillable`.

        Bit-exactness: a slot admitted mid-flight starts from exactly
        the state a fresh solo batch would stage for it (same absorbing
        pads, all-active rows, zero chunk counter), and the scheduler's
        per-image independence (image-pinned halos + inactive-cell
        skip) means later rounds apply the same chunk sequence a solo
        run would — so harvested outputs equal solo execution bit for
        bit.  Budget-truncated slots are flagged exhausted and match a
        solo run under ``max_chunks=budget`` (see ``_drive_scheduler``).
        """
        cached = self._sessions.get(n_chunks)
        if cached is not None:
            return cached
        if not self.refillable:
            raise ValueError(
                f"{self!r} is not refillable (continuous batching needs a "
                "single convergent segment on the pallas backend)")
        if n_chunks < 1:
            raise ValueError("n_chunks must be >= 1")
        from repro.kernels.common import qdt_acc_dtype
        from repro.kernels.gdt_chain import D_IDENT, I_IDENT, S_IDENT
        from repro.kernels.ops import (_crop3, _scheduled_gdt,
                                       _scheduled_qdt,
                                       _scheduled_reconstruct, gdt_stage)

        prog = self.program
        seg = prog.segments[0]
        plan = self.plan
        n, h, w = self.n_images, self.height, self.width
        hp, wp = plan.height_pad, plan.width_pad
        fills = dict(self._exec_groups[0][2])  # slot -> pad fill name

        def plane(fill: str, dtype):
            return jnp.full((n * hp, wp), _fill_value(fill, dtype), dtype)

        def write(p, slot, img, fill: str):
            tile = jnp.pad(img, ((0, hp - h), (0, wp - w)),
                           constant_values=_fill_value(fill, img.dtype))
            return jax.lax.dynamic_update_slice(p, tile, (slot * hp, 0))

        def zero_rows(p, slot):
            return jax.lax.dynamic_update_slice(
                p, jnp.zeros((hp, wp), p.dtype), (slot * hp, 0))

        def arm(sched, slot):
            active, chunks, exhausted = sched
            active = jax.lax.dynamic_update_slice(
                active, jnp.ones((plan.n_bands, plan.n_tiles), jnp.int32),
                (slot * plan.n_bands, 0))
            chunks = jax.lax.dynamic_update_slice(
                chunks, jnp.zeros((1,), jnp.int32), (slot,))
            exhausted = jax.lax.dynamic_update_slice(
                exhausted, jnp.zeros((1,), jnp.bool_), (slot,))
            return active, chunks, exhausted

        def sched0():
            # all slots parked: no active cells, nothing costs work
            return (jnp.zeros((plan.total_bands, plan.n_tiles), jnp.int32),
                    jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n,), jnp.bool_))

        def crops(vals: dict):
            return tuple(_crop3(vals[s], n, h, w)
                         for s in prog.run_outputs)

        if seg.kind == "reconstruct":
            op = seg.param("op")
            budget = self._budget_rec(plan)
            f_slot, m_slot = seg.srcs

            def init():
                return (plane(fills[f_slot], self.dtype),
                        plane(fills[m_slot], self.dtype), *sched0())

            def admit(state, slot, marker, mask):
                fp, mp, *sched = state
                fp = write(fp, slot, marker, fills[f_slot])
                mp = write(mp, slot, mask, fills[m_slot])
                return (fp, mp, *arm(tuple(sched), slot))

            def round_(state):
                fp, mp, *sched = state
                fp, _, _, _, finished, sched, _ = _scheduled_reconstruct(
                    fp, mp, plan, op, n_chunks, False,
                    resume=tuple(sched), budget=budget)
                return (fp, mp, *sched), finished, sched[2]

            def extract(state):
                return crops({seg.dsts[0]: state[0]})

            def chunks_of(state):
                return state[3]

        elif seg.kind == "gdt":
            budget = self._budget_rec(plan)
            i_slot, s_slot = seg.srcs
            lamb, nu = seg.param("lamb"), seg.param("nu")

            def ident_plane(v):
                return jnp.full((n * hp, wp), jnp.asarray(v, self.dtype),
                                self.dtype)

            def init():
                # parked slots hold the kernel's halo identities: +inf
                # distance, zero image, −1 seed marker (clamped region)
                return (ident_plane(D_IDENT), ident_plane(I_IDENT),
                        ident_plane(S_IDENT), *sched0())

            def admit(state, slot, image, seeds):
                d, ip, sp, *sched = state
                img_t = jnp.pad(
                    image, ((0, hp - h), (0, wp - w)),
                    constant_values=_fill_value(fills[i_slot], image.dtype))
                sd_t = jnp.pad(
                    seeds, ((0, hp - h), (0, wp - w)),
                    constant_values=_fill_value(fills[s_slot], seeds.dtype))
                d0, i_t, s_t = gdt_stage(img_t, sd_t, nu)
                at = (slot * hp, 0)
                d = jax.lax.dynamic_update_slice(d, d0, at)
                ip = jax.lax.dynamic_update_slice(ip, i_t, at)
                sp = jax.lax.dynamic_update_slice(sp, s_t, at)
                return (d, ip, sp, *arm(tuple(sched), slot))

            def round_(state):
                d, ip, sp, *sched = state
                d, finished, sched, _ = _scheduled_gdt(
                    d, ip, sp, plan, lamb, n_chunks,
                    resume=tuple(sched), budget=budget)
                return (d, ip, sp, *sched), finished, sched[2]

            def extract(state):
                return crops({seg.dsts[0]: state[0]})

            def chunks_of(state):
                return state[4]

        else:  # qdt
            budget = self._budget_qdt(plan)
            x_slot = seg.srcs[0]
            acc = qdt_acc_dtype(self.dtype)

            def init():
                return (plane(fills[x_slot], self.dtype),
                        jnp.zeros((n * hp, wp), acc),
                        jnp.zeros((n * hp, wp), jnp.int32), *sched0())

            def admit(state, slot, f):
                x, r, d, *sched = state
                x = write(x, slot, f, fills[x_slot])
                r = zero_rows(r, slot)
                d = zero_rows(d, slot)
                return (x, r, d, *arm(tuple(sched), slot))

            def round_(state):
                x, r, d, *sched = state
                x, r, d, finished, sched, _ = _scheduled_qdt(
                    x, plan, n_chunks, rp=r, dp=d,
                    resume=tuple(sched), budget=budget)
                return (x, r, d, *sched), finished, sched[2]

            def extract(state):
                return crops({seg.dsts[0]: state[2], seg.dsts[1]: state[1]})

            def chunks_of(state):
                return state[4]

        session = SlotSession(
            n_slots=n, n_chunks=n_chunks, init=jax.jit(init),
            admit=jax.jit(admit), round=jax.jit(round_),
            extract=jax.jit(extract), chunks_of=chunks_of,
        )
        self._sessions[n_chunks] = session
        return session

    @property
    def all_plans(self) -> tuple:
        """Every ChainPlan this executable runs under (primary first)."""
        if self.seg_plans is not None:
            return tuple(p for _, p in self.seg_plans)
        return (self.plan,) if self.plan is not None else ()

    def stats(self) -> dict:
        """Static accounting of the compiled program (pads, launches,
        refills): what the fusion tests count.  ``pads``/``crops`` are
        the pad/crop round-trips of one execution (including the
        re-band round-trips at specialized group boundaries); the
        legacy per-stage path pays one of each per elementary operator
        stage.  ``plans`` counts the plan groups, ``rebands`` the group
        boundaries values re-band across.  ``convergent``/``chunk_budget_rec``/
        ``chunk_budget_qdt`` describe the watchdog configuration the
        convergence-driven segments run under; the *runtime* verdict
        for a particular execution comes from :meth:`run_batch_stats`
        (or ``ReconstructStats.converged`` on the engine entry
        points)."""
        prog = self.program
        groups = self._exec_groups
        return {
            "backend": self.backend,
            "pads": sum(len(pads) for _, _, pads, _ in groups),
            "crops": sum(len(crops) for _, _, _, crops in groups),
            "launches": len(prog.kernel_segments),
            "refills": sum(1 for s in prog.segments if s.kind == "refill"),
            "fused_chain_len": prog.fused_chain_len,
            "plan_key": self.plan.key if self.plan is not None else None,
            "plans": len(groups),
            "rebands": max(0, len(groups) - 1),
            "convergent": prog.convergent,
            "chunk_budget_rec": (self._max_chunks_rec
                                 if self.plan is not None else None),
            "chunk_budget_qdt": (self._max_chunks_qdt
                                 if self.plan is not None else None),
        }

    def compiled_text(self) -> str:
        """HLO text of the compiled :meth:`run_batch_stats` program at
        this executable's shapes.  It is lowered and compiled anew; with
        the persistent compilation cache enabled the compile finds the
        program the calls ran, so its instruction names match theirs."""
        shape = ((self.height, self.width) if self.was_2d
                 else (self.n_images, self.height, self.width))
        arg = jax.ShapeDtypeStruct(shape, self.dtype)
        args = [arg] * len(self.program.run_input_slots)
        return self._run_stats_fn.lower(*args).compile().as_text()

    def __repr__(self):
        return (f"Executable({self.program.sig_label()}, "
                f"shape=({self.n_images}, {self.height}, {self.width}), "
                f"dtype={self.dtype}, backend={self.backend!r})")

    # -- internals ---------------------------------------------------------

    def _check(self, a):
        # 2-D executables keep 2-D arrays end-to-end (XLA:CPU handles a
        # leading unit dim poorly); the pallas engine promotes privately.
        want = ((self.height, self.width) if self.was_2d
                else (self.n_images, self.height, self.width))
        if tuple(a.shape) != want:
            raise ValueError(
                f"input shape {a.shape} does not match the compiled "
                f"shape {want}"
            )
        if a.dtype != self.dtype:
            raise ValueError(
                f"input dtype {a.dtype} does not match the compiled "
                f"dtype {self.dtype}"
            )
        return a

    def _budget_rec(self, plan) -> int:
        return (self.max_chunks if self.max_chunks is not None
                else (self.height * self.width) // plan.fuse_k + 2)

    def _budget_qdt(self, plan) -> int:
        return (self.max_chunks if self.max_chunks is not None
                else max(self.height, self.width) // plan.fuse_k + 2)

    @functools.cached_property
    def _call_fn(self):
        return jax.jit(self._pipeline)

    @functools.cached_property
    def _run_fn(self):
        return jax.jit(self._run_segments)

    @functools.cached_property
    def _run_stats_fn(self):
        return jax.jit(self._run_segments_stats)

    def _pipeline(self, *inputs3):
        prog = self.program
        env = dict(zip(prog.input_names, inputs3))
        canonical = [eval_pointwise(e, env, {}, {}) for e in prog.prepare]
        cropped = self._run_segments(*canonical)
        kernel_vals = {
            (node, i): cropped[j]
            for j, (node, i, _) in enumerate(prog.kernel_outputs)
        }
        memo = {}
        return tuple(eval_pointwise(e, env, kernel_vals, memo)
                     for e in prog.result_exprs())

    def _run_segments(self, *canonical):
        if self.plan is None:
            return self._run_xla(canonical)
        return self._run_padded(canonical)

    def _run_segments_stats(self, *canonical):
        """Run phase + (N,) convergence vector + chunk utilization and
        scheduler counts (see run_batch_stats)."""
        all_ok = jnp.ones((self.n_images,), jnp.bool_)
        zero = jnp.zeros((), jnp.int32)
        if self.plan is None:
            # the jnp oracle bodies iterate to their own fixpoint
            return self._run_xla(canonical), all_ok, zero, zero, zero, zero
        conv: list = []
        util: list = []
        outs = self._run_padded(canonical, conv, util)
        for vec in conv:
            all_ok = jnp.logical_and(all_ok, vec)
        sums = [zero] * 4
        for u in util:
            sums = [a + b for a, b in zip(sums, u)]
        return (outs, all_ok, *sums)

    # -- xla engine: the jnp oracle bodies, unpadded -----------------------

    def _run_xla(self, canonical):
        vals = {}
        for slot, x3 in zip(self.program.run_input_slots, canonical):
            vals[slot] = x3
        for seg in self.program.segments:
            if seg.kind == "refill":       # no padding exists to refill
                vals[seg.dsts[0]] = vals[seg.srcs[0]]
            elif seg.kind == "chain":
                body = (M.erode3 if seg.param("op") == "erode"
                        else M.dilate3)
                vals[seg.dsts[0]] = jax.lax.fori_loop(
                    0, seg.param("n"), lambda _, y, b=body: b(y),
                    vals[seg.srcs[0]],
                )
            elif seg.kind == "geodesic":
                step = (M.geodesic_erode if seg.param("op") == "erode"
                        else M.geodesic_dilate)
                vals[seg.dsts[0]] = step(vals[seg.srcs[0]],
                                         vals[seg.srcs[1]], seg.param("n"))
            elif seg.kind == "reconstruct":
                rec = (M.erode_reconstruct if seg.param("op") == "erode"
                       else M.dilate_reconstruct)
                vals[seg.dsts[0]] = rec(vals[seg.srcs[0]], vals[seg.srcs[1]])
            elif seg.kind == "qdt":
                d, r = OPS.qdt_raw(vals[seg.srcs[0]])
                vals[seg.dsts[0]], vals[seg.dsts[1]] = d, r
            elif seg.kind == "gdt":
                from repro.kernels.ops import gdt_fixpoint_xla, gdt_stage

                # bucket pads arrive as −inf, as on the pallas path
                d0, ip, sp = gdt_stage(vals[seg.srcs[0]],
                                       vals[seg.srcs[1]], seg.param("nu"))
                # Jacobi advances every shortest path by ≥1 edge per
                # iteration; H·W bounds any simple path's length.
                vals[seg.dsts[0]] = gdt_fixpoint_xla(
                    d0, ip, sp, seg.param("lamb"),
                    self.height * self.width + 2,
                )
            elif seg.kind == "point":
                env = {f"__p{j}": vals[s]
                       for j, s in enumerate(seg.srcs)}
                vals[seg.dsts[0]] = eval_pointwise(
                    seg.param("expr"), env, {}, {})
            else:  # pragma: no cover
                raise AssertionError(seg.kind)
        return tuple(vals[s] for s in self.program.run_outputs)

    # -- pallas engine: one padded program per plan group ------------------

    @property
    def _groups(self) -> tuple:
        """``(segment_indices, plan)`` plan groups, in execution order."""
        if self.seg_plans is not None:
            return self.seg_plans
        if self.plan is None:
            return ()
        return ((tuple(range(len(self.program.segments))), self.plan),)

    @functools.cached_property
    def _exec_groups(self) -> tuple:
        """Static execution schedule: per group, the ``(slot, fill)``
        pads to apply on entry (first-consume order) and the dst slots
        to crop back to unpadded form on exit (consumed by a later
        group, or a run output)."""
        prog = self.program
        segs = prog.segments
        groups = self._groups
        # abstract pad state a slot's cropped value must be re-padded
        # with: inputs carry their declared fill, refill outputs their
        # target fill; kernel outputs are dirty (None) — only a masked
        # refill may consume them across a boundary, and its own fill
        # is then used (the mask overwrites the pad region regardless).
        fill_state: dict = dict(zip(prog.run_input_slots, prog.run_fills))
        for seg in segs:
            for d in seg.dsts:
                fill_state[d] = (seg.param("fill") if seg.kind == "refill"
                                 else None)
        out = []
        for gi, (idxs, plan) in enumerate(groups):
            local: set = set()
            pad_map: dict = {}
            for i in idxs:
                seg = segs[i]
                for s in seg.srcs:
                    if s in local or s in pad_map:
                        continue
                    pad_map[s] = fill_state.get(s) or _seg_need_fill(seg)
                local.update(seg.dsts)
            later: set = set(prog.run_outputs)
            for idxs2, _ in groups[gi + 1:]:
                for i in idxs2:
                    later.update(segs[i].srcs)
            crops = tuple(d for i in idxs for d in segs[i].dsts
                          if d in later)
            out.append((tuple(idxs), plan, tuple(pad_map.items()), crops))
        return tuple(out)

    def _image_mask(self, plan):
        """(TOTAL_H, W_pad) bool: True inside the real image regions."""
        mask = self._mask_cache.get(plan.key)
        if mask is None:
            rows = (jnp.arange(plan.n_images * plan.height_pad)
                    % plan.height_pad) < self.height
            cols = jnp.arange(plan.width_pad) < self.width
            mask = rows[:, None] & cols[None, :]
            self._mask_cache[plan.key] = mask
        return mask

    def _run_padded(self, canonical, conv: list | None = None,
                    util: list | None = None):
        from repro.kernels.ops import _crop3, _pad, _stacked

        prog = self.program
        vals3 = {
            slot: (x[None] if x.ndim == 2 else x)
            for slot, x in zip(prog.run_input_slots, canonical)
        }
        for idxs, plan, pads, crops in self._exec_groups:
            vals2 = {}
            for s, fill in pads:
                x3 = vals3[s]
                vals2[s] = _stacked(_pad(x3, plan,
                                         _fill_value(fill, x3.dtype)))
            for i in idxs:
                self._pallas_seg(prog.segments[i], vals2, plan, conv,
                                 util)
            for d in crops:
                vals3[d] = _crop3(vals2[d], self.n_images, self.height,
                                  self.width)
        outs = tuple(vals3[s] for s in prog.run_outputs)
        return tuple(o[0] if self.was_2d else o for o in outs)

    def _pallas_seg(self, seg, vals, plan, conv: list | None = None,
                    util: list | None = None):
        from repro.kernels.ops import (_raster_gdt, _scheduled_gdt,
                                       _scheduled_qdt,
                                       _scheduled_reconstruct, gdt_stage)

        if seg.kind == "refill":
            x2 = vals[seg.srcs[0]]
            vals[seg.dsts[0]] = jnp.where(
                self._image_mask(plan), x2,
                _fill_value(seg.param("fill"), x2.dtype),
            )
        elif seg.kind == "chain":
            vals[seg.dsts[0]] = self._chain2(
                vals[seg.srcs[0]], seg.param("op"), seg.param("n"), plan)
        elif seg.kind == "geodesic":
            vals[seg.dsts[0]] = self._geodesic2(
                vals[seg.srcs[0]], vals[seg.srcs[1]],
                seg.param("op"), seg.param("n"), plan)
        elif seg.kind == "reconstruct":
            out, it, _, _, img_conv, state, counts = _scheduled_reconstruct(
                vals[seg.srcs[0]], vals[seg.srcs[1]], plan,
                seg.param("op"), self._budget_rec(plan), False,
            )
            vals[seg.dsts[0]] = out
            if conv is not None:
                conv.append(img_conv)
            if util is not None:
                # busy = chunks each image actually consumed; capacity =
                # chunks the batch held every slot for (chunk-weighted
                # work occupancy — parked converged slots are waste)
                util.append((jnp.sum(state[1]),
                             it * jnp.int32(plan.n_images), *counts))
        elif seg.kind == "qdt":
            _, r, d, img_conv, state, counts = _scheduled_qdt(
                vals[seg.srcs[0]], plan, self._budget_qdt(plan))
            vals[seg.dsts[0]], vals[seg.dsts[1]] = d, r
            if conv is not None:
                conv.append(img_conv)
            if util is not None:
                util.append((jnp.sum(state[1]),
                             jnp.max(state[1]) * jnp.int32(plan.n_images),
                             *counts))
        elif seg.kind == "gdt":
            d0, ip, sp = gdt_stage(vals[seg.srcs[0]], vals[seg.srcs[1]],
                                   seg.param("nu"))
            budget = self._budget_rec(plan)
            if plan.schedule == "raster":
                d, rounds, img_conv = _raster_gdt(
                    d0, ip, sp, plan, seg.param("lamb"), budget)
                if util is not None:
                    # the sweeps run every image every round — full
                    # occupancy by construction, no parked-slot slack
                    swept = rounds * jnp.int32(plan.n_images)
                    none = jnp.int32(0)
                    util.append((swept, swept, none, none))
            else:
                d, img_conv, state, counts = _scheduled_gdt(
                    d0, ip, sp, plan, seg.param("lamb"), budget)
                if util is not None:
                    util.append((jnp.sum(state[1]),
                                 jnp.max(state[1])
                                 * jnp.int32(plan.n_images), *counts))
            vals[seg.dsts[0]] = d
            if conv is not None:
                conv.append(img_conv)
        elif seg.kind == "point":
            env = {f"__p{j}": vals[s] for j, s in enumerate(seg.srcs)}
            vals[seg.dsts[0]] = eval_pointwise(seg.param("expr"), env, {}, {})
        else:  # pragma: no cover
            raise AssertionError(seg.kind)

    def _chain2(self, x2, op, n, plan):
        from repro.kernels.ops import _interpret, _stacked, _unstacked

        full, rem = divmod(n, plan.fuse_k)
        if full:
            def chunk(x, _):
                return chain_step(
                    x, op=op, fuse_k=plan.fuse_k, band_h=plan.band_h,
                    interpret=_interpret(),
                    vmem_limit_bytes=plan.vmem_limit_bytes,
                    bands_per_image=plan.n_bands,
                ), None
            x2, _ = jax.lax.scan(chunk, x2, None, length=full)
        if rem:
            # jnp tail on the 3-D view: axis-polymorphic per image, and
            # the pad region continues the identity-padded semantics.
            body = M.erode3 if op == "erode" else M.dilate3
            x3 = jax.lax.fori_loop(
                0, rem, lambda _, y, b=body: b(y),
                _unstacked(x2, self.n_images),
            )
            x2 = _stacked(x3)
        return x2

    def _geodesic2(self, f2, m2, op, n, plan):
        from repro.kernels.ops import _interpret, _stacked, _unstacked

        full, rem = divmod(n, plan.fuse_k)
        if full:
            def chunk(x, _):
                y, _ = geodesic_chain_step(
                    x, m2, op=op, fuse_k=plan.fuse_k, band_h=plan.band_h,
                    interpret=_interpret(),
                    vmem_limit_bytes=plan.vmem_limit_bytes,
                    bands_per_image=plan.n_bands,
                )
                return y, None
            f2, _ = jax.lax.scan(chunk, f2, None, length=full)
        if rem:
            step = (M.geodesic_erode1 if op == "erode"
                    else M.geodesic_dilate1)
            m3 = _unstacked(m2, self.n_images)
            f3 = jax.lax.fori_loop(
                0, rem, lambda _, y: step(y, m3),
                _unstacked(f2, self.n_images),
            )
            f2 = _stacked(f3)
        return f2
