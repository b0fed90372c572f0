"""Mutation self-tests for the static verifier (``repro.analysis``).

Each test class seeds a violation of one of the five check classes —
halo/pad-state, dtype safety, plan constraints, cache-key
completeness, index-map bounds — and asserts the verifier reports it,
plus the corresponding clean-input case.  Mutants are forged past the
constructors' own validation (``object.__new__`` for frozen plans,
``dataclasses.replace`` for programs) so the checks are exercised
independently of ``__post_init__``.
"""
import dataclasses

import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro import analysis as A
from repro.analysis.findings import ERROR, WARN, VerificationError
from repro.api import E
from repro.api.compile import compile as compile_expr
from repro.api.executable import Executable
from repro.core.chain import ChainPlan, plan_chain


def exe_for(expr, shape3=(1, 40, 72), dtype="uint8", backend="pallas"):
    return compile_expr(expr, shape3, dtype, backend, verify=False)


def forge_plan(plan, **over):
    """Copy ``plan`` with fields overridden, bypassing __post_init__."""
    mutant = object.__new__(ChainPlan)
    for f in dataclasses.fields(ChainPlan):
        object.__setattr__(mutant, f.name,
                           over.get(f.name, getattr(plan, f.name)))
    return mutant


def errors_of(findings):
    return [f for f in findings if f.severity == ERROR]


# ---------------------------------------------------------------------------
# check class a: halo coverage / pad-state discipline
# ---------------------------------------------------------------------------

class TestHalo:
    def test_clean_multi_phase_program_passes(self):
        e = E.reconstruct(E.erode(4, E.input("f")), E.input("m"),
                          op="dilate")
        exe = exe_for(e)
        assert A.check_program(exe.program) == []
        assert errors_of(A.check_coverage(
            exe.program, exe.plan, (1, 40, 72))) == []

    def test_wrong_refill_identity_detected(self):
        """Flip one masked refill to the wrong lattice identity: the
        consumer kernel's operand pad is no longer absorbing."""
        e = E.reconstruct(E.erode(4, E.input("f")), E.input("m"),
                          op="dilate")
        prog = exe_for(e).program
        segs = list(prog.segments)
        idx = next(i for i, s in enumerate(segs) if s.kind == "refill")
        fill = segs[idx].param("fill")
        flipped = tuple(("fill", "hi" if fill == "lo" else "lo")
                        if n == "fill" else (n, v)
                        for n, v in segs[idx].params)
        segs[idx] = dataclasses.replace(segs[idx], params=flipped)
        bad = dataclasses.replace(prog, segments=tuple(segs))
        errs = errors_of(A.check_program(bad))
        assert errs and any("leak" in f.message for f in errs)

    def test_dropped_refill_detected(self):
        e = E.reconstruct(E.erode(4, E.input("f")), E.input("m"),
                          op="dilate")
        prog = exe_for(e).program
        assert any(s.kind == "refill" for s in prog.segments)
        bad = dataclasses.replace(prog, segments=tuple(
            s for s in prog.segments if s.kind != "refill"))
        assert errors_of(A.check_program(bad))

    def test_input_slot_misbinding_detected(self):
        """Binding canonical inputs by position instead of by the
        lowered ``run_input_slots`` (the historical executable bug)."""
        e = E.reconstruct(E.erode(1, E.input("a")), E.input("b"),
                          op="erode")
        prog = exe_for(e).program
        # the lowerer allocates the mask's slot after the chain's output
        assert prog.run_input_slots != tuple(
            range(len(prog.run_input_slots)))
        bad = dataclasses.replace(
            prog, run_input_slots=tuple(range(len(prog.run_input_slots))))
        errs = errors_of(A.check_program(bad))
        assert errs and any("before any definition" in f.message
                            for f in errs)

    def test_slot_binding_regression_bit_exact(self):
        """The non-contiguous-slot program itself runs bit-exact on both
        engines (regression for the enumerate-based binding)."""
        rng = np.random.default_rng(3)
        a = rng.integers(0, 255, (1, 40, 72), dtype=np.uint8)
        b = rng.integers(0, 255, (1, 40, 72), dtype=np.uint8)
        e = E.reconstruct(E.erode(1, E.input("a")), E.input("b"),
                          op="erode")
        outs = [np.asarray(exe_for(e, backend=bk)(a, b))
                for bk in ("pallas", "xla")]
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_plan_under_coverage_warned(self):
        exe = exe_for(E.erode(6, E.input("f")))
        # a stale schedule: 1 launch of 2 fused steps for a 6-chain
        short = forge_plan(exe.plan, fuse_k=2, band_h=16, n_chunks=1)
        finds = A.check_coverage(exe.program, short, (1, 40, 72))
        assert any(f.severity == WARN and "under-cover" in f.message
                   for f in finds)


# ---------------------------------------------------------------------------
# check class b: dtype safety
# ---------------------------------------------------------------------------

class TestDtypes:
    def test_bucketer_fills_clean(self):
        assert errors_of(A.check_bucketer_fills()) == []

    def test_non_identity_fill_detected(self):
        assert errors_of(A.check_fill_value("uint8", "hi", 254))
        assert errors_of(A.check_fill_value("float32", "lo", np.inf))
        assert A.check_fill_value("uint8", "hi", 255) == []

    def test_unrepresentable_fill_detected(self):
        assert errors_of(A.check_fill_value("uint8", "hi", 255.5))

    def test_work_dtype_round_trips(self):
        # production rule: uint8…int16 proved exact in float32 by
        # exhaustion, the 32- and 64-bit dtypes kept as they are
        assert A.check_work_dtypes() == []
        assert A.check_work_dtype("int8") == []

    @pytest.mark.parametrize("img,work", [
        ("int32", "float32"),    # 2^24 + 1 rounds
        ("uint16", "float16"),   # 2049 rounds
        ("int16", "uint16"),     # negatives wrap
        ("float64", "float32"),  # precision and range lost
    ])
    def test_lossy_work_dtype_detected(self, img, work):
        assert errors_of(A.check_work_dtype(img, work))

    def test_qdt_accumulator_overflow(self):
        # provable: uint16 residuals overflow an int16 accumulator
        assert errors_of(A.check_qdt_accumulator("uint16", "int16"))
        # provable: fractional residuals truncate in an int accumulator
        assert errors_of(A.check_qdt_accumulator("float32", "int32"))
        # provable: int32 residuals exceed the float32 mantissa
        assert errors_of(A.check_qdt_accumulator("int32", "float32"))
        # production rule is safe for the narrow dtypes
        assert A.check_qdt_accumulator("uint8") == []
        assert A.check_qdt_accumulator("uint16") == []

    def test_qdt_accumulator_domain_conditional_warns(self):
        for img, acc in (("int32", "int32"), ("float64", "float32")):
            finds = A.check_qdt_accumulator(img, acc)
            assert finds and all(f.severity == WARN for f in finds)

    def test_distance_plane_overflow(self):
        assert errors_of(A.check_distance_plane(2 ** 28, 2 ** 8))
        assert A.check_distance_plane(1000, 16) == []


# ---------------------------------------------------------------------------
# check class c: plan constraints + Mosaic readiness
# ---------------------------------------------------------------------------

class TestPlans:
    def test_derived_plans_pass(self):
        for h, w in ((64, 64), (33, 70), (200, 128)):
            plan = plan_chain(h, w, "uint8", 8)
            assert errors_of(A.check_plan(plan, (1, h, w))) == []

    def test_band_fuse_violation_detected(self):
        plan = plan_chain(64, 64, "uint8", 8)
        bad = forge_plan(plan, band_h=plan.fuse_k * 2 + 1)
        assert errors_of(A.check_plan(bad))

    def test_ragged_tile_detected(self):
        plan = plan_chain(64, 64, "uint8", 8)
        bad = forge_plan(plan, tile_w=plan.fuse_k + 1)
        errs = errors_of(A.check_plan(bad))
        assert errs and any("tile_w" in f.message for f in errs)

    def test_requeue_exactness_detected(self):
        plan = plan_chain(64, 64, "uint8", 8)
        bad = forge_plan(plan, requeue_halo=0)
        assert errors_of(A.check_plan(bad))

    def test_vmem_limit_beyond_the_core_detected(self):
        plan = plan_chain(64, 64, "uint8", 8)
        bad = forge_plan(plan, vmem_limit_bytes=256 * 1024 * 1024)
        errs = errors_of(A.check_plan(bad))
        assert errs and any("vmem_limit_bytes" in f.message for f in errs)

    def test_shape_coverage_detected(self):
        plan = plan_chain(64, 64, "uint8", 8)
        assert errors_of(A.check_plan(plan, (1, plan.height_pad + 1,
                                             plan.width_pad)))
        assert errors_of(A.check_plan(plan, (2, 64, 64)))  # n_images=1

    def test_mosaic_readiness_warns(self):
        plan = ChainPlan(band_h=16, fuse_k=8, width_pad=256,
                         height_pad=64, n_bands=4, n_chunks=1, tile_w=64)
        finds = A.check_mosaic_readiness(plan, "uint8")
        assert finds and all(f.severity == WARN for f in finds)
        # tile and halo blocks off the lane grid; K and bands off the
        # 32-row uint8 sublane grid
        assert {f.subject for f in finds} == {"mosaic/tile",
                                              "mosaic/sublane"}

    @pytest.mark.parametrize("dtype", ["uint8", "float32"])
    def test_planner_plans_are_mosaic_ready(self, dtype):
        # the plans tests/test_mosaic_compile.py compiles for v5e
        for plan in (plan_chain(1024, 1024, dtype, 1536),
                     plan_chain(1024, 1024, dtype, None,
                                n_images_resident=2, n_images=4,
                                convergent=True)):
            assert A.check_mosaic_readiness(plan, dtype) == []

    def test_lane_aligned_plan_is_quiet_on_width(self):
        plan = plan_chain(64, 128, "uint8", 8)
        assert not any(f.subject == "mosaic/width"
                       for f in A.check_mosaic_readiness(plan, "uint8"))


# ---------------------------------------------------------------------------
# check class d: cache-key completeness
# ---------------------------------------------------------------------------

class TestCacheKeys:
    def test_plan_key_is_complete(self):
        plan = plan_chain(64, 96, "uint8", 8)
        assert A.check_plan_key(plan) == []

    def test_plan_key_gap_detected(self):
        plan = plan_chain(64, 96, "uint8", 8)
        # a key that forgets the schedule's tile/requeue fields
        broken = lambda p: (p.band_h, p.fuse_k, p.width_pad,  # noqa: E731
                            p.height_pad)
        finds = A.check_plan_key(plan, key_of=broken)
        assert finds and all(f.check == "cache-key" for f in finds)
        assert any("n_chunks" in f.message for f in finds)

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_executable_key_is_complete(self, backend):
        e = E.reconstruct(E.erode(4, E.input("f")), E.input("m"),
                          op="dilate")
        exe = exe_for(e, backend=backend)
        assert A.check_executable_key(exe) == []

    def test_executable_key_gap_detected(self):
        exe = exe_for(E.erode(4, E.input("f")))
        # forget everything but the run signature and shape
        broken = lambda x: x.key[:2]  # noqa: E731
        finds = A.check_executable_key(exe, key_of=broken)
        insensitive = {f.message.split(" — ")[0] for f in finds}
        assert any("was_2d" in m for m in insensitive)
        assert any("max_chunks" in m for m in insensitive)


# ---------------------------------------------------------------------------
# check class e: index-map bounds
# ---------------------------------------------------------------------------

class TestIndexMaps:
    def test_real_specs_in_bounds(self):
        for kwargs in ({}, {"tile_w": 64}):
            plan = ChainPlan(band_h=16, fuse_k=8, width_pad=128,
                             height_pad=64, n_bands=4, n_chunks=2,
                             n_images=2, **kwargs)
            assert A.check_plan_index_maps(plan) == []

    def test_unclamped_top_halo_detected(self):
        # the real map is max(i*r - 1, 0); drop the clamp
        spec = pl.BlockSpec((8, 64), lambda i: (i * 2 - 1, 0))
        finds = A.check_block_specs([spec], (4,), (64, 64))
        assert any("negative block index" in f.message for f in finds)

    def test_unclamped_bottom_halo_detected(self):
        # the real map is min((i+1)*r, last); drop the clamp
        spec = pl.BlockSpec((8, 64), lambda i: (i * 2 + 2, 0))
        finds = A.check_block_specs([spec], (4,), (64, 64))
        assert any("past axis-0 extent" in f.message for f in finds)

    def test_non_dividing_block_detected(self):
        spec = pl.BlockSpec((10, 64), lambda i: (i, 0))
        finds = A.check_block_specs([spec], (4,), (64, 64))
        assert any("does not divide" in f.message for f in finds)

    def test_partition_violations_detected(self):
        overlap = pl.BlockSpec((16, 64), lambda i: (0, 0))
        finds = A.check_partition(overlap, (4,), (64, 64))
        assert any("both map to block" in f.message for f in finds)
        assert any("never visited" in f.message for f in finds)


# ---------------------------------------------------------------------------
# orchestration: verifier levels, compile hook, lint
# ---------------------------------------------------------------------------

class TestVerifier:
    def test_full_level_clean_on_registry_sample(self):
        from repro.analysis.lint import iter_registry_cases
        cases = list(iter_registry_cases(
            dtypes=("uint8",), shapes=((1, 48, 64),),
            backends=("pallas",)))
        assert cases
        for _label, expr, shape3, dtype, backend in cases:
            exe = compile_expr(expr, shape3, dtype, backend, verify=False)
            report = A.verify_executable(exe, level="full")
            assert report.ok, str(report)

    def test_hook_raises_on_seeded_violation(self):
        exe = exe_for(E.erode(4, E.input("f")))
        bad_prog = dataclasses.replace(
            exe.program,
            run_input_slots=tuple(s + 7 for s in
                                  exe.program.run_input_slots))
        bad = Executable(bad_prog, (1, 40, 72), "uint8", "pallas",
                         exe.plan, None, False)
        report = A.verify_executable(bad, level="fast")
        with pytest.raises(VerificationError) as ei:
            report.raise_if_errors()
        assert isinstance(ei.value, AssertionError)

    def test_hook_env_toggle(self, monkeypatch):
        from repro.analysis.verifier import verify_on_compile
        monkeypatch.setenv("REPRO_VERIFY", "0")
        assert not verify_on_compile()
        monkeypatch.setenv("REPRO_VERIFY", "1")
        assert verify_on_compile()

    def test_lint_cli_clean(self, capsys):
        from repro.analysis.lint import main
        rc = main(["--dtypes", "uint8", "--shapes", "1x48x64",
                   "--backends", "xla"])
        out = capsys.readouterr().out
        assert rc == 0 and "lint: ok" in out

    def test_lint_cli_rejects_bad_shape(self):
        from repro.analysis.lint import main
        with pytest.raises(SystemExit):
            main(["--shapes", "48x64"])
