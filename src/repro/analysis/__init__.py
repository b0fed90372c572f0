"""Static program verifier for lowered morphology plans.

Proves invariants about :class:`~repro.api.expr.Expr` graphs, lowered
:class:`~repro.api.lower.Program`\\ s and
:class:`~repro.core.chain.ChainPlan` schedules **without executing
them** — five check classes (halo coverage, dtype safety, plan
constraints, cache-key completeness, index-map bounds), three entry
points (the ``verify=`` hook in ``repro.api.compile``, the
``python -m repro.analysis.lint`` CLI, and direct calls from the
mutation self-tests).  See ``docs/VERIFIER.md``.
"""
from repro.analysis.cachekeys import check_executable_key, check_plan_key
from repro.analysis.dtypes import (
    SUPPORTED_DTYPES,
    check_bucketer_fills,
    check_distance_plane,
    check_fill_value,
    check_qdt_accumulator,
    check_work_dtype,
    check_work_dtypes,
)
from repro.analysis.findings import (
    CHECKS,
    ERROR,
    WARN,
    Finding,
    Report,
    VerificationError,
)
from repro.analysis.halo import check_coverage, check_program
from repro.analysis.indexmaps import (
    check_block_specs,
    check_partition,
    check_plan_index_maps,
)
from repro.analysis.plans import check_mosaic_readiness, check_plan
from repro.analysis.verifier import verify_executable, verify_on_compile

__all__ = [
    "CHECKS", "ERROR", "WARN", "Finding", "Report", "VerificationError",
    "SUPPORTED_DTYPES",
    "check_bucketer_fills", "check_distance_plane", "check_fill_value",
    "check_qdt_accumulator", "check_work_dtype", "check_work_dtypes",
    "check_coverage", "check_program",
    "check_block_specs", "check_partition", "check_plan_index_maps",
    "check_mosaic_readiness", "check_plan",
    "check_executable_key", "check_plan_key",
    "verify_executable", "verify_on_compile",
]
