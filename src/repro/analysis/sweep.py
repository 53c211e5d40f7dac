"""Representative repo-wide sweeps for the three static checkers.

This is what ``python -m repro.analysis`` and the benchmark's
``analysis`` section run:

* :func:`sweep_lint` — the concurrency lint over the engine's serving
  sources.
* :func:`sweep_plans` — static plan verification across the full
  backend x vertical-policy x precision grid at the paper's design
  point (ABPN, 360-row frames, 60-row bands) — no compilation.
* :func:`sweep_programs` — compile small representative sessions
  (tilted fp32/bf16/int8 + the reference oracle) and audit every cached
  executor's jaxpr/HLO.

:func:`analysis_report` bundles the outcome as per-checker severity
counts plus a ``clean`` verdict.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from repro.analysis import concurrency_lint, plan_check, program_audit
from repro.analysis.findings import Finding, count_by_severity, errors

__all__ = [
    "sweep_lint",
    "sweep_plans",
    "sweep_programs",
    "analysis_report",
    "PLAN_SWEEP_SHAPE",
    "PROGRAM_SWEEP_SHAPE",
    "PROGRAM_SWEEP_CONFIGS",
]

# The paper's design point: 360-row frames in 60-row bands.
PLAN_SWEEP_SHAPE: Tuple[int, int, int] = (360, 640, 3)

# Small enough to compile everywhere in seconds, banded (24 = 2 bands of
# 12 after derive_band_rows picks 24... a single 24-row band) — the
# audit rules are shape-independent.
PROGRAM_SWEEP_SHAPE: Tuple[int, int, int] = (24, 16, 3)

# (backend, precision) grid the program sweep compiles.  The kernel
# backend is exercised by the parity/bench suites; compiling its
# interpret-mode Pallas program here would dominate CI time without
# adding audit coverage (its jaxpr is a single pallas_call).
PROGRAM_SWEEP_CONFIGS: Tuple[Tuple[str, str], ...] = (
    ("tilted", "fp32"),
    ("tilted", "bf16"),
    ("tilted", "int8"),
    ("reference", "fp32"),
)


def sweep_lint() -> List[Finding]:
    """Concurrency-lint the engine serving sources."""
    return concurrency_lint.lint_files()


def sweep_plans(lr_shape: Tuple[int, int, int] = PLAN_SWEEP_SHAPE) -> List[Finding]:
    """Statically verify the full legal plan grid at the design point."""
    from repro.engine.plan import (
        BACKENDS,
        PRECISIONS,
        VERTICAL_POLICIES,
        SRPlan,
    )

    findings: List[Finding] = []
    for backend in BACKENDS:
        for policy in VERTICAL_POLICIES:
            for precision in PRECISIONS:
                plan = SRPlan.from_request(
                    lr_shape,
                    num_layers=7,
                    backend=backend,
                    vertical_policy=policy,
                    precision=precision,
                )
                findings.extend(plan_check.verify_plan(plan))
    return findings


def sweep_programs(
    lr_shape: Tuple[int, int, int] = PROGRAM_SWEEP_SHAPE,
    configs: Tuple[Tuple[str, str], ...] = PROGRAM_SWEEP_CONFIGS,
) -> List[Finding]:
    """Compile representative sessions and audit every cached executor."""
    import numpy as np

    from repro.engine.session import SRSession

    findings: List[Finding] = []
    frame = np.zeros(lr_shape, np.float32)
    for backend, precision in configs:
        session = SRSession.open(
            "abpn_x3",
            backend=backend,
            precision=precision,
            cache_capacity=4,
        )
        session.upscale(frame)  # populate the cache: one real compile
        findings.extend(program_audit.audit_session(session))
    return findings


def analysis_report(*, programs: bool = True) -> Dict:
    """Run every sweep; per-checker severity counts + a ``clean`` verdict
    (no error-level findings anywhere)."""
    by_checker = {
        "concurrency": sweep_lint(),
        "plan": sweep_plans(),
        "program": sweep_programs() if programs else [],
    }
    all_findings = [f for fs in by_checker.values() for f in fs]
    return {
        "concurrency": count_by_severity(by_checker["concurrency"]),
        "plan": count_by_severity(by_checker["plan"]),
        "program": count_by_severity(by_checker["program"]),
        "clean": not errors(all_findings),
    }
