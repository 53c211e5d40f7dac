"""repro.analysis — the static-verification subsystem, end to end.

Three checker families, each proven BOTH ways:

* clean on the repo as it stands (the same sweeps CI gates on), and
* catching a deliberately-illegal fixture (the seeded red tests): a
  band-coverage gap, an insufficient halo margin, a past-budget kernel
  geometry, a quantise round-trip / host callback in a compiled program,
  a missing donation, a recompiled cache key, blocking-and-await under a
  held lock, and a lock-order cycle.

Plus the Table II cross-check: the Pallas kernel's buffer accounting
must match ``core.analysis.buffer_sizes`` exactly on logical elements
and stay within the documented padding tolerance on padded bytes.
"""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.analysis import (
    PlanVerificationError,
    concurrency_lint,
    plan_check,
    program_audit,
    sweep,
)
from repro.analysis.findings import Finding, count_by_severity, errors
from repro.core import analysis as core_analysis
from repro.engine.plan import SRPlan
from repro.kernels.tilted_fusion import kernel_buffers, round_up_channels
from repro.models.abpn import ABPNConfig, init_abpn

LAYERS = init_abpn(jax.random.PRNGKey(2), ABPNConfig())
LR = (12, 16, 3)


def rules(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------------------
# Plan verifier: clean grid + seeded violations
# ----------------------------------------------------------------------
def test_design_point_plan_grid_is_clean():
    assert sweep.sweep_plans() == []


def test_plan_verify_method_clean():
    assert SRPlan(height=360, width=640).verify() == []


def test_band_coverage_violation_is_caught():
    """A height the bands do not partition exactly — constructible only by
    bypassing SRPlan validation, which is exactly what the checker must
    not rely on."""
    bad = dataclasses.replace(SRPlan(height=360, width=64))
    object.__setattr__(bad, "height", 100)  # 100 % 60 != 0
    findings = plan_check.verify_plan(bad)
    assert "band_coverage" in rules(errors(findings))


def test_halo_margin_measured_from_geometry():
    assert plan_check.measured_halo_margin(60, 7) == 7
    assert plan_check.required_halo_margin(7) == 7


def test_insufficient_halo_is_caught():
    plan = SRPlan(height=360, width=64, vertical_policy="halo")
    assert plan.verify() == []
    findings = plan.verify(halo_margin=plan.num_layers - 1)
    assert rules(errors(findings)) == ["halo_sufficiency"]


def test_budget_violation_past_design_point():
    """Doubling the band height blows the fixed Table II allocation: a
    hard error on the kernel backend (literal VMEM scratch), advisory on
    the pure-JAX tilted path."""
    kern = SRPlan(height=360, width=64, band_rows=120, backend="kernel")
    findings = kern.verify()
    assert rules(errors(findings)) == ["on_chip_budget"]
    tilted = SRPlan(height=360, width=64, band_rows=120, backend="tilted")
    findings = tilted.verify()
    assert errors(findings) == []
    assert "on_chip_budget" in rules(findings)  # warning-level


@pytest.mark.parametrize("band_rows", [12, 60])
def test_table2_crosscheck_exact_and_bounded(band_rows):
    """The kernel's logical element counts EQUAL the analytical model
    (independently coded, same equations); the padded allocation stays
    within the documented tolerance of the Table II budget."""
    x = plan_check.table2_crosscheck(band_rows=band_rows)
    assert x["kernel_overlap_kb"] == pytest.approx(x["model_overlap_kb"])
    assert x["kernel_residual_kb"] == pytest.approx(x["model_residual_kb"])
    assert x["kernel_weight_kb"] == pytest.approx(x["model_weight_kb"])
    if band_rows == 60:  # the design point is held to the paper budget
        assert x["table2_total_kb"] == pytest.approx(102.36)
        assert x["budget_ratio"] <= 1.0 + plan_check.BUDGET_TOLERANCE


def test_kernel_buffers_match_launch_scratch_shapes():
    """The introspection reports the SAME scratch shapes the pallas_call
    allocates (single source of truth)."""
    from repro.kernels.tilted_fusion import scratch_shapes

    rep = kernel_buffers(channels=core_analysis.ABPN_CHANNELS,
                         band_rows=60, tile_cols=8)
    overlap, residual = scratch_shapes(7, 60, 8, rep["chp"], rep["c0p"])
    assert rep["buffers"]["overlap"]["shape"] == overlap
    assert rep["buffers"]["residual"]["shape"] == residual
    assert rep["chp"] == round_up_channels(28) == 32
    assert rep["c0p"] == round_up_channels(3) == 8


def test_on_chip_budget_kb_exported():
    cfg = core_analysis.HWConfig()
    assert core_analysis.on_chip_budget_kb(cfg) == pytest.approx(
        core_analysis.buffer_sizes(cfg)["total_kb"]
    )
    assert "dram_reduction" in core_analysis.__all__


# ----------------------------------------------------------------------
# Degenerate plans: surfaced, counted, never fatal
# ----------------------------------------------------------------------
def test_degenerate_plans_counted_and_warned():
    session = engine.SRSession(LAYERS)
    with pytest.warns(RuntimeWarning, match="ONE 127-row band"):
        plan = session.plan_for((127, 16, 3))  # prime height: fallback
    assert plan.degenerate_bands
    assert session.cache_stats()["degenerate_plans"] == 1
    findings = plan.verify()
    assert errors(findings) == []  # legal, just undesirable
    assert "degenerate_bands" in rules(findings)
    # a second shape with a fine decomposition does not count
    session.plan_for((120, 16, 3))
    assert session.cache_stats()["degenerate_plans"] == 1


def test_strict_session_rejects_illegal_plan_before_compile():
    session = engine.SRSession(
        LAYERS, backend="kernel", band_rows=120, strict=True
    )
    with pytest.raises(PlanVerificationError, match="on_chip_budget"):
        session.plan_for((360, 64, 3))
    assert session.cache_stats()["size"] == 0  # nothing compiled


def test_strict_session_serves_legal_plans():
    session = engine.SRSession(LAYERS, strict=True)
    hr = session.upscale(np.zeros(LR, np.float32))
    assert hr.shape == (36, 48, 3)


def test_open_accepts_strict():
    session = engine.SRSession.open("abpn_x3", strict=True)
    assert session.strict


# ----------------------------------------------------------------------
# Program audit: clean sessions + seeded violations
# ----------------------------------------------------------------------
def test_audit_clean_session():
    session = engine.SRSession(LAYERS)
    session.upscale(np.zeros(LR, np.float32))
    assert program_audit.audit_session(session) == []


def test_audit_catches_host_callback():
    """An executor compiled with a host callback — the seeded violation
    for the program pass — is flagged in BOTH the jaxpr and the HLO."""
    def cb(x):
        return x + jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    x = jnp.zeros((4,))
    jaxpr = str(jax.make_jaxpr(cb)(x))
    assert "host_callback" in rules(program_audit.audit_jaxpr(jaxpr))
    hlo = jax.jit(cb).lower(x).compile().as_text()
    assert "host_callback" in rules(program_audit.audit_hlo(hlo))


def test_audit_catches_fp32_upcast():
    upcast = (
        "{ lambda ; a:bf16[2,12,16,3] b:f32[3,3,3,28].\n"
        "    c:f32[2,12,16,28] = conv_general_dilated[foo] a b\n"
        "    d:f32[2,12,16,28] = dot_general[bar] c c }"
    )
    found = program_audit.audit_jaxpr(upcast, precision="bf16")
    assert rules(found) == ["fp32_upcast"]
    # same program under an fp32/int8 plan: deliberate, no finding
    assert program_audit.audit_jaxpr(upcast, precision="fp32") == []
    assert program_audit.audit_jaxpr(upcast, precision="int8") == []


def test_real_bf16_program_has_no_upcast():
    session = engine.SRSession(LAYERS, precision="bf16")
    session.upscale(np.zeros(LR, np.float32))
    assert program_audit.audit_session(session) == []


def test_audit_catches_missing_donation():
    session = engine.SRSession(LAYERS, donate_frames=True)
    session.upscale(np.zeros(LR, np.float32))
    findings = program_audit.audit_session(session)
    if jax.default_backend() == "cpu":
        # donation honoured in the build; XLA:CPU ignoring it is an info
        assert errors(findings) == []
        assert "donation_ignored" in rules(findings)
    # break the entry: session wants donation, executor lost it
    for entry in session._cache.entries():
        entry.donates = False
        entry.fn.donates_frames = False
    assert "missing_donation" in rules(
        errors(program_audit.audit_session(session))
    )


def test_recompile_detection():
    session = engine.SRSession(LAYERS, cache_capacity=1)
    plan = session.plan_for(LR)
    session.serve_batch(plan, jnp.zeros((1, *LR)))
    session.serve_batch(plan, jnp.zeros((2, *LR)))  # evicts bucket 1
    session.serve_batch(plan, jnp.zeros((1, *LR)))  # re-miss: recompile
    assert session.cache_stats()["recompiles"] == 1
    findings = program_audit.audit_session(session)
    assert "recompile" in rules(findings)
    assert errors(findings) == []  # a warning, not a gate failure


# ----------------------------------------------------------------------
# Concurrency lint: clean engine sources + seeded snippets
# ----------------------------------------------------------------------
def test_engine_serving_sources_are_clean():
    assert concurrency_lint.lint_files() == []


def test_lint_default_targets_exist():
    targets = concurrency_lint.default_lint_targets()
    assert [p.name for p in targets] == [
        "server.py", "scheduler.py", "session.py", "band_diff.py",
        "delta_stream.py", "output_cache.py", "resilience.py"
    ]
    assert all(p.exists() for p in targets)


BLOCKING_SNIPPET = """
import threading, jax
class S:
    def __init__(self):
        self._lock = threading.Lock()
    def bad(self, hr):
        with self._lock:
            jax.block_until_ready(hr)
"""

AWAIT_SNIPPET = """
import threading
class S:
    def __init__(self):
        self._lock = threading.Lock()
    async def bad(self, fut):
        with self._lock:
            return await fut
"""

ASYNC_BLOCKING_SNIPPET = """
class S:
    async def bad(self, fut):
        return fut.result()
"""

CYCLE_SNIPPET = """
import threading
a_lock = threading.Lock()
b_lock = threading.Lock()
def one():
    with a_lock:
        with b_lock:
            pass
def two():
    with b_lock:
        with a_lock:
            pass
"""

WALL_CLOCK_SNIPPET = """
import time
class S:
    def expire(self, deadline):
        return time.time() >= deadline
"""

SAFE_SNIPPET = """
import threading, time
class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
    def ok(self):
        with self._cv:
            self._cv.wait()
            self._cv.notify_all()
    def also_ok(self, hr):
        import jax
        jax.block_until_ready(hr)  # off-lock: the sanctioned discipline
        with self._lock:
            self.done = True
    def deadline_ok(self, deadline):
        # the sanctioned clocks for deadline/latency math
        return time.monotonic() >= deadline or time.perf_counter() > 0
"""


@pytest.mark.parametrize("snippet,rule", [
    (BLOCKING_SNIPPET, "blocking_under_lock"),
    (AWAIT_SNIPPET, "await_under_lock"),
    (ASYNC_BLOCKING_SNIPPET, "blocking_in_async"),
    (CYCLE_SNIPPET, "lock_order_cycle"),
    (WALL_CLOCK_SNIPPET, "wall_clock"),
])
def test_lint_catches_seeded_violation(snippet, rule):
    findings = concurrency_lint.lint_source(snippet, "snippet.py")
    assert rule in rules(errors(findings))


def test_lint_safe_patterns_pass():
    assert concurrency_lint.lint_source(SAFE_SNIPPET, "safe.py") == []


def test_lock_order_consistent_is_clean():
    consistent = CYCLE_SNIPPET.replace(
        "with b_lock:\n        with a_lock:",
        "with a_lock:\n        with b_lock:",
    )
    findings = concurrency_lint.lint_source(consistent, "consistent.py")
    assert "lock_order_cycle" not in rules(findings)


# ----------------------------------------------------------------------
# Findings plumbing + CLI front door
# ----------------------------------------------------------------------
def test_finding_severity_validated():
    with pytest.raises(ValueError):
        Finding(checker="x", rule="y", severity="fatal", message="z")


def test_count_by_severity():
    fs = [
        Finding(checker="a", rule="r", severity="error", message="m"),
        Finding(checker="a", rule="r", severity="warning", message="m"),
        Finding(checker="a", rule="r", severity="warning", message="m"),
    ]
    assert count_by_severity(fs) == {"error": 1, "warning": 2, "info": 0}


def test_analysis_report_shape():
    report = sweep.analysis_report(programs=False)
    assert report["clean"] is True
    for checker in ("concurrency", "plan", "program"):
        assert set(report[checker]) == {"error", "warning", "info"}


def test_cli_lint_and_plans(subproc):
    out = subproc(
        "import sys\n"
        "from repro.analysis.__main__ import main\n"
        "sys.exit(main(['--lint', '--plans']))",
        devices=1,
    )
    assert "OK" in out


def test_cli_exits_nonzero_on_error_findings(tmp_path, monkeypatch):
    """Seed a lint violation into the CLI's target set: the gate must
    fail the build."""
    bad = tmp_path / "server.py"
    bad.write_text(BLOCKING_SNIPPET)
    monkeypatch.setattr(
        concurrency_lint, "default_lint_targets", lambda root=None: [bad]
    )
    from repro.analysis.__main__ import main

    assert main(["--lint"]) == 1
