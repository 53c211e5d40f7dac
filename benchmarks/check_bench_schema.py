"""Validate committed benchmark records against their schemas.

CI's load-smoke and temporal-smoke jobs regenerate quick records and run
this against both the fresh output and the committed JSON, so schema
drift (renamed/dropped keys, a broken bit-exactness or SLO guarantee)
fails the build instead of silently rotting the recorded numbers.

    PYTHONPATH=src python benchmarks/check_bench_schema.py [path ...]

With no path it checks the committed BENCH_server_load.json and
BENCH_temporal.json.  Records are dispatched on their ``bench`` field:
``server_load`` records (benchmarks/server_load.py) get the
load-harness checks, ``temporal_delta`` records
(benchmarks/temporal_delta.py) get the delta-serving checks; any other
record is schema drift.

No third-party schema library: the required key sets live next to the
producer (``server_load.LOAD_RECORD_KEYS``, ...), so adding a field
means updating producer and checker in the same place.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from server_load import (  # noqa: E402
    ACCEPTANCE_KEYS,
    CALIBRATION_KEYS,
    FAULT_KEYS,
    LOAD_MODE_KEYS,
    LOAD_POINT_KEYS,
    LOAD_RECORD_KEYS,
)
from temporal_delta import (  # noqa: E402
    MIN_STATIC_COMPUTE_REDUCTION,
    TEMPORAL_ACCEPTANCE_KEYS,
    TEMPORAL_CACHE_KEYS,
    TEMPORAL_CLIP_KEYS,
    TEMPORAL_RECORD_KEYS,
)


def _require(obj: dict, keys, where: str, errors: list) -> None:
    missing = [k for k in keys if k not in obj]
    if missing:
        errors.append(f"{where}: missing keys {missing}")


def check_server_load(rec: dict) -> list:
    """All violations in one server_load record (empty list = valid)."""
    errors: list = []
    _require(rec, LOAD_RECORD_KEYS, "record", errors)
    _require(rec.get("calibration", {}), CALIBRATION_KEYS,
             "calibration", errors)
    points = rec.get("points", [])
    if not points:
        errors.append("points must hold at least one load point")
    for i, p in enumerate(points):
        _require(p, LOAD_POINT_KEYS, f"points[{i}]", errors)
        for mode in ("block", "hardened"):
            _require(p.get(mode, {}), LOAD_MODE_KEYS,
                     f"points[{i}].{mode}", errors)
    acc = rec.get("acceptance", {})
    _require(acc, ACCEPTANCE_KEYS, "acceptance", errors)
    # the headline claim: at the overload point, shedding + degradation
    # hold the served tail inside the SLO while plain blocking admission
    # at the same offered rate does not
    if acc.get("hardened_within_slo") is not True:
        errors.append(
            "acceptance.hardened_within_slo must be true — the hardened "
            "server failed to hold its p99 inside the SLO at overload"
        )
    if acc.get("block_within_slo") is not False:
        errors.append(
            "acceptance.block_within_slo must be false — if blocking "
            "admission also holds the SLO, the record never actually "
            "overloaded the server and proves nothing"
        )
    if points:
        top = points[-1].get("hardened", {})
        if (top.get("shed", 0) or 0) + (top.get("deadline_missed", 0)
                                        or 0) <= 0:
            errors.append(
                "points[-1].hardened must shed or expire at overload — "
                "an SLO held without rejecting anything means the point "
                "was not an overload"
            )
        if (top.get("degrade_transitions", 0) or 0) < 1:
            errors.append(
                "points[-1].hardened.degrade_transitions must be >= 1 — "
                "the DegradePolicy never stepped down under overload"
            )
    fi = rec.get("fault_injection", {})
    _require(fi, FAULT_KEYS, "fault_injection", errors)
    if fi.get("neighbors_bit_exact") is not True:
        errors.append(
            "fault_injection.neighbors_bit_exact must be true — an "
            "injected dispatch failure changed an UNAFFECTED request's "
            "output"
        )
    if fi.get("served_after_failure") is not True:
        errors.append(
            "fault_injection.served_after_failure must be true — the "
            "server stopped serving after an injected failure"
        )
    if fi.get("failed_requests") != fi.get("injected_failures"):
        errors.append(
            "fault_injection.failed_requests must equal "
            "injected_failures — the blast radius leaked past the "
            f"failed dispatch ({fi.get('failed_requests')} failed for "
            f"{fi.get('injected_failures')} injected)"
        )
    return errors


def check_temporal(rec: dict) -> list:
    """All violations in one temporal_delta record (empty list = valid)."""
    errors: list = []
    _require(rec, TEMPORAL_RECORD_KEYS, "record", errors)
    clips = rec.get("clips", [])
    names = [c.get("clip") for c in clips]
    for want in ("static", "panning", "full_motion"):
        if want not in names:
            errors.append(f"clips must include the {want!r} motion regime")
    for i, c in enumerate(clips):
        where = f"clips[{i}] ({c.get('clip')})"
        _require(c, TEMPORAL_CLIP_KEYS, where, errors)
        _require(c.get("cache", {}), TEMPORAL_CACHE_KEYS,
                 f"{where}.cache", errors)
        # the subsystem's contract: splicing cached bands NEVER changes
        # the output, no matter the motion regime
        if c.get("bit_exact") is not True:
            errors.append(
                f"{where}: bit_exact must be true — the delta splice "
                "diverged from full re-upscale"
            )
        ratio = c.get("reuse_ratio")
        if ratio is not None and not 0.0 <= ratio <= 1.0:
            errors.append(f"{where}: reuse_ratio {ratio} outside [0, 1]")
        served = c.get("bands_served")
        skipped = c.get("bands_skipped")
        total = c.get("bands_total")
        if None not in (served, skipped, total) and served + skipped != total:
            errors.append(
                f"{where}: bands_served {served} + bands_skipped {skipped} "
                f"!= bands_total {total} — a band was double-counted or "
                "dropped from the splice accounting"
            )
    acc = rec.get("acceptance", {})
    _require(acc, TEMPORAL_ACCEPTANCE_KEYS, "acceptance", errors)
    # the headline claim: a static clip reuses enough to cut conv-stack
    # compute by at least the committed floor
    floor = acc.get("min_static_compute_reduction")
    if floor is not None and floor < MIN_STATIC_COMPUTE_REDUCTION:
        errors.append(
            f"acceptance.min_static_compute_reduction {floor} is below "
            f"the committed floor {MIN_STATIC_COMPUTE_REDUCTION}"
        )
    red = acc.get("static_compute_reduction")
    if red is None or red < MIN_STATIC_COMPUTE_REDUCTION:
        errors.append(
            f"acceptance.static_compute_reduction {red} must be >= "
            f"{MIN_STATIC_COMPUTE_REDUCTION} — the static clip did not "
            "reuse enough to justify the delta path"
        )
    if acc.get("static_ok") is not True:
        errors.append("acceptance.static_ok must be true")
    if acc.get("all_bit_exact") is not True:
        errors.append(
            "acceptance.all_bit_exact must be true — delta serving "
            "changed at least one frame's output"
        )
    return errors


def main(argv) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = argv[1:] or [os.path.join(root, name) for name in
                         ("BENCH_server_load.json", "BENCH_temporal.json")]
    status = 0
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        if rec.get("bench") == "server_load":
            errors = check_server_load(rec)
            if errors:
                status = 1
                print(f"{path}: SCHEMA DRIFT")
                for e in errors:
                    print(f"  - {e}")
            else:
                acc = rec["acceptance"]
                top = rec["points"][-1]["hardened"]
                print(f"{path}: ok "
                      f"(@{acc['offered_rate_rps']} req/s hardened p99 "
                      f"{acc['hardened_p99_ms']} ms <= SLO "
                      f"{acc['slo_p99_ms']} ms, block p99 "
                      f"{acc['block_p99_ms']} ms, shed {top['shed']}, "
                      f"expired {top['deadline_missed']}, "
                      f"degrade_level {top['degrade_level']})")
            continue
        if rec.get("bench") == "temporal_delta":
            errors = check_temporal(rec)
            if errors:
                status = 1
                print(f"{path}: SCHEMA DRIFT")
                for e in errors:
                    print(f"  - {e}")
            else:
                acc = rec["acceptance"]
                pan = next(c for c in rec["clips"]
                           if c["clip"] == "panning")
                print(f"{path}: ok "
                      f"(static compute x{acc['static_compute_reduction']} "
                      f">= x{acc['min_static_compute_reduction']}, "
                      f"panning reuse {pan['reuse_ratio']}, "
                      f"bit_exact={acc['all_bit_exact']})")
            continue
        status = 1
        print(f"{path}: SCHEMA DRIFT")
        print(f"  - unknown bench {rec.get('bench')!r} (expected "
              "'server_load' or 'temporal_delta')")
    return status


if __name__ == "__main__":
    sys.exit(main(sys.argv))
