"""Static-analysis subsystem tests: every pass provably fires on its
seeded-violation fixture (exact rule id + location), and the real tree
passes clean.

The fixture corpus lives in ``tests/fixtures/analysis/`` — small files
with deliberate contract violations that the passes must pin down to
the line.  ``repro.analysis.twin`` deliberately skips any corpus path
containing an ``analysis`` component, so the fixtures never leak into
the real-tree checks.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

from repro.analysis import run_all
from repro.analysis import dtypes, retrace, sweeps, twin
from repro.analysis.common import rel

FIX = Path(__file__).resolve().parent / "fixtures" / "analysis"


def by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# ------------------------------------------------------------- retrace
def test_retrace_fires_on_baked_operand():
    def fn(sc):
        return sc["used"] * 2.0

    findings = retrace.check_traced(fn=fn, args=({"used": 1.0,
                                                  "baked": 2.0},))
    assert [f.rule for f in findings] == ["retrace-baked-static"]
    assert "'baked'" in findings[0].message


def test_retrace_clean_when_all_operands_live():
    def fn(sc):
        return sc["a"] + sc["b"]

    assert retrace.check_traced(fn=fn, args=({"a": 1.0, "b": 2.0},)) == []


def test_retrace_fires_on_baked_epoch_operand():
    # the schedule variant of the baked-static slip: the step indexes a
    # per-epoch row with a *Python* constant and never reads the shared
    # epoch_bounds vector, so DCE must prove the boundary operand dead
    spec = importlib.util.spec_from_file_location(
        "epoch_baked", FIX / "epoch_baked.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    findings = retrace.check_traced(
        fn=mod.step,
        args=({"quota": np.arange(2.0), "crash_at": 1.0,
               "epoch_bounds": np.full((1,), 9.9e9)},))
    assert [f.rule for f in findings] == ["retrace-baked-static"]
    assert "'epoch_bounds'" in findings[0].message


def test_retrace_registered_fields_fixture():
    # declaration-side half: a params-like dataclass grew a schedule
    # knob without registering it sweepable-or-static
    spec = importlib.util.spec_from_file_location(
        "params_bad", FIX / "params_bad.py")
    mod = importlib.util.module_from_spec(spec)
    # inspect.getsourcelines (the field anchor) resolves the class's
    # file through sys.modules, so the fixture must be registered
    sys.modules["params_bad"] = mod
    spec.loader.exec_module(mod)
    findings = retrace.check_registered_fields(
        [mod.BadPolicy],
        sweepable={"BadPolicy.threshold": ("threshold_count",)},
        static={})
    assert [f.rule for f in findings] == ["retrace-unregistered-field"]
    assert "BadPolicy.quota_schedule" in findings[0].message
    assert findings[0].file.endswith("params_bad.py")
    assert findings[0].line == 12  # the quota_schedule field line


# ---------------------------------------------------------------- twin
def test_twin_policy_fixture():
    findings = twin.check_policy_fields(
        engine_paths=[FIX / "engine_bad.py"],
        oracle_paths=[FIX / "oracle_bad.py"],
        fields={"Fake.alpha": ("fake.py", 10),
                "Fake.beta": ("fake.py", 20)})
    oracle_miss = by_rule(findings, "twin-policy-oracle")
    engine_miss = by_rule(findings, "twin-policy-engine")
    assert [(f.file, f.line) for f in oracle_miss] == [("fake.py", 10)]
    assert "Fake.alpha" in oracle_miss[0].message
    assert [(f.file, f.line) for f in engine_miss] == [("fake.py", 20)]
    assert "Fake.beta" in engine_miss[0].message
    assert len(findings) == 2


# -------------------------------------------------------------- dtypes
def test_dtype_packing_fixture():
    findings = dtypes.check_packing(
        shapes={"a": ("int32", (4,)), "c": ("int8", (2,))},
        expected={"a": "int8", "b": "float64"},
        anchor_file=FIX / "grid_bad.py")
    assert [f.rule for f in findings] == ["dtype-packing"] * 3
    msgs = " | ".join(f.message for f in findings)
    assert "'a' is int32" in msgs           # widened column
    assert "'b' is registered but absent" in msgs
    assert "'c' is not in the packing" in msgs


def test_dtype_f32_leak_fixture():
    spec = importlib.util.spec_from_file_location(
        "leak_fixture", FIX / "leak_fixture.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    findings = dtypes.check_f32_leaks(fn=mod.leak,
                                      args=(np.float64(1.0),))
    assert [f.rule for f in findings] == ["dtype-f32-leak"]
    assert findings[0].file.endswith("leak_fixture.py")
    assert findings[0].line == 8


def test_dtype_donation_fixture():
    findings = dtypes.check_donation(path=FIX / "grid_bad.py")
    assert [f.rule for f in findings] == ["dtype-undonated"] * 2
    assert findings[0].line == 6           # _DONATED misses gaps, mlen
    assert "gaps, mlen" in findings[0].message
    assert findings[1].line == 9           # jit partial, no donation
    assert "run" in findings[1].message


# -------------------------------------------------------------- sweeps
def test_sweeps_fixture():
    findings = sweeps.check(bench_dir=FIX / "bench_bad")
    unreg = by_rule(findings, "sweep-unregistered")
    assert [(f.file, f.line) for f in unreg] \
        == [(rel(FIX / "bench_bad" / "fig_x.py"), 7)]
    assert "'rogue_sweep'" in unreg[0].message
    partial = by_rule(findings, "sweep-missing-key")
    assert len(partial) == 1
    assert "partial_sweep_compiles" in partial[0].message
    stale = by_rule(findings, "sweep-stale")
    assert [(f.file, f.line) for f in stale] \
        == [(rel(FIX / "bench_bad" / "_sweeps.py"), 5)]
    assert "'ghost_sweep'" in stale[0].message
    assert len(findings) == 3


def test_sweeps_fixture_unregistered_fabric():
    """A figure script emitting the full fabric_sweep_* telemetry
    without registering the sweep must produce exactly one
    sweep-unregistered finding — the guard that keeps fabric_sweep
    under check_compiles' one-XLA-program watch."""
    findings = sweeps.check(bench_dir=FIX / "bench_bad_fabric")
    assert [f.rule for f in findings] == ["sweep-unregistered"]
    assert "'fabric_sweep'" in findings[0].message
    assert findings[0].file == rel(
        FIX / "bench_bad_fabric" / "fig_fabric.py")


# --------------------------------------------------- real tree is clean
def test_real_tree_all_passes_clean():
    results = run_all()
    rendered = [f.render() for fs in results.values() for f in fs]
    assert rendered == []
