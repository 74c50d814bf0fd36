"""Static invariant analysis for the persistent-CXL-switch simulator.

Four passes, each pinning a contract the test suite can only probe
dynamically (and expensively):

  * ``retrace``  — every sweepable config knob survives DCE of the
    abstractly traced engine cell (no baked statics);
  * ``twin``     — engine and untimed oracle consume the same policy
    fields and map their statistics onto each other;
  * ``dtypes``   — the packed scan carry keeps its dtypes, no f64->f32
    time leaks, the grid donates its staged buffers;
  * ``sweeps``   — the benchmark sweep registry matches the telemetry
    the figure scripts actually emit.

CLI: ``python -m repro.analysis [--fail-on-findings] [--json PATH]``.
"""
from __future__ import annotations

from typing import Dict, List

from repro.analysis.common import Finding

__all__ = ["Finding", "PASSES", "run_all", "run_pass"]


def _retrace() -> List[Finding]:
    from repro.analysis import retrace
    return retrace.check_engine()


def _twin() -> List[Finding]:
    from repro.analysis import twin
    return twin.check()


def _dtypes() -> List[Finding]:
    from repro.analysis import dtypes
    return dtypes.check()


def _sweeps() -> List[Finding]:
    from repro.analysis import sweeps
    return sweeps.check()


PASSES = {
    "retrace": _retrace,
    "twin": _twin,
    "dtypes": _dtypes,
    "sweeps": _sweeps,
}


def run_pass(name: str) -> List[Finding]:
    return PASSES[name]()


def run_all() -> Dict[str, List[Finding]]:
    """Run every pass; pass name -> findings (empty list when clean)."""
    return {name: fn() for name, fn in PASSES.items()}
