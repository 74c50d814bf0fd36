"""The engine's own spans and counters (``repro.core.engine.spans``) for
the run's sweeps, as the per-layer metrics that read them need them.

The run's sweeps are the engine's last ``len(run["sweeps"])`` calls (the
reference after the window is not the engine).  Each must have simulated
the run's ``ops_per_sweep`` trace ops, else nothing is read; nothing is
read either from a program that keeps no such records.
"""
from __future__ import annotations

import statistics
from typing import Callable, Iterable, List, Optional

# the host stages of a call around its device scan
HOST_STAGES = ("engine.stack", "engine.plan_runs", "engine.lower_configs",
               "engine.put", "engine.fetch", "engine.unpack")


def _spans():
    try:
        from repro.core.engine import spans
    except ImportError:
        return None
    return spans


def sweep_calls(run) -> Optional[List[object]]:
    """The call records of the run's sweeps, oldest first, or None."""
    spans = _spans()
    n = len(run.get("sweeps") or ())
    if spans is None or not n or len(spans.calls()) < n:
        return None
    calls = spans.calls()[-n:]
    if any(c.trace_ops != run.get("ops_per_sweep") for c in calls):
        return None
    return calls


def span_ns(call, names: Iterable[str]) -> int:
    """Nanoseconds the call spent in the spans of these names."""
    names = set(names)
    return sum(s.t1_ns - s.t0_ns for s in _spans().spans()
               if s.call_id == call.call_id and s.name in names)


def median_over(run, value: Callable[[object], Optional[float]]
                ) -> Optional[float]:
    """The median over the run's sweeps of ``value(call)``; None when
    there are no records or any sweep gives None."""
    calls = sweep_calls(run)
    if not calls:
        return None
    got = [value(c) for c in calls]
    return None if any(v is None for v in got) else statistics.median(got)


def setup_seconds(run, kind: str) -> Optional[float]:
    """JAX's ``kind`` seconds (``trace``, ``lower``, ``compile``) before
    the run's first sweep: those of the set-up."""
    calls = sweep_calls(run)
    return calls[0].jax_s[kind] if calls else None
