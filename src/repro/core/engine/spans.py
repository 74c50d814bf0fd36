"""The engine's spans and counters: the one home of its telemetry.

Every ``simulate_grid`` / ``simulate_cells`` call is one *call*: a root
span ``engine.call`` with its stages as child spans, and one counter
record (:class:`Call`).  A span opens a ``jax.profiler.TraceAnnotation``
of its name, so a profiled run shows it on the device trace's clock, and
appends a :class:`Span` on ``time.perf_counter_ns`` to a bounded
in-process log.  Always on: off the profiler a span costs two clock
reads and an append.

What reads each (the benchmark's per-layer metrics, ``bench/metrics/``):

* ``engine.scan`` over :attr:`Call.steps` — ``step_us``;
* :attr:`Call.segments` — ``step_waste``;
* ``engine.stack``, ``engine.plan_runs``, ``engine.lower_configs``,
  ``engine.put``, ``engine.fetch``, ``engine.unpack`` — ``host_span_ms``;
* :attr:`Call.macro_ops` over :attr:`Call.trace_ops`
  (:func:`last_macro_hit_rate`) — ``macro_hit``;
* :func:`jax_seconds` as :attr:`Call.jax_s` of the first timed call —
  ``setup_trace_s``, ``setup_lower_s``, ``setup_compile_s``;
* :func:`compile_count` — the benchmark's ``window_compiles`` check and
  ``benchmarks/check_compiles.py``.

``jax_seconds`` sums the durations JAX reports through
``jax.monitoring`` for tracing a program to a jaxpr, lowering it to MLIR,
and compiling it or loading it from the persistent cache.  These events
nest (tracing an inner ``jit`` inside an outer trace or a lowering), so
only the outermost one is counted, under its own kind.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax
import numpy as np

from repro.core.engine.macro import MACRO_ABORT_REASONS

SPAN_LOG = 4096     # spans kept, newest last
CALL_LOG = 256      # call records kept, newest last


class Span(NamedTuple):
    call_id: int
    name: str
    parent: Optional[str]
    t0_ns: int
    t1_ns: int


@dataclasses.dataclass
class Call:
    """Counters of one ``simulate_grid`` / ``simulate_cells`` call."""
    call_id: int
    jax_s: Dict[str, float]         # jax_seconds() when the call began
    cells: int = 0
    trace_ops: int = 0              # trace slots simulated, all cells
    segments: Optional[np.ndarray] = None   # CHUNK-step segments, per cell
    steps: int = 0                  # grid steps executed
    macro_ops: int = 0              # trace slots committed by macro-steps
    abort_reasons: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(MACRO_ABORT_REASONS, 0))
    compiles: int = 0               # engine programs built by the call


_SPANS: "collections.deque[Span]" = collections.deque(maxlen=SPAN_LOG)
_CALLS: "collections.deque[Call]" = collections.deque(maxlen=CALL_LOG)
_IDS = itertools.count(1)
_LOCAL = threading.local()
# one tick per engine program traced (``step.scan_cell``)
_COMPILES = [0]


def _open_spans() -> List[tuple]:
    if not hasattr(_LOCAL, "open"):
        _LOCAL.open = []
    return _LOCAL.open


@contextlib.contextmanager
def _span(call_id: int, name: str) -> Iterator[None]:
    stack = _open_spans()
    parent = stack[-1][1] if stack else None
    stack.append((call_id, name))
    t0 = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        stack.pop()
        _SPANS.append(Span(call_id, name, parent, t0,
                           time.perf_counter_ns()))


def span(name: str):
    """A stage of the engine call open on this thread."""
    stack = _open_spans()
    assert stack, f"span {name!r} outside an engine call"
    return _span(stack[-1][0], name)


@contextlib.contextmanager
def call() -> Iterator[Call]:
    """One engine call: the root span ``engine.call`` and its counter
    record, kept once the call returns."""
    rec = Call(call_id=next(_IDS), jax_s=jax_seconds())
    c0 = _COMPILES[0]
    with _span(rec.call_id, "engine.call"):
        yield rec
    rec.compiles = _COMPILES[0] - c0
    _CALLS.append(rec)


def spans() -> List[Span]:
    return list(_SPANS)


def calls() -> List[Call]:
    return list(_CALLS)


def _last_call() -> Optional[Call]:
    return _CALLS[-1] if _CALLS else None


def count_compile() -> None:
    _COMPILES[0] += 1


def compile_count() -> int:
    """Number of engine XLA programs traced/compiled so far this process."""
    return _COMPILES[0]


def last_macro_hit_rate() -> float:
    """Fraction of trace slots the latest simulate_* call ran via
    macro-steps (0.0 when macro was disabled or nothing ran)."""
    rec = _last_call()
    return rec.macro_ops / rec.trace_ops if rec and rec.trace_ops else 0.0


def last_macro_abort_reasons() -> dict:
    """Per-reason counts of live macro candidates the latest simulate_*
    call failed to commit, keyed by ``MACRO_ABORT_REASONS`` name (all
    zero when macro was disabled or nothing ran).  ``macro_hit`` reads
    the hit rate beside it; these say which guard turned the rest
    away."""
    rec = _last_call()
    if rec is None:
        return dict.fromkeys(MACRO_ABORT_REASONS, 0)
    return dict(rec.abort_reasons)


# ------------------------------------------------- jax.monitoring listener
_JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
               "/jax/core/compile/backend_compile_duration": "compile"}
_JAX_S = dict.fromkeys(_JAX_EVENTS.values(), 0.0)
_JAX_LOCK = threading.Lock()


def jax_seconds() -> Dict[str, float]:
    """Seconds this process spent tracing (``trace``), lowering
    (``lower``) and compiling or loading from the cache (``compile``)."""
    with _JAX_LOCK:
        return dict(_JAX_S)


def _depth() -> List[int]:
    if not hasattr(_LOCAL, "depth"):
        _LOCAL.depth = [0]
    return _LOCAL.depth


def _on_start(event: str, _value: float, **_kw) -> None:
    # JAX records the start of each timed stage as a scalar event
    if event in _JAX_EVENTS:
        _depth()[0] += 1


def _on_duration(event: str, secs: float, **_kw) -> None:
    kind = _JAX_EVENTS.get(event)
    if kind is None:
        return
    depth = _depth()
    depth[0] = max(depth[0] - 1, 0)
    if depth[0] == 0:
        with _JAX_LOCK:
            _JAX_S[kind] += secs


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
