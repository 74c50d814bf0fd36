"""The ``macro_gate_open`` reader over the engine's call records: nothing
without records or from a program that keeps no gate counter, and the
median share of gate-open steps over a run's sweeps."""
import types

import pytest

import harness
from repro.core.engine import spans


def _call(steps, gate=None, ops=100):
    c = types.SimpleNamespace(trace_ops=ops, steps=steps)
    if gate is not None:
        c.macro_gate_steps = gate
    return c


@pytest.fixture
def reader():
    return harness.load_metric("macro_gate_open").read


def test_nothing_without_records(reader, monkeypatch):
    monkeypatch.setattr(spans, "calls", lambda: [])
    assert reader({"sweeps": [(1.0, 0.0)], "ops_per_sweep": 100}) is None
    assert reader({"sweeps": [], "ops_per_sweep": 100}) is None


def test_nothing_from_a_program_without_the_counter(reader, monkeypatch):
    monkeypatch.setattr(spans, "calls", lambda: [_call(1024), _call(1024)])
    assert reader({"sweeps": [(1.0, 0.0)] * 2, "ops_per_sweep": 100}) is None


def test_median_share_over_the_sweeps(reader, monkeypatch):
    # an older call of another size is not one of the run's sweeps
    calls = [_call(512, 512, ops=7), _call(1024, 52), _call(1024, 41),
             _call(2048, 2048)]
    monkeypatch.setattr(spans, "calls", lambda: calls)
    run = {"sweeps": [(1.0, 0.0)] * 3, "ops_per_sweep": 100}
    assert reader(run) == pytest.approx(100.0 * 52 / 1024)
    assert reader(dict(run, ops_per_sweep=7)) is None
