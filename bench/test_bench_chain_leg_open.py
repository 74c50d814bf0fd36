"""The ``chain_leg_open`` reader over the engine's call records: nothing
without records, from a program that keeps no leg counter or from one
without a chain, and the median share of leg runs over a run's sweeps."""
import types

import pytest

import harness
from repro.core.engine import spans


def _call(steps, legs=None, ops=100, counter=True):
    c = types.SimpleNamespace(trace_ops=ops, steps=steps)
    if counter:
        c.chain_leg_steps = legs
    return c


@pytest.fixture
def reader():
    return harness.load_metric("chain_leg_open").read


def test_nothing_without_records(reader, monkeypatch):
    monkeypatch.setattr(spans, "calls", lambda: [])
    assert reader({"sweeps": [(1.0, 0.0)], "ops_per_sweep": 100}) is None
    assert reader({"sweeps": [], "ops_per_sweep": 100}) is None


@pytest.mark.parametrize("counter", [False, True],
                         ids=["no_counter", "no_chain"])
def test_nothing_without_the_counter_or_a_chain(reader, monkeypatch,
                                                counter):
    # a chain-free program keeps the counter as None
    calls = [_call(1024, counter=counter), _call(1024, counter=counter)]
    monkeypatch.setattr(spans, "calls", lambda: calls)
    assert reader({"sweeps": [(1.0, 0.0)] * 2, "ops_per_sweep": 100}) is None


def test_median_share_over_the_sweeps(reader, monkeypatch):
    # an older call of another size is not one of the run's sweeps
    calls = [_call(512, (512, 512), ops=7), _call(4096, (0, 2000)),
             _call(4096, (8, 2010)), _call(2048, (0, 0))]
    monkeypatch.setattr(spans, "calls", lambda: calls)
    run = {"sweeps": [(1.0, 0.0)] * 3, "ops_per_sweep": 100}
    assert reader(run) == pytest.approx(100.0 * 2000 / (2 * 4096))
    assert reader(dict(run, ops_per_sweep=7)) is None
