"""The PB/PB_RF cell on the CPU: the program equals the plain reference
bit for bit, macro-stepping changes no bit, and the float32 control
fails the cell's limit.

Seed 378304475 is the seed at which the chip's emulated float64 once
parted from IEEE on this machine (lu_non under PB)."""
import copy

import numpy as np
import pytest

import check
import harness
import reference

WORKLOAD = "pcs16_1sw_pbrf.splash7"
SEED = 378304475
SMALL_BUDGET = 60


def _cell():
    spec = harness.load_spec()
    w = harness.find_cell(spec, WORKLOAD)
    cfg = harness.load_json(harness.config_path(w["config"]))
    trf = harness.load_json(harness.traffic_path(w["traffic"]))
    limits = {k: v["limit"] for k, v in harness.load_json(
        harness.limits_path(WORKLOAD))["numbers"].items()}
    return cfg, trf, limits


@pytest.fixture(scope="module")
def small():
    cfg, trf, _ = _cell()
    cfg = copy.deepcopy(cfg)
    cfg["persist_budget"] = SMALL_BUDGET
    traces = harness.build_traces(cfg, trf, SEED)
    return cfg, traces, harness.make_configs(cfg, traces)


def _sweep(traces, configs, macro):
    from repro.core.engine import simulate_grid
    return harness.grid_records(simulate_grid(
        traces, configs, bucket=harness.bucket_for(traces), macro=macro))


def test_program_equals_reference(small):
    cfg, traces, configs = small
    assert [c.scheme.name for c in configs] == ["PB", "PB_RF"]
    got = check.compare([_sweep(traces, configs, True)],
                        reference.simulate_grid(traces, cfg))
    assert got == {"sweeps_differ": 0.0, "gap": 0.0}


def test_macro_on_and_off_identical(small):
    _, traces, configs = small
    assert _sweep(traces, configs, True) == _sweep(traces, configs, False)


def test_float32_control_fails_the_limit():
    cfg, trf, limits = _cell()
    traces = harness.build_traces(cfg, trf, SEED)
    ref = reference.simulate_grid(traces, cfg)
    ctl = reference.simulate_grid(traces, cfg, ftype=np.float32)
    numbers = check.compare([ctl], ref)
    numbers["window_compiles"] = 0.0
    assert numbers["gap"] > limits["gap"]
    assert not check.verdict(numbers, limits)
