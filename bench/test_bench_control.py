"""The control of ``correct``: the plain reference put in the program's
place and computed in float32, the precision below the float64 that the
configurations state, at each cell's own size.  It must come out as not
correct against the committed limits, on every seed tried."""
import numpy as np
import pytest

import check
import harness
import reference

SPEC = harness.load_spec()
SEEDS = (1, 2, 2**31 + 3)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_float32_control_fails(workload, seed):
    w = harness.find_cell(SPEC, workload)
    cfg = harness.load_json(harness.config_path(w["config"]))
    trf = harness.load_json(harness.traffic_path(w["traffic"]))
    limits = {k: v["limit"] for k, v in harness.load_json(
        harness.limits_path(workload))["numbers"].items()}
    traces = harness.build_traces(cfg, trf, seed)
    ref = reference.simulate_grid(traces, cfg)
    ctl = reference.simulate_grid(traces, cfg, ftype=np.float32)
    numbers = check.compare([ctl], ref)
    numbers["window_compiles"] = 0.0
    assert numbers["gap"] > limits["gap"]
    assert not check.verdict(numbers, limits)
