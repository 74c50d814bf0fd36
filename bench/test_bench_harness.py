"""The harness on the CPU: files found by name, one sweep per cell's
traffic at a tiny size, the refusal without a TPU, a new traffic file
found with no other edit, and the faults that ``correct`` must catch."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import check
import harness
import reference

SPEC = harness.load_spec()


def _tiny(config: dict, traffic: dict) -> None:
    """Shrink a cell's inputs in place to a size the CPU runs quickly."""
    config["persist_budget"] = min(config["persist_budget"], 24)
    for st in traffic["streams"]:
        if st["kind"] == "fft":
            st["params"]["m"] = 6
        if st["kind"] == "lu":
            st["params"]["n"] = 32
        if st["kind"] == "signature":
            st["params"]["n_iters"] = min(st["params"]["n_iters"], 40)
        if st["kind"] == "probe":
            st["params"]["n_ops"] = 40


def test_named_files_load():
    for c in SPEC["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["grid"] and cfg["machine"]["n_pbe"] > 0
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    for w in SPEC["workloads"]:
        assert harness.config_path(w["config"]).is_file()
        trf = harness.load_json(harness.traffic_path(w["traffic"]))
        assert trf["streams"]
        lim = harness.load_json(harness.limits_path(w["name"]))
        assert set(lim["numbers"]) == {"sweeps_differ", "gap", "window_compiles"}
    for m in SPEC["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_one_sweep_matches_reference_tiny(workload):
    """One sweep of the cell's traffic through the harness's own sweep
    function: on the CPU (IEEE float64) it equals the reference."""
    w = harness.find_cell(SPEC, workload)
    cfg = harness.load_json(harness.config_path(w["config"]))
    trf = harness.load_json(harness.traffic_path(w["traffic"]))
    _tiny(cfg, trf)
    traces = harness.build_traces(cfg, trf, 2**31 + 5)
    configs = harness.make_configs(cfg, traces)
    cells = harness.grid_records(
        harness.sweep(traces, configs, harness.bucket_for(traces)))
    ref = reference.simulate_grid(traces, cfg)
    got = check.compare([cells], ref)
    assert got == {"sweeps_differ": 0.0, "gap": 0.0}


def test_command_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr and "cpu" in p.stderr
    assert "{" not in p.stdout


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """A copy of the benchmark's layout with one traffic file added
    (``splash7_tiny``) and one workload entry naming it."""
    root = tmp_path_factory.mktemp("layout")
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    base = harness.find_cell(spec, "pcs16_chain4.fig1_probe")
    cfg = harness.load_json(harness.config_path(base["config"]))
    trf = harness.load_json(harness.traffic_path(base["traffic"]))
    _tiny(cfg, trf)
    # the new mix: a shorter probe, depth 0..2 of the chain configuration
    cfg["grid"] = [g for g in cfg["grid"] if g["n_switches"] <= 2]
    (root / "bench" / "configs" / "chain_tiny.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "probe_tiny.json").write_text(
        json.dumps(trf))
    shutil.copy(harness.limits_path(base["name"]),
                root / "bench" / "limits" / "chain_tiny.probe_tiny.json")
    spec["workloads"].append(dict(base, name="chain_tiny.probe_tiny",
                                  config="chain_tiny", traffic="probe_tiny"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(layout, sweep_fn=None, seed=11):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.main("chain_tiny.probe_tiny", seed, 0.0, False,
                      time.time(), root=layout, bench=layout / "bench",
                      require_tpu=False, sweep_fn=sweep_fn, out=out, err=err)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "check"
    assert err.getvalue().strip().splitlines()[-1].startswith(
        "check correct")
    return line


def test_new_traffic_found_by_name(layout):
    line = _run(layout)
    assert line["correct"] is True and line["failed"] == 0
    trf = harness.load_json(layout / "bench" / "traffic" / "probe_tiny.json")
    assert line["attempted"] == 11 * trf["min_sweeps"]
    assert set(line["metrics"]) == {"sim_ops_per_s", "setup_s"}
    assert line["check"]["gap"]["value"] == 0.0


def _altered(traces, configs, bucket):
    """An answer altered where it is produced: one cell's runtime."""
    cells = harness.sweep(traces, configs, bucket)
    import dataclasses
    r = cells[0][1]
    cells[0][1] = dataclasses.replace(r, runtime_ns=r.runtime_ns * 1.001)
    return cells


def _half(traces, configs, bucket):
    """Half of the batch left out: each stream cut to half its ops."""
    from repro.core import Trace
    cut = [Trace(ops=t.ops, addrs=t.addrs, gaps=t.gaps,
                 lengths=(t.lengths // 2).astype(np.int32), name=t.name)
           for t in traces]
    return harness.sweep(cut, configs, bucket)


def _unchanged(traces, configs, bucket):
    """A step that leaves the state unchanged: nothing is simulated."""
    return harness.sweep(harness.empty_like(traces), configs, bucket)


def _dropped(traces, configs, bucket):
    """A cell left out of the result: the last configuration's column
    of the first trace."""
    cells = harness.sweep(traces, configs, bucket)
    cells[0] = cells[0][:-1]
    return cells


@pytest.mark.parametrize("fault", [_altered, _half, _unchanged, _dropped],
                         ids=["answer_altered", "half_left_out",
                              "state_unchanged", "cell_left_out"])
def test_faults_make_correct_false(layout, fault):
    line = _run(layout, sweep_fn=fault)
    assert line["correct"] is False
    assert line["failed"] > 0
