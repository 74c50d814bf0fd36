"""Benchmark harness: one cell of ``BENCHMARK.json``, run once.

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (read by :mod:`gen`), and each
per-layer metric in ``bench/metrics/<metric>.py``.  A later cell or
metric is a new file and a new entry, never an edit here.

The timed path is the program's entry point ``simulate_grid``, driven
closed-loop: one client sends the next sweep (every {trace x config}
cell of the mix in one call) once the last one has returned its
``SimResult``s to the host.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                     f"have {[w['name'] for w in spec['workloads']]}")


def config_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "configs" / f"{name}.json"


def traffic_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "traffic" / f"{name}.json"


def metric_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "metrics" / f"{name}.py"


def load_metric(name: str, bench: Path = BENCH):
    """The reader module of one per-layer metric (``read(run)``)."""
    path = metric_path(name, bench)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ inputs
def build_traces(config: dict, traffic: dict, seed: "int | None"):
    import gen
    return gen.build(traffic, seed, int(config["persist_budget"]))


def nominal_span_ns(traces) -> float:
    """The longest core's sum of compute gaps: the run's span when no
    op waits for anything (the crash anchor of a crashed replica)."""
    best = 0.0
    for tr in traces:
        for c in range(tr.n_cores):
            n = int(tr.lengths[c])
            best = max(best, float(np.sum(tr.gaps[c, :n], dtype=np.float64)))
    return best


def make_configs(config: dict, traces) -> list:
    """The cell's ``PCSConfig`` column, in the order of ``grid``."""
    from repro.core import PCSConfig, Scheme
    from repro.core.params import (DrainPolicy, LatencyProfile, PBPolicy)
    m = config["machine"]
    lat = LatencyProfile(**m["latency"])
    policy = PBPolicy(drain=DrainPolicy(
        threshold=m["drain_threshold"], preset=m["drain_preset"],
        low_water_drains=m["low_water_drains"],
        empty_slack=m["empty_slack"]))
    span = nominal_span_ns(traces)
    out = []
    for g in config["grid"]:
        crash = math.inf
        if "crash_at_span_fraction" in g:
            crash = float(g["crash_at_span_fraction"]) * span
        out.append(PCSConfig(
            scheme=Scheme[g["scheme"]], n_pbe=m["n_pbe"],
            n_switches=g["n_switches"], n_cores=m["n_cores"],
            policy=policy, pm_banks=m["pm_banks"], crash_at_ns=crash,
            latency=lat))
    return out


def bucket_for(traces) -> int:
    """One shape bucket that holds every stream and the scan length; a
    seed never changes a size, so every run of a cell lands in it."""
    from repro.core.params import MACRO_KMAX
    need = max(max(t.total_ops for t in traces),
               max(t.ops.shape[1] for t in traces) + MACRO_KMAX)
    return 1024 * ((need + 1023) // 1024)


def empty_like(traces):
    """Traces of the same core counts with no ops: the program they
    lower to has the cell's exact shapes, and its scan exits at once."""
    from repro.core import Trace
    out = []
    for t in traces:
        C = t.n_cores
        out.append(Trace(ops=np.zeros((C, 1), np.int32),
                         addrs=np.zeros((C, 1), np.int32),
                         gaps=np.zeros((C, 1), np.float32),
                         lengths=np.zeros((C,), np.int32), name=t.name))
    return out


def sweep(traces, configs, bucket: int):
    """One request of the timed path: every cell in one program call."""
    from repro.core.engine import simulate_grid
    return simulate_grid(traces, configs, bucket=bucket)


def ops_per_sweep(traces, configs) -> int:
    return sum(t.total_ops for t in traces) * len(configs)


# ----------------------------------------------------------- results
def result_record(r) -> Dict[str, object]:
    """Every ``SimResult`` field as plain numbers (arrays as lists)."""
    out = {}
    for f in dataclasses.fields(r):
        v = getattr(r, f.name)
        if v is None:
            out[f.name] = None
        elif isinstance(v, np.ndarray):
            out[f.name] = np.asarray(v, np.float64).tolist()
        else:
            out[f.name] = float(v)
    return out


def grid_records(cells) -> List[List[dict]]:
    return [[result_record(r) for r in row] for row in cells]


# ------------------------------------------------------------- the run
def device_check(chips: int):
    """The chips JAX found; exits when there is no TPU or too few."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind}). There is no CPU "
            "fallback.")
    return devs


class Prepared:
    """A cell after set-up: inputs built, the program loaded and warm."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 sweep_fn=None):
        from repro.core.engine import compile_count
        self.sweep_fn = sweep_fn or sweep
        self.traces = build_traces(config, traffic, seed)
        self.configs = make_configs(config, self.traces)
        self.bucket = bucket_for(self.traces)
        self.ops = ops_per_sweep(self.traces, self.configs)
        c0 = compile_count()
        t0 = time.perf_counter()
        # the cell's exact shapes with empty streams: compiles (or loads
        # from the compile cache) the one program, and its scan exits at
        # its first check
        self.sweep_fn(empty_like(self.traces), self.configs, self.bucket)
        self.warm_s = time.perf_counter() - t0
        self.compiles = compile_count() - c0


def run_window(prep: Prepared, seconds: float, min_sweeps: int,
               max_sweeps: int = 10 ** 9):
    """Closed-loop sweeps until the next one would overrun ``seconds``
    (judged by the last sweep's time), at least ``min_sweeps``.

    Returns ``(sweeps, window_s)``; each sweep is ``(host seconds, macro
    hit share, result records)``."""
    from repro.core.engine import last_macro_hit_rate
    sweeps = []
    t_win = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        cells = prep.sweep_fn(prep.traces, prep.configs, prep.bucket)
        dt = time.perf_counter() - t0
        sweeps.append((dt, last_macro_hit_rate(), grid_records(cells)))
        done = time.perf_counter() - t_win
        if len(sweeps) >= max_sweeps:
            break
        if len(sweeps) >= min_sweeps and done + dt > seconds:
            break
    return sweeps, time.perf_counter() - t_win


def limits_path(workload: str, bench: Path = BENCH) -> Path:
    return bench / "limits" / f"{workload}.json"


def process_start(fallback: float) -> float:
    """Wall-clock time at which this process started."""
    try:
        import psutil
        return psutil.Process().create_time()
    except (ImportError, OSError):
        return fallback


def _peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def _mark(name: str) -> None:
    """A zero-length host span: a marker on the trace's clock."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        pass


def _profile(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def traced_run(prep: Prepared, window_s: float):
    """The traced run: three sweeps, two of them profiled at one end.

    A whole sweep's device trace is too large to record (millions of op
    events a second), and on a TPU v5e a second session opened late in a
    sweep whose start a first session profiled recorded no device op.  So the
    profiler records ``window_s`` at the start of one sweep (from the
    ``sweep_start`` marker: host front end, then the program's first
    trips) and ``window_s`` at the end of the next (the program's last
    trips, then the host, up to the ``sweep_end`` marker).  A first,
    untraced sweep gives the sweep's time on the host clock and predicts
    when the second session opens.  Returns ``(sweeps, reduction)``; the
    traces go to ``TMPDIR`` and are removed once reduced."""
    import shutil
    import tempfile
    import threading
    import jax
    import trace_reduce
    from repro.core.engine import last_macro_hit_rate
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        sweeps, _ = run_window(prep, 0.0, 1, 1)
        sweep_s = sweeps[0][0]
        a, b = os.path.join(tmp, "start"), os.path.join(tmp, "end")

        def traced(opener, open_at: float, mark_first: bool) -> None:
            timer = threading.Timer(open_at, opener)
            if mark_first:
                _mark("sweep_start")
            timer.start()
            t0 = time.perf_counter()
            cells = prep.sweep_fn(prep.traces, prep.configs, prep.bucket)
            dt = time.perf_counter() - t0
            timer.join()
            if not mark_first:
                _mark("sweep_end")
            sweeps.append((dt, last_macro_hit_rate(), grid_records(cells)))

        _profile(a)
        traced(jax.profiler.stop_trace, window_s, True)
        traced(lambda: _profile(b), max(sweep_s - window_s, 0.0), False)
        jax.profiler.stop_trace()
        red = trace_reduce.combine(
            trace_reduce.reduce(trace_reduce.find_xplane(a), "start"),
            trace_reduce.reduce(trace_reduce.find_xplane(b), "end"),
            sweep_s)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return sweeps, red


def main(workload: str, seed: int, seconds: float, trace: bool,
         t_start: float, *, root: Path = ROOT, bench: Path = BENCH,
         require_tpu: bool = True, sweep_fn=None, out=None, err=None
         ) -> int:
    """One run of one cell; prints the result line and returns 0."""
    import sys
    import jax
    import check
    import reference
    out = out or sys.stdout
    err = err or sys.stderr
    spec = load_spec(root)
    cell = find_cell(spec, workload)
    if require_tpu:
        devs = device_check(int(cell["chips"]))
    else:
        devs = jax.devices()
    devs = devs[:int(cell["chips"])]
    from repro.compile_cache import use_compile_cache
    from repro.core.engine import compile_count
    use_compile_cache()
    config = load_json(config_path(cell["config"], bench))
    traffic = load_json(traffic_path(cell["traffic"], bench))
    limits = {k: float(v["limit"]) for k, v in load_json(
        limits_path(workload, bench))["numbers"].items()}

    device_s = time.time() - t_start
    prep = Prepared(config, traffic, seed, sweep_fn)
    setup_s = time.time() - t_start
    c0 = compile_count()
    red = None
    if trace:
        sweeps, red = traced_run(prep, float(traffic["trace_window_s"]))
        window_s = sum(s[0] for s in sweeps)
    else:
        # the window holds whole sweeps only, at least the mix's minimum
        sweeps, window_s = run_window(prep, seconds,
                                      int(traffic.get("min_sweeps", 1)))
    window_compiles = compile_count() - c0
    peak = _peak_bytes(devs) if require_tpu else 0

    # the reference, once the window has closed
    t0 = time.perf_counter()
    ref = reference.simulate_grid(prep.traces, config)
    ref_s = time.perf_counter() - t0
    numbers = check.compare([s[2] for s in sweeps], ref)
    numbers["window_compiles"] = float(window_compiles)
    correct = check.verdict(numbers, limits)
    per_sweep = sum(len(row) for row in ref)
    failed = sum(check.cells_failing(s[2], ref, limits) for s in sweeps)

    print(f"bench: {workload} seed {seed}: {len(sweeps)} sweeps of "
          f"{per_sweep} cells, {prep.ops} ops each; sweep seconds "
          f"{[s[0] for s in sweeps]}; window {window_s!r} s; set-up "
          f"{setup_s!r} s: {device_s!r} s to reach the device, "
          f"{prep.warm_s!r} s to load and warm the program "
          f"({prep.compiles} program(s) built); reference {ref_s!r} s",
          file=out)
    if red is not None:
        print(f"bench: traced sweep {red['window_s']!r} s: program span "
              f"{red['span_s']!r} s, of which the trace shows "
              f"{red['program_seen_s']!r} s and leaves {red['unseen_s']!r} s "
              "unseen (control flow with no recorded body)", file=out)
    run = {"sweeps": [(s[0], s[1]) for s in sweeps], "window_s": window_s,
           "ops_per_sweep": prep.ops, "setup_s": setup_s, "trace": red}
    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            v = load_metric(m["name"], bench).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics["sim_ops_per_s"] = {
            "value": prep.ops * len(sweeps) / window_s, "unit": "ops/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": per_sweep * len(sweeps),
            "failed": int(failed), "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = red["breakdown"]
    line["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    for k in limits:
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r}", file=err)
    print(f"check correct {bool(correct)}", file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
    return 0
