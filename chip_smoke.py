"""Chip smoke test: the timed PCS engine on one TPU at the paper's budget.

    python3 chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time, so nothing here starts a child).  Phases, in order; any failure
exits non-zero and no result line is printed:

1. device — a TPU must be JAX's default device; there is no CPU fallback;
2. paper grid — the workloads of ``PAPER_WORKLOADS`` at the paper's
   trace budget and bucket x NoPB/PB/PB_RF in one ``simulate_grid``
   call, run cold then warm: one XLA program, finite positive runtimes,
   every persist of every trace simulated, identical results on both
   runs;
3. correctness on the chip — the fuzzed engine<->oracle crash
   differential (engine on the chip, oracle on the host), macro-stepping
   on against off on the smoke grid (bit-exact), and the smoke grid on
   the chip against the host CPU backend (integer counts exact, float
   fields within ``CPU_REL_TOL``; a cell of ``CPU_DIVERGENT`` must
   differ instead, and every differing cell is printed).

The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests"), str(ROOT)]

# the chip-vs-CPU phase needs the host backend next to the TPU
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks._shared import (SCHEMES, _SMOKE_BUCKET,  # noqa: E402
                                _SMOKE_BUDGET, _SMOKE_TRACE_KW)
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import (PCSConfig, SimResult, WORKLOADS,  # noqa: E402
                        make_trace, simulate_grid)
from repro.core.engine import compile_count  # noqa: E402

PAPER_BUDGET = 100_000          # persist budget per workload (paper)
PAPER_BUCKET = 16384
# The scan runs until the longest trace of the grid is done and every
# trace is padded to the longest per-core stream, so the longest trace
# sets the run time.  cholesky (379,029 ops) and volrend_npl (268,169)
# are left out so that the grid fits one chip run; the budget and bucket
# stay the paper's.
PAPER_WORKLOADS = ["fft", "lu_cont", "lu_non", "radiosity", "raytrace"]
PAPER_MEAN_SPEEDUP = {"PB": 12.0, "PB_RF": 15.0}   # % over NoPB, paper
# The engine keeps time in time words (``engine.timebase``: IEEE binary64
# bit patterns in int64), which round the same on the chip as on the CPU,
# so every decision and every time field agrees exactly.  Only the
# statistics stay float64, which the TPU emulates with pairs of float32
# (about 48 significand bits, relative rounding ~3.6e-15 per operation):
# a latency sum over at most ~1e4 scan steps on the smoke grid stays
# below ~1e-10 relative; a float32 demotion shows up near 1e-7.
CPU_REL_TOL = 1e-9
# Smoke cells whose chip result may be another trajectory than the
# CPU's.  None since time rounds like IEEE on the chip (before, PB_RF
# under lu_non flipped a near-tie: ROADMAP S3).  Pinned exactly: any cell
# diverging fails the run.
CPU_DIVERGENT: set = set()


def _compare(a: SimResult, b: SimResult, rel_tol: float):
    """``(largest relative difference of the float fields, names of the
    fields that differ)``; integer fields must be equal, float fields
    within ``rel_tol`` (NaN equals NaN, inf equals only inf)."""
    worst, bad = 0.0, []
    for f in dataclasses.fields(SimResult):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if x is None or y is None:
            if x is not y:
                bad.append(f.name)
            continue
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            bad.append(f.name)
        elif np.issubdtype(x.dtype, np.floating):
            same = (x == y) | (np.isnan(x) & np.isnan(y))
            with np.errstate(invalid="ignore", divide="ignore"):
                rel = np.where(
                    same, 0.0,
                    np.where(np.isfinite(x) & np.isfinite(y),
                             np.abs(x - y) / np.maximum(np.abs(x), np.abs(y)),
                             np.inf))
            r = float(rel.max(initial=0.0))
            worst = max(worst, r)
            if r > rel_tol:
                bad.append(f.name)
        elif not np.array_equal(x, y):
            bad.append(f.name)
    return worst, bad


def _compare_grids(a, b, names, rel_tol: float):
    """``(largest relative difference over the float fields of the cells
    that agree, {(i, j): differing field names})``."""
    worst, differ = 0.0, {}
    for i in range(len(names)):
        for j in range(len(SCHEMES)):
            w, bad = _compare(a[i][j], b[i][j], rel_tol)
            if bad:
                differ[i, j] = bad
            else:
                worst = max(worst, w)
    return worst, differ


def _require_equal(a, b, names, what: str) -> None:
    _, differ = _compare_grids(a, b, names, 0.0)
    if differ:
        raise RuntimeError(f"{what}: cells differ: " + ", ".join(
            f"{names[i]}/{SCHEMES[j].name} {bad}"
            for (i, j), bad in differ.items()))


def _show(field, x, y) -> str:
    if x is None or y is None or np.ndim(x) == 0:
        return f"{field} {x!r} vs {y!r}"
    x, y = np.asarray(x), np.asarray(y)
    idx = np.argwhere(x != y)[:3]
    return f"{field} " + ", ".join(
        f"[{','.join(map(str, k))}] {x[tuple(k)].item()!r} vs "
        f"{y[tuple(k)].item()!r}"
        for k in idx)


def phase_device():
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's default device "
                         f"is {d.platform!r}); this script never falls back "
                         f"to the CPU")
    return d


def phase_paper_grid():
    t0 = time.perf_counter()
    traces = [make_trace(n, persist_budget=PAPER_BUDGET)
              for n in PAPER_WORKLOADS]
    build_s = time.perf_counter() - t0
    configs = [PCSConfig(scheme=s) for s in SCHEMES]
    print(f"paper grid: {len(traces)} workloads x "
          f"{[s.name for s in SCHEMES]}, persist_budget={PAPER_BUDGET}, "
          f"bucket={PAPER_BUCKET}, "
          f"ops={sum(t.total_ops for t in traces)}")
    print(f"paper grid: host trace build {build_s!r} s")
    print(f"paper grid cut: {sorted(set(WORKLOADS) - set(PAPER_WORKLOADS))} "
          f"left out, their traces are too long for one chip run")

    c0 = compile_count()
    t0 = time.perf_counter()
    cells = simulate_grid(traces, configs, bucket=PAPER_BUCKET)
    cold_s = time.perf_counter() - t0
    compiles = compile_count() - c0
    t0 = time.perf_counter()
    warm = simulate_grid(traces, configs, bucket=PAPER_BUCKET)
    warm_s = time.perf_counter() - t0
    print(f"paper grid: cold {cold_s!r} s (compile included), "
          f"warm {warm_s!r} s, compiles {compiles}")
    if compiles != 1:
        raise RuntimeError(f"paper grid compiled {compiles} programs, not 1")

    for tr, row in zip(traces, cells):
        want = tr.counts()["persist"]
        for scheme, r in zip(SCHEMES, row):
            if not (np.isfinite(r.runtime_ns) and r.runtime_ns > 0):
                raise RuntimeError(f"{tr.name}/{scheme.name}: runtime_ns "
                                   f"{r.runtime_ns!r}")
            if r.persists != want:
                raise RuntimeError(f"{tr.name}/{scheme.name}: {r.persists} "
                                   f"persists simulated, trace has {want}")
    _require_equal(cells, warm, PAPER_WORKLOADS, "paper grid warm rerun")
    print("paper grid: runtimes finite and positive, persist counts equal "
          "the traces', warm rerun identical")

    print("simulated speed-up over NoPB (model output, not a chip "
          "measurement):")
    speedups = {s: [] for s in PAPER_MEAN_SPEEDUP}
    for tr, (nopb, *rest) in zip(traces, cells):
        line = []
        for key, r in zip(PAPER_MEAN_SPEEDUP, rest):
            s = 100.0 * (nopb.runtime_ns / r.runtime_ns - 1.0)
            speedups[key].append(s)
            line.append(f"{key} {s:+.2f}%")
        print(f"  {tr.name:12s} " + "  ".join(line))
    for key, vals in speedups.items():
        print(f"  mean {key} {sum(vals) / len(vals):+.2f}% "
              f"(paper {PAPER_MEAN_SPEEDUP[key]:.0f}%)")


def phase_correctness():
    import test_crash_differential as tcd

    t0 = time.perf_counter()
    tcd.test_differential_matrix_one_compile()
    n_cells = tcd.N_SEEDS * len(tcd.SCHEMES) * len(tcd.CRASH_SLOTS)
    print(f"crash differential: {n_cells} fuzzed cells, engine on the chip, "
          f"oracle on the host: durable state and counts agree exactly "
          f"({time.perf_counter() - t0!r} s)")

    names = list(WORKLOADS)
    traces = [make_trace(n, persist_budget=_SMOKE_BUDGET,
                         **_SMOKE_TRACE_KW.get(n, {}))
              for n in names]
    configs = [PCSConfig(scheme=s) for s in SCHEMES]
    t0 = time.perf_counter()
    chip = simulate_grid(traces, configs, bucket=_SMOKE_BUCKET)
    plain = simulate_grid(traces, configs, bucket=_SMOKE_BUCKET, macro=False)
    _require_equal(chip, plain, names, "macro on vs off")
    print(f"macro on/off: smoke grid ({len(names)} workloads, "
          f"{_SMOKE_BUDGET} persists, bucket {_SMOKE_BUCKET}) identical "
          f"field for field on the chip ({time.perf_counter() - t0!r} s)")

    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        host = simulate_grid(traces, configs, bucket=_SMOKE_BUCKET)
    worst, differ = _compare_grids(chip, host, names, CPU_REL_TOL)
    print(f"chip vs CPU: {len(names) * len(SCHEMES) - len(differ)} of "
          f"{len(names) * len(SCHEMES)} smoke cells agree (integer counts "
          f"equal, float fields within {CPU_REL_TOL!r}); largest relative "
          f"difference of their float fields {worst!r} "
          f"({time.perf_counter() - t0!r} s)")
    diverged = set()
    for (i, j), bad in differ.items():
        cell = f"{names[i]}/{SCHEMES[j].name}"
        diverged.add(cell)
        print(f"chip vs CPU: {cell} differs (chip vs CPU): " + "; ".join(
            _show(f, getattr(chip[i][j], f), getattr(host[i][j], f))
            for f in bad))
    print(f"chip vs CPU: cells that diverge: {sorted(diverged)} "
          f"(pinned: {sorted(CPU_DIVERGENT)})")
    if diverged != CPU_DIVERGENT:
        raise RuntimeError(f"chip vs CPU: cells {sorted(diverged)} differ, "
                           f"expected {sorted(CPU_DIVERGENT)}")


def main() -> None:
    if not __debug__:
        raise SystemExit("chip_smoke: run without -O (the crash "
                         "differential checks with assert)")
    use_compile_cache()
    d = phase_device()
    phase_paper_grid()
    phase_correctness()
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
