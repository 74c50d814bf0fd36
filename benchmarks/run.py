"""Run every benchmark; print one ``name,value,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # full paper budget
    BENCH_QUICK=1 PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --smoke    # tiny traces, <60s

``--smoke`` runs each figure script on a tiny trace and writes
machine-readable ``BENCH_engine.json`` (per-figure wall time, the shared
grid's wall time and XLA compile count) so the engine perf trajectory is
tracked across PRs.  Each sweep's wall time is the WARM re-run
(``*_wall_s``); XLA compile latency is recorded separately as
``*_compile_s`` so a compile-cache hit can't mask a run regression.
"""
from __future__ import annotations

import argparse
import json
import time

from benchmarks import _shared
from repro.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny traces; write BENCH_engine.json")
    # Only smoke runs write BENCH_engine.json by default: the tracked
    # perf trajectory must stay budget-comparable across PRs.  A full
    # run writes a report only when --out is passed explicitly.
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    use_compile_cache()
    if args.out is None and args.smoke:
        args.out = "BENCH_engine.json"
    if args.smoke:
        _shared.set_smoke()

    # imported late so smoke mode is set before any trace is built
    from benchmarks import (ckpt_tier_bench, fig1_switch_depth, fig5_speedup,
                            fig6_latency, fig7_rf_rates, fig8_pbe_sweep,
                            fig_dynamic, fig_fabric, fig_qos, fig_recovery,
                            fig_slo, fig_tenants)
    from repro.core.engine import compile_count

    figures = (fig1_switch_depth, fig5_speedup, fig6_latency, fig7_rf_rates,
               fig8_pbe_sweep, fig_recovery, fig_tenants, fig_qos, fig_slo,
               fig_fabric, fig_dynamic)
    extras = () if args.smoke else (ckpt_tier_bench,)

    rows, timings = [], {}
    # Figures sharing the cached {workload x scheme} grid cost ~0 wall
    # seconds when another figure already paid for it; mark them so the
    # perf trajectory cannot misread a reused grid as a free figure.
    # The grid's own wall time is attributed once, under shared_grid_*.
    reused = {}
    t_start = time.time()
    for mod in figures + extras:
        name = mod.__name__.split(".")[-1]
        grid_was_built = bool(_shared.grid_metrics)
        t0 = time.time()
        rows.extend(mod.run())
        timings[name] = round(time.time() - t0, 2)
        if getattr(mod, "REUSES_SHARED_GRID", False) and grid_was_built:
            reused[name] = "shared_grid"
            if timings[name] < 0.05:
                # pure grid reader: its work was paid for under
                # shared_grid_wall_s, so a 0.0 here would misread as
                # "this figure is free" in the perf trajectory
                timings[name] = "reused"
        rows.append((f"_elapsed_{name}", timings[name], "seconds"))

    if args.smoke:
        # the three-layer crash demo rides the smoke path so it can't rot
        from examples.crash_recovery_demo import main as demo_main
        t0 = time.time()
        demo_main()
        timings["crash_recovery_demo"] = round(time.time() - t0, 2)
        rows.append(("_elapsed_crash_recovery_demo",
                     timings["crash_recovery_demo"], "seconds"))
    _shared.emit(rows)

    if args.out is None:
        return
    report = {
        "smoke": args.smoke,
        "budget": _shared.BUDGET,
        "bucket": _shared.bucket(),
        # measurement methodology marker: *_wall_s is the WARM re-run,
        # *_compile_s the cold-warm delta (benchmarks.compare refuses to
        # ratio reports measured under a different convention)
        "timing": "cold_warm_split",
        "total_wall_s": round(time.time() - t_start, 2),
        "compile_count": compile_count(),
        "figures_wall_s": timings,
        # figures whose wall time excludes a shared artifact they reuse
        # (the shared grid is attributed once, under shared_grid_wall_s)
        "figures_reused": reused,
        # telemetry of the shared {workload x scheme} one-program grid
        **{f"shared_{k}": v for k, v in _shared.grid_metrics.items()},
        # telemetry of the {scheme x switch-depth x crash} chain sweep
        **fig1_switch_depth.sweep_metrics,
        # telemetry of the {workload x scheme x crash-point} sweep
        **fig_recovery.sweep_metrics,
        # telemetry of the {tenant-count x scheme} shared-switch sweep
        **fig_tenants.sweep_metrics,
        # telemetry of the mixed {scheme x policy} QoS sweep
        **fig_qos.sweep_metrics,
        # telemetry of the {offered-load x scheme x policy} SLO sweep
        **fig_slo.sweep_metrics,
        # telemetry of the {scheme x leaves x placement x bp} fabric sweep
        **fig_fabric.sweep_metrics,
        # telemetry of the epoched {rate x strategy x crash} dynamic sweep
        **fig_dynamic.sweep_metrics,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
