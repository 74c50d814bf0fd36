"""Reproduce the paper's headline numbers programmatically.

Runs the selected workloads under all three schemes and prints speedups,
persist/read latencies and the RF hit/coalesce rates (Figs 5-7).  The
whole {workload x scheme} grid — schemes mixed — is ONE ``simulate_grid``
call and therefore one XLA compilation: the scheme id is a traced
scalar, not a compile-time static.

    PYTHONPATH=src python examples/pcs_simulation.py [--quick]
"""
import argparse

from repro.compile_cache import use_compile_cache
from repro.core import PCSConfig, Scheme, make_trace, simulate_grid

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--workloads", nargs="+",
                    default=["radiosity", "cholesky", "fft"])
    args = ap.parse_args()
    use_compile_cache()
    budget = 8_000 if args.quick else 100_000

    schemes = (Scheme.NOPB, Scheme.PB, Scheme.PB_RF)
    traces = [make_trace(n, persist_budget=budget) for n in args.workloads]
    grid = simulate_grid(traces, [PCSConfig(scheme=s) for s in schemes])

    for tr, row in zip(traces, grid):
        nopb, pb, rf = row
        print(f"\n=== {tr.name} ({tr.total_ops} ops) ===")
        print(f"  speedup:   PB {100*(nopb.runtime_ns/pb.runtime_ns-1):+.1f}%"
              f"   PB_RF {100*(nopb.runtime_ns/rf.runtime_ns-1):+.1f}%")
        print(f"  persist:   NoPB {nopb.persist_lat_ns:.0f}ns -> "
              f"PB {pb.persist_lat_ns:.0f}ns "
              f"({100*pb.persist_lat_ns/nopb.persist_lat_ns:.0f}%)")
        print(f"  read:      NoPB {nopb.read_lat_ns:.0f}ns -> "
              f"PB {pb.read_lat_ns:.0f}ns "
              f"({100*pb.read_lat_ns/nopb.read_lat_ns:.0f}%)")
        print(f"  RF:        hit {100*rf.read_hit_rate:.1f}%  "
              f"coalesce {100*rf.coalesce_rate:.1f}%")
