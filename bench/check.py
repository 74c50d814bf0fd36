"""The comparison that decides ``correct``.

What the window's sweeps returned is held against the plain reference
(:mod:`reference`) cell by cell and field by field, every field of the
program's ``SimResult`` that the reference models.  The numbers compared:

* ``sweeps_differ``: sweeps of the window whose results are not those of
  the window's first sweep, field for field (the program is
  deterministic: the same inputs give the same bits).  Limit 0.
* ``gap``: the widest relative gap between the program's and the
  reference's value of any field of any cell.  A time field (runtime,
  mean latencies, stall and recovery times, per-switch commit-latency
  sums) is compared relative to the larger of the two magnitudes; a
  count (persists, reads, hits, coalesces, PM writes, detours, victims,
  acked and durable persists, recovered entries per switch, the latency
  histogram's bins, the per-switch counts) relative to the reference's
  count, at least 1.  ``cell_gaps`` keeps the two kinds apart for the
  diagnostics printed beside it.  A sweep that returns fewer or more
  cells than the reference computes has the gap ``inf``.
* ``window_compiles``: programs built inside the window.  Limit 0.

Each number has its own limit, kept per workload in
``bench/limits/<workload>.json`` with the readings it was set from.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

# SimResult fields that hold times (relative gap) and counts
TIME_FIELDS = ("runtime_ns", "persist_lat_ns", "read_lat_ns", "stall_ns",
               "recovery_ns")
COUNT_FIELDS = ("persists", "pm_reads", "read_hits", "coalesces",
                "pm_writes", "pi_detours", "victim_drains",
                "acked_persists", "durable_persists", "recovery_entries",
                "hop_recovery", "lat_hist")
# per-switch statistics: column 0 is a latency sum, the rest are counts
HOP_TIME_COLS = (0,)


def _rel(a: float, b: float) -> float:
    if math.isnan(a) or math.isnan(b):
        return 0.0 if math.isnan(a) and math.isnan(b) else math.inf
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


def _count_gap(a, b) -> float:
    if a is None or b is None:
        return 0.0 if a is None and b is None else math.inf
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    if a.shape != b.shape:
        return math.inf
    if not a.size:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def cell_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """(time gap, count gap) of one cell: program record vs reference."""
    tg = max(_rel(float(prog[f]), float(ref[f])) for f in TIME_FIELDS)
    cg = max(_count_gap(prog[f], ref[f]) for f in COUNT_FIELDS)
    ph, rh = prog["hop_stats"], ref["hop_stats"]
    if (ph is None) != (rh is None):
        cg = math.inf
    elif ph is not None:
        ph, rh = np.asarray(ph, np.float64), np.asarray(rh, np.float64)
        if ph.shape != rh.shape:
            cg = math.inf
        else:
            for col in range(ph.shape[1]):
                if col in HOP_TIME_COLS:
                    tg = max([tg] + [_rel(float(x), float(y))
                                     for x, y in zip(ph[:, col], rh[:, col])])
                else:
                    cg = max(cg, _count_gap(ph[:, col], rh[:, col]))
    return {"time_gap": tg, "count_gap": cg}


def identical(a: List[List[dict]], b: List[List[dict]]) -> bool:
    """Two sweeps' records equal field for field (NaN equals NaN)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for x, y in zip(ra, rb):
            if x.keys() != y.keys():
                return False
            for k in x:
                u, v = x[k], y[k]
                if u is None or v is None:
                    if u is not v:
                        return False
                elif not np.array_equal(np.asarray(u, np.float64),
                                        np.asarray(v, np.float64),
                                        equal_nan=True):
                    return False
    return True


def same_shape(sweep: List[List[dict]], ref: List[List[dict]]) -> bool:
    """A row for every trace and a cell for every configuration."""
    return len(sweep) == len(ref) and all(
        len(p) == len(r) for p, r in zip(sweep, ref))


def compare(sweeps: List[List[List[dict]]], ref: List[List[dict]]
            ) -> Dict[str, float]:
    """The numbers compared, over every sweep of the window.  A sweep
    with a cell missing or added has the gap ``inf``."""
    differ = sum(0 if identical(s, sweeps[0]) else 1 for s in sweeps[1:])
    if not same_shape(sweeps[0], ref):
        return {"sweeps_differ": float(differ), "gap": math.inf}
    gap = 0.0
    for prow, rrow in zip(sweeps[0], ref):
        for p, r in zip(prow, rrow):
            gap = max([gap] + list(cell_gaps(p, r).values()))
    return {"sweeps_differ": float(differ), "gap": gap}


def cells_failing(sweep: List[List[dict]], ref: List[List[dict]],
                  limits: Dict[str, float]) -> int:
    """Cells of the reference that one sweep missed, or answered with a
    gap past its limit."""
    failing = 0
    for i, rrow in enumerate(ref):
        prow = sweep[i] if i < len(sweep) else []
        for j, r in enumerate(rrow):
            failing += (j >= len(prow)
                        or max(cell_gaps(prow[j], r).values()) > limits["gap"])
    return failing


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
