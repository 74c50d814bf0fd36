"""Reduction of profiler traces (``.xplane.pb``) to the per-layer numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  On a TPU trace
each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per operation run; host threads are ``/host:CPU`` planes,
where the runtime's own spans (``shard_args``, ``np.asarray(jax.Array)``,
...) and the benchmark's markers (``sweep_start``, ``sweep_end``) land.
Host and device events share one clock.

The traced run records two sessions (``harness.traced_run``): ``start``
opens at the ``sweep_start`` marker, just before a sweep is sent, and
ends where the profiler was stopped; ``end`` opens where the profiler was
started, late in the next sweep, and closes at the ``sweep_end`` marker,
just after that sweep returned.
For each session (device times averaged over the chips):

* ``front_s`` (start): from the marker to the program's first op on the
  device, the host front end of a sweep;
* ``back_s`` (end): from the program's last op to the marker;
* ``program_seen_s`` and ``program_busy_s``: the part of the session
  inside the program (after its first op, or before its last) that the
  trace shows, and the union of the op intervals in it;
* ``unseen_s``: what control-flow ops with no recorded body span inside
  the program, left out of both;
* ``ops``: device time per op, under the instruction name the trace
  gives (``%fusion.12``); ``gaps``: the device's idle gaps, each named
  by where it falls (before, inside or after the program) and by the
  innermost host span open at its middle.

Only leaf ops count as busy: an op inside which another op starts (a
``while`` spans its body) is a container, left out of the busy time,
``ops`` and ``gaps``.  The program's first and last op bound it either
way.  :func:`combine` joins the two sessions into the sweep's numbers.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MARKERS = ("sweep_start", "sweep_end")
TOP = 10


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def op_name(name: str) -> str:
    """The HLO instruction name of a trace op (``%fusion.12 = ...``)."""
    return name.split(" = ", 1)[0]


def _union(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float
           ) -> np.ndarray:
    """Disjoint sorted intervals covering ``[starts, ends)`` clipped to
    ``[lo, hi]``, as an ``(n, 2)`` array."""
    s = np.clip(starts, lo, hi)
    e = np.clip(ends, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], np.append(reach[idx[1:] - 1], reach[-1])],
                    axis=1)


def leaves(ev) -> np.ndarray:
    """Mask of the events that hold no other event.  A core runs one op
    at a time, so an event another one starts inside is a container (a
    ``while`` op spans its body's ops): counting it would hide every gap
    between the ops it holds."""
    starts = np.asarray([x[0] for x in ev], np.float64)
    ends = np.asarray([x[1] for x in ev], np.float64)
    order = np.argsort(starts, kind="stable")
    nxt = np.full(len(ev), np.inf)
    nxt[order[:-1]] = starts[order[1:]]
    return nxt >= ends


def _host_at(t: float, host) -> str:
    """The innermost host span open at ``t``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no host span"


CONTROL_FLOW = ("%while", "%conditional", "%call")


def bodiless(ev, leaf: np.ndarray) -> np.ndarray:
    """Mask of the control-flow ops (``while``, ``conditional``,
    ``call``) that hold no recorded op: their body ran but was not
    traced, so what they span is neither busy nor idle as far as the
    trace can tell."""
    return np.asarray([bool(leaf[k]) and ev[k][2].startswith(CONTROL_FLOW)
                       and op_name(ev[k][2]).split(".")[0] in CONTROL_FLOW
                       for k in range(len(ev))], bool)


def _length(u: np.ndarray) -> float:
    return float(np.sum(u[:, 1] - u[:, 0])) if u.size else 0.0


def reduce(path: str, kind: str) -> Dict[str, object]:
    """One session's numbers; ``kind`` is ``"start"`` or ``"end"``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    marks: Dict[str, float] = {}
    devices = []
    first_event = np.inf
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for ln in plane.lines:
                if ln.name != OP_LINE:
                    continue
                ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in ln.events]
                if ev:
                    devices.append(ev)
                    first_event = min(first_event, min(x[0] for x in ev))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    first_event = min(first_event, e.start_ns)
                    if e.name in MARKERS:
                        marks[e.name] = e.start_ns
                    else:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    if not devices:
        raise RuntimeError(f"trace {path}: no {OP_LINE!r} line on a TPU")
    if kind == "start":
        lo = marks["sweep_start"]
        hi = max(max(x[1] for x in ev) for ev in devices)
    else:
        lo, hi = first_event, marks["sweep_end"]
    first_op = min(min(x[0] for x in ev) for ev in devices)
    last_op = max(max(x[1] for x in ev) for ev in devices)
    # the part of the session inside the program
    p_lo, p_hi = max(lo, first_op), min(hi, last_op)
    busy, seen, dark, ops, gaps = [], [], [], {}, []
    for ev in devices:
        leaf = leaves(ev)
        blind = bodiless(ev, leaf)
        for s, e, n in (ev[k] for k in np.flatnonzero(leaf)):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                k = op_name(n)
                ops[k] = ops.get(k, 0.0) + d / len(devices)
        cut = [ev[k] for k in np.flatnonzero(blind)]
        cut = _union(np.asarray([x[0] for x in cut], np.float64),
                     np.asarray([x[1] for x in cut], np.float64), p_lo, p_hi)
        run = [ev[k] for k in np.flatnonzero(leaf & ~blind)]
        starts = np.asarray([x[0] for x in run], np.float64)
        ends = np.asarray([x[1] for x in run], np.float64)
        busy.append(_length(_union(starts, ends, p_lo, p_hi)))
        dark.append(_length(cut))
        seen.append(p_hi - p_lo - dark[-1])
        edges = np.concatenate([[lo], _union(
            np.concatenate([starts, cut[:, 0]]),
            np.concatenate([ends, cut[:, 1]]), lo, hi).ravel(),
            [hi]]).reshape(-1, 2)
        gaps += [(float(s), float(e)) for s, e in edges if e > s]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2.0
        where = ("before the program" if mid < first_op else
                 "after the program" if mid > last_op else
                 "inside the program")
        named.append([f"{kind} window, {where}: {_host_at(mid, host)}",
                      (e - s) / len(devices)])
    n = len(devices)
    out = {"session_s": (hi - lo) / 1e9,
           "program_seen_s": sum(seen) / n / 1e9,
           "program_busy_s": sum(busy) / n / 1e9,
           "unseen_s": sum(dark) / n / 1e9,
           "ops": {k: v / 1e9 for k, v in ops.items()},
           "gaps": [[g, d / 1e9] for g, d in named]}
    if kind == "start":
        out["front_s"] = (first_op - lo) / 1e9
    else:
        out["back_s"] = (hi - last_op) / 1e9
    return out


def combine(start: dict, end: dict, sweep_s: float) -> Dict[str, object]:
    """One sweep's numbers from the two sessions.

    ``sweep_s`` is a sweep's time on the host clock.  The program's span
    is that less the host time before its first op (``front_s``) and
    after its last (``back_s``).  Inside the program the device's busy
    share is the one the two sessions saw there, leaving out what
    control-flow ops with no recorded body span; outside it the device is
    idle.  So ``busy_s`` is the span times that share, and ``window_s``
    is the sweep."""
    seen = start["program_seen_s"] + end["program_seen_s"]
    if not seen > 0:
        raise RuntimeError("the traced sessions saw no op inside the "
                           "program")
    share = (start["program_busy_s"] + end["program_busy_s"]) / seen
    span = sweep_s - start["front_s"] - end["back_s"]
    ops: Dict[str, float] = dict(start["ops"])
    for k, v in end["ops"].items():
        ops[k] = ops.get(k, 0.0) + v
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(start["gaps"] + end["gaps"], key=lambda g: -g[1])[:TOP]
    return {
        "window_s": sweep_s,
        "busy_s": span * share,
        "span_s": span,
        "front_s": start["front_s"],
        "back_s": end["back_s"],
        "program_seen_s": seen,
        "unseen_s": start["unseen_s"] + end["unseen_s"],
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": gaps},
    }
