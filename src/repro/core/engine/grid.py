"""Batched (trace x config x scheme) front-end and compatibility wrappers.

``simulate_grid`` runs the paper's whole evaluation grid as one XLA
program: traces are padded into shared (C, L) buckets and stacked on a
leading axis, configs are lowered to stacked latency/policy scalars plus
a traced scheme id, and the cell program (``engine.step.scan_cell``) is
nested-``vmap``-ed over the config axis then the trace axis.  Mixed
schemes in one grid are first-class — the scheme is traced, not a
compile-time static.

``simulate_cells`` is the flat variant: one result per (trace, config)
*pair* under a single vmap axis, for sweeps that never needed the full
cross product (half the cells of an anchored two-trace sweep).

The stacker pads the op axis by ``MACRO_KMAX`` slots so the engine's
macro-step window slice never clamps, and the macro-run pre-pass
(``core.traces.plan_runs``) plans each stacked trace; ``macro=False``
opts a call out (the differential tests' control column).  Input
buffers are donated to the jitted programs — they are freshly staged
per call, so XLA may reuse them for the scan carry instead of
allocating.

Each call is one ``engine.call`` span with a span per stage
(``engine.stack``, ``engine.plan_runs``, ``engine.lower_configs``,
``engine.put``, ``engine.scan``, ``engine.fetch``, ``engine.unpack``)
and one counter record (``engine.spans``).

``simulate`` and ``simulate_sweep`` are thin compatibility wrappers over
the same cell program, returning identical ``SimResult`` objects to the
original monolithic simulator.
"""
from __future__ import annotations

import functools
import warnings
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import spans
from repro.core.engine import timebase as tb
from repro.core.engine.handlers import ALL_SCHEMES
from repro.core.engine.macro import MACRO_ABORT_REASONS
from repro.core.engine.state import (SimResult, lower_scalars,
                                     result_from_stats, scalars_from_config)
from repro.core.engine.step import CHUNK, scan_cell
from repro.core.params import MACRO_KMAX, PCSConfig
from repro.core.traces import Trace, plan_runs

_BUCKET = 16384


def _pad_up(n: int, b: int = _BUCKET) -> int:
    return ((max(n, 1) + b - 1) // b) * b


def _stack_traces(traces: Sequence[Trace], bucket: int):
    """Pad traces into one shared (C, L) bucket and stack them.

    Padded cores get zero-length streams (they never issue an op and
    never count toward barriers); padded steps are no-ops, so sharing
    one bucket across workloads of different sizes changes no result.
    The op axis carries ``MACRO_KMAX`` slots of slack past the longest
    stream (inside the bucket rounding) so the macro-step window slice
    never clamps.
    """
    C = max(t.ops.shape[0] for t in traces)
    L = _pad_up(max(t.ops.shape[1] for t in traces) + MACRO_KMAX, bucket)
    T = len(traces)
    ops = np.zeros((T, C, L), np.int32)
    addrs = np.zeros((T, C, L), np.int32)
    gaps = np.zeros((T, C, L), np.float32)
    lengths = np.zeros((T, C), np.int32)
    for k, t in enumerate(traces):
        c, l = t.ops.shape
        ops[k, :c, :l] = t.ops
        addrs[k, :c, :l] = t.addrs
        gaps[k, :c, :l] = t.gaps
        lengths[k, :c] = t.lengths
    n_steps = _pad_up(max(t.total_ops for t in traces), bucket)
    return ops, addrs, gaps, lengths, n_steps


def _plan_runs(ops, addrs, gaps):
    """The macro-run plan of each stacked trace (``plan_runs``)."""
    return np.stack([plan_runs(ops[k], addrs[k], gaps[k], MACRO_KMAX)
                     for k in range(len(ops))])


def _stack_configs(configs: Sequence[PCSConfig], max_pbe: int | None,
                   n_tenants_max: int):
    # the static PBE bound must cover every hop of every chain (deep
    # rows share the slot axis with hop 1)
    max_pbe = max_pbe or max(c.max_hop_pbe for c in configs)
    if any(c.max_hop_pbe > max_pbe for c in configs):
        raise ValueError("n_pbe exceeds max_pbe")
    banks = {c.pm_banks for c in configs}
    if len(banks) != 1:
        raise ValueError("grid configs must share pm_banks (array shape)")
    # deep-hop rows are a static shape; only PB-bearing configs need
    # them (a deep NOPB chain is pure wire), and a depth-<=1-only grid
    # lowers to the chain-free program (n_deep == 0)
    n_deep = max((len(c.hop_pbes) - 1 for c in configs), default=0)
    n_deep = max(n_deep, 0)
    # the fabric leaf axis is a static shape too: 1 (no fabric cell in
    # the grid) keeps the per-leaf PBC column empty and the whole fabric
    # layer out of the traced program
    n_leaves = max((c.fabric.n_leaves if c.fabric is not None else 1
                    for c in configs), default=1)
    # the epoch axis is a static shape shared grid-wide: a schedule-free
    # grid lowers the flat single-epoch dict (byte-identical program),
    # while any scheduled config promotes every config's EPOCH_KEYS rows
    # to the grid-wide epoch bound (static configs broadcast their one
    # row; short schedules clamp to their last epoch)
    n_epochs = max((c.n_epochs for c in configs), default=1)
    # policy lowering pads its per-tenant vectors to the grid-wide
    # n_tenants_max, so mixed tenant counts / policies stack into one
    # (K,) or (K, T) array per scalar and share the program
    rows = [scalars_from_config(c, n_tenants_max, n_deep, n_leaves,
                                n_epochs_max=n_epochs)
            for c in configs]
    sc = lower_scalars({k: np.asarray([r[k] for r in rows], np.float64)
                        for k in rows[0]})
    schemes = np.asarray([int(c.scheme) for c in configs], np.int32)
    return sc, schemes, max_pbe, banks.pop(), n_deep, n_leaves


_STATICS = ("max_pbe", "n_steps", "pm_banks", "n_track", "n_tenants_max",
            "n_deep_max", "n_leaves_max", "macro", "schemes")
_DONATED = ("ops", "addrs", "gaps", "mlen")


@functools.partial(jax.jit, static_argnames=_STATICS,
                   donate_argnames=_DONATED)
def _run_cell(ops, addrs, gaps, lengths, mlen, scheme, sc, *,
              max_pbe, n_steps, pm_banks, n_track, n_tenants_max,
              n_deep_max, n_leaves_max, macro, schemes=ALL_SCHEMES):
    # single-cell program: no batch axes, so `lax.switch` and the macro
    # gate's `lax.cond` lower to real branches instead of vmap's
    # execute-all-and-select
    return scan_cell(ops, addrs, gaps, lengths, scheme, sc,
                     max_pbe=max_pbe, n_steps=n_steps, pm_banks=pm_banks,
                     n_track=n_track, n_tenants_max=n_tenants_max,
                     n_deep_max=n_deep_max, n_leaves_max=n_leaves_max,
                     mlen=mlen, macro=macro, schemes=schemes)


def _cell_fn(max_pbe, n_steps, pm_banks, n_track, n_tenants_max,
             n_deep_max, n_leaves_max, macro, axis_names, schemes):
    # ``axis_names``: the vmap axes the cell runs under, over which the
    # macro gate reduces (engine.step)
    def cell(ops, addrs, gaps, lengths, mlen, scheme, sc):
        return scan_cell(ops, addrs, gaps, lengths, scheme, sc,
                         max_pbe=max_pbe, n_steps=n_steps,
                         pm_banks=pm_banks, n_track=n_track,
                         n_tenants_max=n_tenants_max,
                         n_deep_max=n_deep_max, n_leaves_max=n_leaves_max,
                         mlen=mlen, macro=macro, axis_names=axis_names,
                         schemes=schemes)
    return cell


@functools.partial(jax.jit, static_argnames=_STATICS,
                   donate_argnames=_DONATED)
def _run_grid(ops, addrs, gaps, lengths, mlen, scheme, sc, *,
              max_pbe, n_steps, pm_banks, n_track, n_tenants_max,
              n_deep_max, n_leaves_max, macro, schemes=ALL_SCHEMES):
    cell = _cell_fn(max_pbe, n_steps, pm_banks, n_track, n_tenants_max,
                    n_deep_max, n_leaves_max, macro, ("tr", "cfg"), schemes)
    over_cfg = jax.vmap(cell, in_axes=(None, None, None, None, None, 0, 0),
                        axis_name="cfg")
    over_tr = jax.vmap(over_cfg, in_axes=(0, 0, 0, 0, 0, None, None),
                       axis_name="tr")
    return over_tr(ops, addrs, gaps, lengths, mlen, scheme, sc)


@functools.partial(jax.jit, static_argnames=_STATICS,
                   donate_argnames=_DONATED)
def _run_cells(ops, addrs, gaps, lengths, mlen, scheme, sc, *,
               max_pbe, n_steps, pm_banks, n_track, n_tenants_max,
               n_deep_max, n_leaves_max, macro, schemes=ALL_SCHEMES):
    # flat pairing: one shared batch axis over traces AND configs
    cell = _cell_fn(max_pbe, n_steps, pm_banks, n_track, n_tenants_max,
                    n_deep_max, n_leaves_max, macro, ("cell",), schemes)
    return jax.vmap(cell, axis_name="cell")(ops, addrs, gaps, lengths, mlen,
                                            scheme, sc)


def _execute(program, buffers, configs: Sequence[PCSConfig], max_pbe,
             n_steps: int, track_addrs: int, macro: bool, single: bool):
    """Lower the configs, stage the inputs, run one program and fetch
    its outputs to the host (with a leading trace and config axis of 1
    each for the ``single`` cell)."""
    with spans.span("engine.lower_configs"):
        # static per-tenant stats row count; every config's rows beyond
        # its own n_tenants stay zero, so mixed tenant counts share one
        # program
        n_tenants_max = max(c.n_tenants for c in configs)
        sc_np, schemes, max_pbe, pm_banks, n_deep, n_leaves = (
            _stack_configs(configs, max_pbe, n_tenants_max))
    first = (lambda a: a[0]) if single else (lambda a: a)
    with jax.enable_x64(True), warnings.catch_warnings():
        # donated buffers the program cannot alias (dtype/layout) emit a
        # UserWarning; donation is best-effort here
        warnings.filterwarnings("ignore", message=".*[Dd]onat")
        with spans.span("engine.put"):
            args = [jnp.asarray(first(b)) for b in buffers]
            args.append(jnp.asarray(first(schemes)))
            sc = {k: jnp.asarray(first(v)) for k, v in sc_np.items()}
        with spans.span("engine.scan"):
            out = jax.block_until_ready(program(
                *args, sc, max_pbe=max_pbe, n_steps=n_steps,
                pm_banks=pm_banks, n_track=track_addrs,
                n_tenants_max=n_tenants_max, n_deep_max=n_deep,
                n_leaves_max=n_leaves, macro=macro,
                schemes=tuple(sorted(set(schemes.tolist())))))
        with spans.span("engine.fetch"):
            out = tuple(np.asarray(o) for o in out)
    return tuple(o[None, None] for o in out) if single else out


def _count(rec: spans.Call, mops, maborts, segments, gate_steps, time_ops,
           chain_legs, traces, configs, n_steps: int, pairs: bool) -> None:
    """The call's counters from the program's telemetry outputs."""
    rec.cells = int(np.size(segments))
    rec.trace_ops = int(sum(t.total_ops for t in traces)
                        * (1 if pairs else len(configs)))
    rec.segments = np.asarray(segments, np.int64).ravel()
    # the early-exit loop runs until the grid's slowest cell is drained,
    # then the tail segment runs for every cell
    rec.steps = int(rec.segments.max()) * CHUNK + n_steps % CHUNK
    # the gate is grid-wide; the slowest cell saw every step
    rec.macro_gate_steps = int(np.max(gate_steps))
    # one count a leg, as grid-wide as the gate; none without a chain
    n_legs = np.shape(chain_legs)[-1]
    if n_legs:
        rec.chain_leg_steps = tuple(int(x) for x in np.max(
            np.reshape(chain_legs, (-1, n_legs)), axis=0))
    rec.macro_ops = int(np.sum(mops))
    rec.time_ops = int(np.max(time_ops))
    rec.abort_reasons = dict(zip(MACRO_ABORT_REASONS, (
        int(x) for x in np.sum(
            np.asarray(maborts).reshape(-1, len(MACRO_ABORT_REASONS)),
            axis=0))))


def _results_from(out, traces, configs, track_addrs, rec, n_steps,
                  pairs: bool):
    (runtimes, stats, durable_ver, n_recov, recov_ns, recov_t,
     hop_stats, recov_h, recov_l, mops, maborts, segments, gate_steps,
     time_ops, chain_legs) = out
    _count(rec, mops, maborts, segments, gate_steps, time_ops, chain_legs,
           traces, configs, n_steps, pairs)
    runtimes, recov_ns = tb.to_host(runtimes), tb.to_host(recov_ns)

    def cell(i, j, k):
        fab = configs[j].fabric
        return result_from_stats(
            float(runtimes[k]), stats[k],
            crash_at_ns=configs[j].crash_at_ns,
            recovery_entries=int(n_recov[k]),
            recovery_ns=float(recov_ns[k]),
            durable_ver=(durable_ver[k][:track_addrs].copy()
                         if track_addrs > 0 else None),
            n_tenants=configs[j].n_tenants,
            tenant_recovery=recov_t[k],
            n_hops=len(configs[j].hop_pbes),
            hop_stats=hop_stats[k],
            hop_recovery=recov_h[k],
            n_leaves=fab.n_leaves if fab is not None else 1,
            leaf_recovery=recov_l[k])
    if pairs:
        return [cell(k, k, (k,)) for k in range(len(traces))]
    return [[cell(i, j, (i, j)) for j in range(len(configs))]
            for i in range(len(traces))]


def simulate_grid(traces: Sequence[Trace], configs: Sequence[PCSConfig], *,
                  max_pbe: int | None = None,
                  bucket: int = _BUCKET,
                  track_addrs: int = 0,
                  macro: bool = True) -> List[List[SimResult]]:
    """Simulate every (trace, config) cell in one compiled program.

    Returns a ``len(traces) x len(configs)`` nested list of SimResult.
    Schemes may be mixed freely; ``pm_banks`` must agree (array shape).
    ``bucket`` controls shape-padding granularity only — results are
    invariant to it.  A config's ``crash_at_ns`` is just another stacked
    traced scalar, so crash-point sweeps share the one program.
    ``track_addrs > 0`` additionally returns, per cell, the durable
    version vector over addresses ``[0, track_addrs)`` (the differential
    harness input); it is a static array shape, so changing it recompiles.
    A config's ``n_tenants`` is a traced scalar too — a {workload x
    scheme x tenant-count} sweep shares the program; only the *max*
    tenant count (per-tenant stats rows) is a static shape.
    ``macro`` (static) toggles the guarded macro-step fast path —
    results are bit-identical either way (the crash differential pins
    this); it exists so the tests can diff the two columns.
    """
    if not traces or not configs:
        return [[] for _ in traces]
    with spans.call() as rec:
        with spans.span("engine.stack"):
            ops, addrs, gaps, lengths, n_steps = _stack_traces(traces,
                                                               bucket)
        with spans.span("engine.plan_runs"):
            mlen = _plan_runs(ops, addrs, gaps)
        # 1x1 grid: skip the vmap so the op/scheme switches keep their
        # branch semantics (~4x less work per scan step)
        single = len(traces) == 1 and len(configs) == 1
        out = _execute(_run_cell if single else _run_grid,
                       (ops, addrs, gaps, lengths, mlen), configs, max_pbe,
                       n_steps, track_addrs, macro, single)
        with spans.span("engine.unpack"):
            return _results_from(out, traces, configs, track_addrs, rec,
                                 n_steps, pairs=False)


def simulate_cells(traces: Sequence[Trace], configs: Sequence[PCSConfig], *,
                   max_pbe: int | None = None,
                   bucket: int = _BUCKET,
                   track_addrs: int = 0,
                   macro: bool = True) -> List[SimResult]:
    """Simulate paired cells: ``result[k]`` is (traces[k], configs[k]).

    The flat twin of :func:`simulate_grid` for sweeps that are not a
    cross product — e.g. a crash sweep anchored on two traces runs
    ``len(configs)`` cells instead of ``2 x len(configs)``.  One vmap
    axis, one compiled program; repeated Trace objects stack by
    reference on the host, so passing the same trace many times costs
    one pad, not many.
    """
    if not traces:
        return []
    if len(traces) != len(configs):
        raise ValueError("simulate_cells wants len(traces) == len(configs)")
    with spans.call() as rec:
        with spans.span("engine.stack"):
            # stack unique traces once, then index the stacked arrays
            # per pair
            uniq: List[Trace] = []
            index = {}
            for t in traces:
                if id(t) not in index:
                    index[id(t)] = len(uniq)
                    uniq.append(t)
            ops, addrs, gaps, lengths, n_steps = _stack_traces(uniq, bucket)
            sel = np.asarray([index[id(t)] for t in traces], np.int32)
            paired = (ops[sel], addrs[sel], gaps[sel], lengths[sel])
        with spans.span("engine.plan_runs"):
            mlen = _plan_runs(ops, addrs, gaps)[sel]
        out = _execute(_run_cells, paired + (mlen,), configs, max_pbe,
                       n_steps, track_addrs, macro, single=False)
        with spans.span("engine.unpack"):
            return _results_from(out, traces, configs, track_addrs, rec,
                                 n_steps, pairs=True)


def simulate(trace: Trace, config: PCSConfig,
             max_pbe: int | None = None, *,
             bucket: int = _BUCKET, track_addrs: int = 0,
             macro: bool = True) -> SimResult:
    """Simulate one (trace, config) pair and return aggregate metrics."""
    max_pbe = max_pbe or config.max_hop_pbe
    return simulate_grid([trace], [config], max_pbe=max_pbe,
                         bucket=bucket, track_addrs=track_addrs,
                         macro=macro)[0][0]


def simulate_sweep(trace: Trace, configs: List[PCSConfig], *,
                   bucket: int = _BUCKET) -> List[SimResult]:
    """vmap one trace over many configs (Fig. 1 / Fig. 8).

    All latency scalars *and the scheme id* are batched; the padded PBE
    capacity is the only shared static, so the whole sweep — including
    mixed-scheme sweeps — is a single compiled program.
    """
    if not configs:
        return []
    return simulate_grid([trace], configs, bucket=bucket)[0]
