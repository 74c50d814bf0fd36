"""Step driver: merge per-core op streams by issue time and run the scan.

One scan step = one trace op of the core whose next op *issues*
earliest (core clock + compute gap; fence semantics: a core blocks on
its persists and PM reads, so its clock only advances when its op
completes).  Merging on issue time rather than bare clocks makes the
global op order well-defined even under wildly heterogeneous gaps —
the property the crash model and the differential conformance harness
(tests/_crash_driver.py) rest on.  Padded steps after stream
exhaustion are provable no-ops, which lets callers pad the scan length
to shared buckets without changing any result.

Two step-count optimizations ride on that no-op property:

  * **macro-stepping** (``engine.macro``): when the trace-time run plan
    (``mlen``) marks an eligible homogeneous window at the selected
    core's cursor, the step executes up to ``MACRO_KMAX`` ops at once
    behind a traced guard conjunction, falling back to the
    slot-at-a-time handlers on guard failure — bit-exact either way.
    The replay sits behind one **grid-wide gate**: ``macro.macro_gate``
    proves from the window's head alone (a lower bound on its last
    issue time) that a cell cannot commit, the step ORs that over every
    cell of the grid (``lax.psum`` over the front-end's named ``vmap``
    axes, which makes the predicate unbatched) and a ``lax.cond`` skips
    the replay when no cell can commit, returning the abort vector the
    replay would have given.  Epoch-scheduled grids keep the full
    replay (their ``epoch_boundary`` attribution needs the replay's
    last issue time); the choice is static, from the ``sc`` keys;
  * **chunked early exit**: the scan runs in ``CHUNK``-step segments
    under a ``while_loop`` that stops at the first segment boundary
    where every core has drained its stream, so bucket-padded
    ``n_steps`` costs nothing once the real work (shortened further by
    macro-steps) is done.  Exactly ``n_steps`` steps are executed in
    the worst case — never more — so short-scan callers see the old
    semantics unchanged.

Crash semantics (Section V-D4): ``sc["crash_at"]`` is a traced scalar;
an op whose issue time exceeds it becomes a no-op (the machine is off),
and after the scan a recovery pass (``handlers.recovery_snapshot``)
computes the durable-version vector and the drain-all cost over the
surviving Dirty/Drain PBEs.

``scan_cell`` is the unjitted single-cell program; the front-ends in
``engine.grid`` wrap it in ``jax.jit`` (single cell) or
``jit(vmap(vmap(...)))`` (full trace x config grid).  Each trace of
``scan_cell`` — i.e. each XLA program built — ticks
``spans.compile_count()``, which backs the one-compilation acceptance
tests and the benchmark's ``window_compiles`` check.
``return_state=True`` traces (the padding-invariant tests'
state-introspection path) are excluded from the counter: they are
test-only retraces of an already-counted program shape, and counting
them double-billed suites that mix both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import spans
from repro.core.engine import timebase as tb
from repro.core.engine.chain import grid_any
from repro.core.engine.handlers import (ALL_SCHEMES, HANDLERS, StepCtx,
                                        recovery_snapshot)
from repro.core.engine.macro import (MACRO_ABORT_REASONS, macro_gate,
                                    macro_step, macro_window)
from repro.core.engine.state import EPOCH_KEYS, MachineState, init_state
from repro.core.params import MACRO_KMAX, Op

# Steps per inner scan segment of the chunked driver.  Segment
# boundaries only ever skip provable no-op steps (every core past its
# stream end), so results are invariant to this constant; it trades
# while_loop trip overhead against wasted post-exhaustion steps.
CHUNK = 128

# an issue time at or above this is the "no op left" sentinel
NO_OP = tb.bits(0.5e30)


def resolve_epoch_sc(sc, t_issue):
    """Select the active epoch's operand rows at an op's issue time.

    Grids carrying a schedule axis stack the :data:`EPOCH_KEYS` rows of
    ``sc`` with a leading ``(E,)`` epoch dimension plus one shared
    ``(E - 1,)`` ``epoch_bounds`` vector (``state.scalars_from_config``).
    The active epoch is ``#{b : b <= t_issue}`` — the boundary instant
    belongs to the *new* epoch, mirroring the crash gate's
    ``t_issue <= crash_at`` convention — and unused boundary slots are
    padded with ``INF``, which can never be ``<=`` a finite issue time.

    Returns ``(sc_op, next_bound)``: an sc view whose scheduled keys are
    indexed down to the old per-epoch shapes (so the handlers, policy,
    chain, fabric and macro layers consume them verbatim), and the next
    boundary strictly after ``t_issue`` (``INF`` in the last epoch) for
    the macro window's epoch-consistency guard.  The branch is decided
    Python-statically on dict membership: single-epoch grids lower the
    flat dict and return it unchanged with ``next_bound=None``, keeping
    their XLA program byte-identical to a schedule-free engine.
    """
    if "epoch_bounds" not in sc:
        return sc, None
    eb = sc["epoch_bounds"]
    ep = jnp.sum(tb.le(eb, t_issue).astype(jnp.int32))
    sc_op = {k: (v[ep] if k in EPOCH_KEYS else v)
             for k, v in sc.items() if k != "epoch_bounds"}
    next_bound = tb.min(jnp.where(tb.gt(eb, t_issue), eb, tb.INF))
    return sc_op, next_bound


def scan_cell(ops, addrs, gaps, lengths, scheme, sc, *,
              max_pbe: int, n_steps: int, pm_banks: int, n_track: int = 0,
              n_tenants_max: int = 1, n_deep_max: int = 0,
              n_leaves_max: int = 1,
              mlen=None, macro: bool = False,
              axis_names: tuple = (), schemes: tuple = ALL_SCHEMES,
              return_state: bool = False):
    """Simulate one (trace, config) cell.

    Returns ``(runtime, stats, durable_ver, n_recovered, recovery_ns,
    recovered_per_tenant, hop_stats, recovered_per_hop,
    recovered_per_leaf, macro_ops, macro_aborts, segments,
    gate_steps, time_ops, chain_legs)``, plus the final
    :class:`MachineState` when ``return_state`` is set (used by the
    padding-invariant tests).  ``scheme`` and every entry of ``sc`` are
    traced scalars; only array shapes (core count C, ``max_pbe``,
    ``pm_banks``, ``n_steps``, ``n_track``, ``n_tenants_max``,
    ``n_deep_max``, ``n_leaves_max``) are static.  ``n_deep_max`` is
    the deep-hop row count of the switch chain (grid max depth minus
    one); 0 skips the chain code entirely at trace time, so depth-1
    grids stay byte-identical to the pre-chain engine.  ``n_leaves_max``
    plays the same role for the fan-out fabric axis (``engine.fabric``):
    1 keeps the per-leaf PBC column empty and skips every fabric branch
    at trace time; ``recovered_per_leaf`` then degenerates to a single
    aggregate cell.  ``macro_aborts`` is the per-reason count of live
    macro candidates that failed to commit
    (:data:`~repro.core.engine.macro.MACRO_ABORT_REASONS` order, all
    zero when ``macro`` is off).  ``segments`` is the number of
    ``CHUNK``-step segments the early-exit loop ran for this cell: under
    ``vmap`` each cell's count stops when its own streams are drained,
    while the loop runs on for the grid's slowest cell.  ``gate_steps``
    counts the steps on which the macro gate opened (the replay ran):
    every cell of a grid sees the same gate, so the slowest cell's
    count is the grid's (0 when ``macro`` is off).  ``chain_legs``
    counts, the same way, the steps on which each switch-chain leg ran
    (victim, policy drain; ``chain.gated_chain``): a (2,) vector, or
    (0,) in a program with no deep row, which has no leg.  ``runtime``
    and ``recovery_ns`` are time words (``engine.timebase``); ``time_ops``
    is the number of time operations in one traced step, a constant of
    the program.

    ``axis_names`` (static) names the ``vmap`` axes the caller maps this
    cell over, so the macro gate and the chain-leg gates can reduce over
    the whole grid; ``()`` (one cell, no batch axes) makes each a
    per-cell branch.  ``schemes`` (static) are the scheme ids of the
    grid's configs: a handler leg no cell takes is left out of the
    program (``handlers.by_scheme``).

    ``macro=True`` (static) enables the macro-stepping fast path;
    ``mlen`` is the (C, L) int8 run plan from
    ``core.traces.plan_runs``.  The caller must then pad the trace
    axis L by at least ``MACRO_KMAX`` slots past the longest stream
    (the grid stacker does) so the window slice never clamps.
    ``macro_ops`` counts the trace slots executed via macro-steps
    (0 when disabled) — the ``macro_hit_rate`` numerator.

    Tenancy: ``sc["n_tenants"]`` (traced) partitions the *live* cores
    into contiguous balanced groups — core ``c`` belongs to tenant
    ``floor(c * T / n_live)`` — that share the PB slots, the PBC FIFO
    and the PM banks but keep independent barriers and stats rows
    (``core.traces.tenant_ids`` is the numpy twin of this mapping).
    """
    if not return_state:
        spans.count_compile()
    use_macro = bool(macro) and mlen is not None
    C = ops.shape[0]
    slot_ids = jnp.arange(max_pbe)
    slot_active = slot_ids < sc["n_pbe"].astype(jnp.int32)
    # Cores with a non-empty stream participate in barriers (padded cores
    # from stacked grids have zero-length streams and never arrive).
    n_live = jnp.sum((lengths > 0).astype(jnp.int32))
    core_ids = jnp.arange(C)
    # Per-core tenant ids: balanced contiguous partition of the live
    # cores; padded cores get a clipped id but never issue ops, never
    # arrive at barriers and never touch a stats row.
    t_int = jnp.maximum(sc["n_tenants"].astype(jnp.int32), 1)
    tids = jnp.clip((core_ids * t_int) // jnp.maximum(n_live, 1), 0,
                    jnp.minimum(t_int, n_tenants_max) - 1)
    live_per_tenant = jnp.zeros((n_tenants_max,), jnp.int32).at[tids].add(
        (lengths > 0).astype(jnp.int32))
    # per-step invariant: the gaps enter as float32, so convert them to
    # time words once instead of on every step
    gaps_t = tb.from_f32(gaps)
    step_ops = []
    n_legs = 2 if n_deep_max > 0 else 0

    def step(carry, _):
        ops0 = tb.op_count()
        st, mops, maborts, gsteps, legs = carry
        active = st.ptr < lengths
        idx = jnp.minimum(st.ptr, jnp.maximum(lengths - 1, 0))
        next_gap = gaps_t[core_ids, idx]
        # blocked cores wait at a barrier and cannot be selected; all
        # others compete on the *issue* time of their next op
        tsel = jnp.where(active & ~st.blocked, tb.add(st.clock, next_gap),
                         tb.INF)
        c = tb.argmin(tsel)
        # padded steps after exhaustion (or a barrier mismatch) are no-ops
        valid = jnp.any(active) & tb.lt(tsel[c], NO_OP)
        i = idx[c]
        t_issue = jnp.where(valid, tsel[c], st.clock[c])
        # ops issuing after the power loss never happen (machine is off)
        live = valid & tb.le(t_issue, sc["crash_at"])
        op = jnp.where(live, ops[c, i], int(Op.COMPUTE))
        t = jnp.where(live, t_issue, st.clock[c])
        # epoched schedules: every layer below sees the operand rows of
        # the epoch active at this op's *issue* time
        sc_op, next_bound = resolve_epoch_sc(sc, t_issue)

        tid_c = tids[c]
        n_live_t = live_per_tenant[tid_c]
        ctx = StepCtx(c=c, op=op, t=t, addr=addrs[c, i], scheme=scheme,
                      sc=sc_op, slot_ids=slot_ids, slot_active=slot_active,
                      tenant=tid_c, tids=tids, n_live_t=n_live_t,
                      n_banks=pm_banks, n_track=n_track, schemes=schemes,
                      axis_names=axis_names)
        branches = [lambda s, h=h: h(ctx, s) for h in HANDLERS]
        st2, wants = jax.lax.switch(jnp.clip(op, 0, 5), branches, st)
        if n_legs:
            # the legs ran where some cell of the grid wanted them
            legs = legs + grid_any(wants, axis_names).astype(jnp.int32)

        if use_macro:
            win = macro_window(ctx, gaps_t, lengths, mlen, tsel, valid,
                               live, i, kmax=MACRO_KMAX)

            def replay(s):
                st_m, took, k_m, ab_vec = macro_step(
                    ctx, st, ops, addrs, win, valid, live, t_issue, i,
                    kmax=MACRO_KMAX, next_epoch_bound=next_bound)
                s = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(took, a, b), st_m, s)
                return s, took, jnp.where(took, k_m, 1), ab_vec

            if next_bound is None:
                want, ab_skip = macro_gate(win, sc_op, t_issue, valid, live)
                gate = grid_any(want, axis_names)
                st2, took, adv, ab_vec = jax.lax.cond(
                    gate, replay,
                    lambda s: (s, jnp.asarray(False),
                               jnp.asarray(1, jnp.int32), ab_skip),
                    st2)
            else:
                gate = jnp.asarray(True)
                st2, took, adv, ab_vec = replay(st2)
            mops = mops + jnp.where(took, adv, 0)
            maborts = maborts + ab_vec
            gsteps = gsteps + gate.astype(jnp.int32)
        else:
            took = jnp.asarray(False)
            adv = 1

        # barriers synchronize only within a tenant (independent hosts);
        # macro windows contain no barriers, so the bookkeeping below is
        # an exact identity whenever the macro path was taken
        is_bar = live & (op == int(Op.BARRIER))
        last = is_bar & ((st.bcount[tid_c] + 1) >= n_live_t)
        blocked = jnp.where(last & (tids == tid_c), False,
                            jnp.where(is_bar, st.blocked.at[c].set(True),
                                      st.blocked))
        bcount = st.bcount.at[tid_c].set(
            jnp.where(last, 0,
                      st.bcount[tid_c] + jnp.where(is_bar, 1, 0)))
        # crashed ops still consume their cursor slot (the stream drains
        # as no-ops, so post-crash cores cannot starve live ones) and
        # still advance the core clock to their issue time: gaps are
        # relative, so a frozen clock would let a *later* op's issue
        # time collapse back below the crash point and wrongly execute
        # (a dead-run macro-step already advanced the clock itself)
        ptr = st2.ptr.at[c].add(jnp.where(valid, adv, 0))
        clock = st2.clock.at[c].set(
            jnp.where(valid & ~live & ~took, t_issue, st2.clock[c]))
        step_ops.append(tb.op_count() - ops0)
        return (st2._replace(clock=clock, ptr=ptr, blocked=blocked,
                             bcount=bcount), mops, maborts, gsteps,
                legs), None

    def segment(carry, length):
        return jax.lax.scan(step, carry, None, length=length)[0]

    carry = (init_state(C, max_pbe, pm_banks, n_track, n_tenants_max,
                        n_deep_max, n_leaves_max),
             jnp.zeros((), jnp.int32),
             jnp.zeros((len(MACRO_ABORT_REASONS),), jnp.int32),
             jnp.zeros((), jnp.int32),
             jnp.zeros((n_legs,), jnp.int32))
    n_full, n_tail = divmod(n_steps, CHUNK)
    segments = jnp.zeros((), jnp.int32)
    if n_full > 0:
        def more_work(loop):
            k, (st, *_) = loop
            return (k < n_full) & jnp.any(st.ptr < lengths)

        def run_segment(loop):
            k, seg_carry = loop
            return k + 1, segment(seg_carry, CHUNK)

        segments, carry = jax.lax.while_loop(
            more_work, run_segment, (jnp.asarray(0, jnp.int32), carry))
    if n_tail > 0:
        carry = segment(carry, n_tail)
    final, mops, maborts, gsteps, legs = carry
    # a crashed run ends at the power loss: dead cores advanced their
    # clocks through never-executed ops, so cap at the crash instant
    runtime = tb.max(jnp.where(tb.lt(final.clock, NO_OP),
                               tb.minimum(final.clock, sc["crash_at"]),
                               tb.ZERO))
    (durable_ver, n_recov, recov_ns, recov_t, recov_h,
     recov_l) = recovery_snapshot(
        final, scheme, sc, slot_active, pm_banks, n_track, schemes)
    # the time operations of one grid step, counted when it was traced
    time_ops = jnp.asarray(step_ops[0] if step_ops else 0, jnp.int32)
    out = (runtime, final.stats, durable_ver, n_recov, recov_ns, recov_t,
           final.hop_stats, recov_h, recov_l, mops, maborts, segments,
           gsteps, time_ops, legs)
    return out + (final,) if return_state else out
