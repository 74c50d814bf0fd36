"""Macro-stepping: execute a homogeneous op run as one guarded step.

The trace-time pre-pass (``core.traces.plan_runs``) marks, per trace
slot, the length of the longest *statically eligible* run starting
there: consecutive PM_READ / PERSIST ops of one core with non-negative
gaps and pairwise-distinct addresses (when a persist is involved).  The
step driver (``engine.step``) consults that plan and hands eligible
windows to :func:`macro_step`, which replays up to ``MACRO_KMAX`` ops of
the selected core as an exact mini-interpreter.  Its decisions are the
handlers' own functions, called on the same operands: bank and PBC
service (``channels``), lazy frees, Empty-slot allocation under the
quota gate, the PB drain, the PB_RF drain-down count and the SLO
tightening (``policy``), and the per-persist stats row
(``state.persist_stats``).  The leg arithmetic it writes out itself (the
read miss and the two persist acks) keeps the handlers' form and order,
so a committed macro-step is bit-identical to the handler path, not an
approximation of it; ``tests/test_macro.py``, ``tests/test_macro_gate.py``
and the crash differential pin that.

Commit-or-abort contract (the SyphonArch trace-speculation shape —
record a hot linear path, guard it, fall back on guard failure):

  * while replaying, the mini-interpreter accumulates a traced guard
    conjunction; any op that would leave the straight-line fast path —
    a PB lookup hit, a coalesce opportunity, a missing Empty slot, a
    PB_RF drain-down that would fire, an op issuing past the crash
    point, a deep (>= 2 switch) chain cell — clears the guard;
  * cross-core interleaving is guarded globally: every other core's
    next issue time must lie strictly after the window's last issue
    time, so the engine's argmin selection provably picks this core
    for the whole window;
  * on guard failure the whole candidate state is discarded (commit-
    or-abort, never a partial prefix) and the driver's slot-at-a-time
    result stands; the run re-enters macro planning at the next step.

A second, independent fast path collapses *dead runs*: once a core's
next op issues after the crash point, its remaining stream drains as
provable no-ops that only advance its cursor and clock — those are
collapsed ``MACRO_KMAX`` at a time with no guard beyond gap
non-negativity (dead ops touch no shared state, so they commute with
every other core's ops bit-exactly).

The replay is an 8-slot serial loop, and under the grid's ``vmap`` it
cannot be branched around per cell.  :func:`macro_gate` is a cheap,
exact pre-filter the step driver evaluates first, from the window's
head alone (:func:`macro_window`): it says when a window *cannot*
commit, so the driver skips the replay on grid steps where no cell of
the grid can (``engine.step``).  It rests on a lower bound on the
window's last issue time ``t_last`` that needs no replay:

    t_last >= t_issue + sum_{1 <= j < k_live} (gap_j + lat_lo)

where ``lat_lo`` is the least latency any replayed op can take.  Proof:
op ``j`` issues at ``t_j = clk_j + gap_j``, and the mini-interpreter
sets ``clk_{j+1}`` to the op's completion — a PM read's response
``max(bank_busy, t_j + ow_cpu_pm) + nvm_read + ow_cpu_pm``, a NoPB
persist's ack ``max(bank_busy, t_j + ow_cpu_pm) + nvm_write +
ow_cpu_pm``, a buffered persist's ack ``max(pbc_busy, t_j + ow_cpu_sw1)
+ pbc_proc_ns + tag_ns + data_ns + ow_cpu_sw1`` — each at least ``t_j``
plus the smallest of ``2 ow_cpu_pm + min(nvm_read, nvm_write)`` and
``2 ow_cpu_sw1 + pbc_proc_ns + tag_ns + data_ns`` (= ``lat_lo``; at
least two one-way link crossings), since ``max(b, x) >= x``.  Induction
from ``t_0 = t_issue`` gives the bound in exact arithmetic.  The gate
adds only *half* of ``lat_lo`` per op: the other half is slack for
rounding, which in the gate's plain float64 sum (IEEE, or the chip's
emulated float pairs) stays many orders of magnitude below ``lat_lo / 2`` (73 ns or more with
Table I's latencies) for any simulated time below ~10^12 ns.  A window is then sure to
fail the interleave guard where ``others_min <= lb``, and where
``lat_lo <= 0`` the gate proves nothing and lets every window through.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import channels, fabric, policy
from repro.core.engine import timebase as tb
from repro.core.engine.state import (DIRTY, EMPTY, H_FWD_CNT, H_FWD_SUM,
                                     S_PBCQ_SUM, S_READ_CNT, S_READ_SUM,
                                     persist_stats)
from repro.core.params import Op

# Prioritized abort attribution for live candidate windows: each live op
# at the head of a window that fails to commit counts under exactly the
# *first* failing gate, in this order.  ``window`` = no eligible >= 2-op
# run at the cursor; ``fabric`` = a multi-leaf fabric cell (the
# mini-interpreter models neither leaf scoping nor spine backpressure);
# ``deep`` = a >= 2-switch chain cell; ``epoch_boundary`` = the window
# straddles an epoch boundary of a scheduled config (the
# mini-interpreter replays every op under the head op's epoch, so a
# mid-window epoch switch must fall back to the slot-at-a-time path);
# ``interleave`` = another core issues inside the window; ``guard`` =
# the in-window traced guard conjunction cleared (PB hit, coalesce,
# drain-down fired, ...).  The vector returned by :func:`macro_step` is
# summed across steps/cells by ``engine.grid`` and surfaced via
# ``last_macro_abort_reasons()``.
MACRO_ABORT_REASONS = ("window", "fabric", "deep", "epoch_boundary",
                       "interleave", "guard")


class Window(NamedTuple):
    """The selected core's window as the step sees it before a replay."""
    w_gap: jax.Array        # (kmax,) gaps from the cursor on (time)
    k_cap: jax.Array        # slots left in the stream, at most kmax
    k_live: jax.Array       # planned run length at the cursor, <= k_cap
    cand: jax.Array         # a live op heads the window
    elig: jax.Array         # ... and the plan gives it >= 2 ops
    fab_ok: jax.Array       # not a multi-leaf fabric cell (or NoPB)
    deep_ok: jax.Array      # not a >= 2-switch chain cell (or NoPB)
    others_min: jax.Array   # earliest next issue time of the other cores


def macro_window(ctx, gaps_t, lengths, mlen, tsel, valid, live, i, *,
                 kmax: int) -> Window:
    """The window at core ``ctx.c``'s cursor ``i``, read from the run
    plan ``mlen`` and the issue times ``tsel`` without replaying it."""
    sc = ctx.sc
    c = ctx.c
    # the grid pads L by kmax slots so the slice never clamps (see
    # grid._stack_traces)
    w_gap = jax.lax.dynamic_slice(
        gaps_t, (c.astype(jnp.int32), i.astype(jnp.int32)), (1, kmax))[0]
    k_cap = jnp.clip(lengths[c] - i, 0, kmax)
    k_live = jnp.minimum(mlen[c, i].astype(jnp.int32), k_cap)
    is_nopb = ctx.scheme == 0                       # Scheme.NOPB
    cand = valid & live
    return Window(
        w_gap=w_gap, k_cap=k_cap, k_live=k_live, cand=cand,
        elig=cand & (k_live >= 2),
        # multi-leaf fabric cells scope hop-1 state to the issuing
        # tenant's leaf and may defer drains on spine backpressure —
        # neither is modelled by the mini-interpreter (a fabric forces
        # n_switches = 2, so deep_ok already aborts these; fab_ok
        # attributes the abort)
        fab_ok=is_nopb | (sc["n_leaves"] < 2.0),
        # chain cells (>= 2 switches) take the deep persist/read legs
        # the mini-interpreter does not model; their dead tails still
        # collapse
        deep_ok=is_nopb | (sc["n_switches"] < 2.0),
        # no other core may issue inside the window (strict: argmin
        # ties break by index, so equality must abort too)
        others_min=tb.min(tsel.at[c].set(tb.INF)))


def _abort_vec(win: Window, ep_ok, no_ilv, guard):
    """The one-hot ``MACRO_ABORT_REASONS`` vector of a window: each
    live candidate that fails to commit counts its first failing gate."""
    gated = win.elig & win.fab_ok & win.deep_ok
    return jnp.stack([
        win.cand & (win.k_live < 2),
        win.elig & ~win.fab_ok,
        win.elig & win.fab_ok & ~win.deep_ok,
        gated & ~ep_ok,
        gated & ep_ok & ~no_ilv,
        gated & ep_ok & no_ilv & ~guard,
    ]).astype(jnp.int32)


def macro_gate(win: Window, sc, t_issue, valid, live):
    """Whether :func:`macro_step` can commit anything at this window.

    Returns ``(want, abort_vec)``.  Where ``want`` is False,
    ``macro_step`` provably returns ``use_macro`` False and exactly
    ``abort_vec`` (module docstring: the lower bound on ``t_last``), so
    the replay may be skipped.  ``want`` holds for an eligible window
    that passes the fabric and deep gates and whose every other core
    issues after the bound ``lb``, and for a dead run of >= 2 slots.
    Not for epoch-scheduled grids: ``epoch_boundary`` comes before
    ``interleave`` in the attribution, and telling them apart needs
    ``t_last`` itself.
    """
    # a bound with slack, not a decision: plain float64 is enough
    ns = {k: tb.to_f64(sc[k]) for k in ("ow_cpu_pm", "nvm_read",
                                         "nvm_write", "ow_cpu_sw1",
                                         "pbc_proc_ns", "tag_ns",
                                         "data_ns")}
    lat_lo = jnp.minimum(
        2.0 * ns["ow_cpu_pm"] + jnp.minimum(ns["nvm_read"], ns["nvm_write"]),
        2.0 * ns["ow_cpu_sw1"] + ns["pbc_proc_ns"] + ns["tag_ns"]
        + ns["data_ns"])
    floor = 0.5 * lat_lo
    j = jnp.arange(win.w_gap.shape[0])
    lb = tb.to_f64(t_issue) + jnp.sum(
        jnp.where((j >= 1) & (j < win.k_live),
                  tb.to_f64(win.w_gap) + floor, 0.0))
    may_fit = (floor <= 0.0) | (tb.to_f64(win.others_min) > lb)
    want = ((win.elig & win.fab_ok & win.deep_ok & may_fit)
            | (valid & ~live & (win.k_cap >= 2)))
    # skipped: no epoch gate, and every window the fabric and deep
    # gates pass failed the interleave guard
    true, false = jnp.asarray(True), jnp.asarray(False)
    return want, _abort_vec(win, ep_ok=true, no_ilv=false, guard=true)


def macro_step(ctx, st, ops, addrs, win: Window, valid, live, t_issue, i,
               *, kmax: int, next_epoch_bound=None):
    """Candidate macro execution of up to ``kmax`` ops of core ``ctx.c``.

    Returns ``(st_macro, use_macro, k_adv, abort_vec)``: the candidate
    state (only meaningful where ``use_macro`` holds), whether either
    macro path (live window or dead run) committed, how many trace
    slots it consumed, and the one-hot ``MACRO_ABORT_REASONS`` vector
    (all-zero when the window committed or no live candidate existed).
    The caller selects ``st_macro`` over the slot-step result and
    advances the cursor by ``k_adv`` when ``use_macro`` is set.
    ``win`` is :func:`macro_window` at the head op.

    ``next_epoch_bound`` is the first epoch boundary strictly after the
    head op's issue time in an epoch-scheduled grid (``INF`` inside the
    last epoch), or ``None`` for single-epoch grids.  ``ctx.sc`` is the
    epoch-resolved view at the head op's issue time; the window commits
    only when its last issue time still precedes the boundary, i.e.
    every replayed op provably shares the head op's epoch (the
    ``epoch_boundary`` abort reason counts the windows this rejects).
    Dead runs are exempt: dead ops touch no policy state, so an epoch
    switch inside a collapsed post-crash stream changes nothing.
    """
    sc = ctx.sc
    c = ctx.c
    crash = sc["crash_at"]
    A = st.aver.shape[0]
    T = st.stats.shape[0]

    # window data; the grid pads L by kmax slots so the slice never
    # clamps (see grid._stack_traces)
    c32 = c.astype(jnp.int32)
    i32 = i.astype(jnp.int32)
    w_ops = jax.lax.dynamic_slice(ops, (c32, i32), (1, kmax))[0]
    w_addr = jax.lax.dynamic_slice(addrs, (c32, i32), (1, kmax))[0]
    w_gap, k_cap, k_live = win.w_gap, win.k_cap, win.k_live

    # ---------------- dead-run collapse (post-crash stream drain) ------
    # Each dead step sets clock[c] to its issue time and bumps the
    # cursor; the sequential masked adds reproduce the step-at-a-time
    # rounding order exactly.  Monotone issue times (gaps >= 0) make
    # first-dead imply all-dead.
    gaps_ok = jnp.all(tb.ge(w_gap, tb.ZERO))
    clk_dead, _ = jax.lax.scan(
        lambda ck, jg: (jnp.where(jg[0] < k_cap, tb.add(ck, jg[1]), ck),
                        None),
        st.clock[c], (jnp.arange(kmax), w_gap))
    dead_ok = valid & ~live & gaps_ok & (k_cap >= 2)
    st_dead = st._replace(clock=st.clock.at[c].set(clk_dead))

    # ---------------- live window (exact mini-interpreter) -------------
    is_nopb = ctx.scheme == 0                       # Scheme.NOPB
    is_rf = ctx.scheme == 2                         # Scheme.PB_RF
    pb_like = ~is_nopb
    # per-leaf PBC clocks: in a grid carrying the fabric axis the
    # handlers serve hop-1 PBC time from lpbc[leaf(tenant)], so the
    # mini-interpreter must read/write the same cell (the window's
    # tenant — hence its leaf — is constant, and non-fabric cells
    # lower leaf_of_t = 0)
    NL = st.lpbc.shape[0]
    if NL > 0:
        my_leaf = fabric.leaf_of_tenant(sc, ctx.tenant)
        pbc0 = st.lpbc[my_leaf]
    else:
        pbc0 = st.pbc_busy

    ow = sc["ow_cpu_pm"]
    proc_tag = tb.add(sc["pbc_proc_ns"], sc["tag_ns"])

    # The window replay is a lax.scan over the kmax slots (not a Python
    # unroll): every iteration runs the identical expressions in
    # sequence, so the result is bitwise the same as unrolling while the
    # op body lowers to ONE XLA subgraph instead of kmax inlined copies
    # (the scan body already dominates compile time; unrolling the
    # mini-interpreter 8x on top of it roughly doubled it again).
    def win_op(carry, x):
        (clk, state_cur, tag_cur, lru_cur, dd_cur, ver_cur, owner_cur,
         pmb_cur, pbc_cur, pm_ver_cur, aver_cur, stats_cur, hop_cur,
         guard, t_last) = carry
        j, o_j, a_j, g_j = x
        m = j < k_live
        is_p = o_j == int(Op.PERSIST)
        t_j = tb.add(clk, g_j)
        t_last = jnp.where(m, t_j, t_last)
        bank = channels.bank_of(a_j, ctx.n_banks)
        tracked = (a_j >= 0) & (a_j < ctx.n_track)
        a_idx = jnp.clip(a_j, 0, A - 1)

        # the two legs' arrivals, and below their ends, as one vector
        # operation each (elementwise: the same roundings)
        t_pm, arr = tb.add(t_j, jnp.stack([ow, sc["ow_cpu_sw1"]]))

        # ---- PM read (handler miss path; identical in both schemes) and
        # persist, NoPB leg (always exact: no guard): one bank wait
        pm_start = channels.service_start(pmb_cur, bank, t_pm)
        read_end, write_end, read_free, write_free = tb.add(
            pm_start, jnp.stack([sc["nvm_read"], sc["nvm_write"],
                                 sc["nvm_r_occ"], sc["nvm_w_occ"]]))
        resp, ack_n = tb.add(jnp.stack([read_end, write_end]), ow)
        state_rd = policy.lazy_free(state_cur, dd_cur, t_j)
        has_rd = jnp.any(ctx.slot_active & (tag_cur == a_j)
                         & (state_rd != EMPTY))
        pmb_rd = pmb_cur.at[bank].set(read_free)
        ok_n = tb.le(ack_n, crash)
        pmb_wn = pmb_cur.at[bank].set(write_free)

        # ---- persist, buffered leg (fresh-Empty allocation only)
        pbc_start = channels.pbc_start(pbc_cur, arr, proc_tag)
        state_p1 = policy.lazy_free(state_cur, dd_cur, pbc_start)
        has_dirty = jnp.any(ctx.slot_active & (tag_cur == a_j)
                            & (state_p1 == DIRTY))
        occ = policy.tenant_occupancy(state_p1, ctx.slot_active, owner_cur,
                                      T)
        _, any_empty, wslot = policy.empty_slot(
            sc, state_p1, ctx.slot_active, lru_cur, ctx.tenant, occ)
        t_written = tb.add(pbc_start, sc["data_ns"])
        ack_p = tb.add(t_written, sc["ow_cpu_sw1"])
        v_new = aver_cur[a_idx] + 1
        state_w = jnp.where(ctx.slot_ids == wslot, DIRTY, state_p1)
        tag_w = tag_cur.at[wslot].set(a_j)
        lru_w = lru_cur.at[wslot].set(t_written)
        ver_w = ver_cur.at[wslot].set(v_new)
        owner_w = owner_cur.at[wslot].set(
            ctx.tenant.astype(owner_cur.dtype))
        # PB: immediate drain of the written entry
        st4_pb, dd4_pb, pmb2_pb, _pw = policy.drain_immediate(
            sc, bank, ctx.slot_ids, wslot, t_written, state_w, dd_cur,
            pmb_cur)
        dd_new_pb = dd4_pb[wslot]
        # the op's five time differences, as one vector operation
        diffs = tb.sub(
            jnp.stack([resp, pbc_cur, jnp.where(is_nopb, ack_n, ack_p),
                       t_written, ack_p]),
            jnp.stack([t_j, arr, t_j, arr, t_j]))
        diffs64 = tb.to_f64(diffs)
        # PB_RF: guard that the drain-down fires zero drains
        tight = policy.slo_tight(sc, stats_cur[ctx.tenant],
                                 policy.slo_over(sc, diffs[4]))
        _, k = policy.rf_drain_k(sc, state_w, ctx.slot_active, owner_w,
                                 ctx.tenant, tight)
        rf_zero = k == 0.0
        # scheme-selected buffered outcome (RF with k == 0 is a no-op
        # drain policy: state/dd/pm_busy provably unchanged)
        state_wp = jnp.where(is_rf, state_w, st4_pb)
        dd_wp = jnp.where(is_rf, dd_cur, dd4_pb)
        pmb_wp = jnp.where(is_rf, pmb_cur, pmb2_pb)
        pbcq_inc = jnp.where(tb.gt(pbc_cur, arr), diffs64[1], 0.0)
        pbc_wp = channels.pbc_hold(pbc_cur, arr, sc["pbc_occ_ns"])

        # ---- per-op guard
        g_wr = (any_empty & tb.le(t_written, crash)
                & (~is_rf | (~has_dirty & rf_zero)))
        g_op = (tb.le(t_j, crash)
                & jnp.where(pb_like, jnp.where(is_p, g_wr, ~has_rd), True))
        guard = guard & jnp.where(m, g_op, True)

        # ---- apply op j (masked; aborted windows are discarded whole)
        sel_r = m & ~is_p
        sel_wn = m & is_p & is_nopb
        sel_wp = m & is_p & pb_like
        clk = jnp.where(
            m, jnp.where(is_p, jnp.where(is_nopb, ack_n, ack_p), resp),
            clk)
        state_cur = jnp.where(sel_wp, state_wp,
                              jnp.where(sel_r & pb_like, state_rd,
                                        state_cur))
        tag_cur = jnp.where(sel_wp, tag_w, tag_cur)
        lru_cur = jnp.where(sel_wp, lru_w, lru_cur)
        ver_cur = jnp.where(sel_wp, ver_w, ver_cur)
        owner_cur = jnp.where(sel_wp, owner_w, owner_cur)
        dd_cur = jnp.where(sel_wp, dd_wp, dd_cur)
        pmb_cur = jnp.where(sel_r, pmb_rd,
                            jnp.where(sel_wn, pmb_wn,
                                      jnp.where(sel_wp, pmb_wp, pmb_cur)))
        pbc_cur = jnp.where(sel_wp, pbc_wp, pbc_cur)
        aver_cur = aver_cur.at[a_idx].add(
            jnp.where(m & is_p & tracked, 1, 0))
        pv_ok = jnp.where(is_nopb, ok_n, ~is_rf & tb.le(dd_new_pb, crash))
        pm_ver_cur = pm_ver_cur.at[a_idx].max(
            jnp.where(m & is_p & tracked & pv_ok, v_new, 0))
        # stats: adds of exact 0.0 are bitwise identities (every counter
        # is >= +0.0), so masked lanes and skipped terms stay exact.  A
        # committed window holds no PB hit, coalesce, stall or eviction,
        # so their columns are never added.
        scols, svals = persist_stats(
            diffs64[2], policy.slo_over(sc, diffs[2]), is_nopb | ~is_rf,
            jnp.where(is_nopb, ok_n, tb.le(ack_p, crash)),
            jnp.where(is_nopb, ok_n, True), on=m & is_p,
            extra=((S_READ_SUM, jnp.where(sel_r, diffs64[0], 0.0)),
                   (S_READ_CNT, jnp.where(sel_r, 1.0, 0.0)),
                   (S_PBCQ_SUM, jnp.where(sel_wp, pbcq_inc, 0.0))))
        stats_cur = stats_cur.at[ctx.tenant, scols].add(svals)
        hop_cur = hop_cur.at[
            0, jnp.asarray([H_FWD_CNT, H_FWD_SUM], jnp.int32)
        ].add(jnp.stack([jnp.where(sel_wp, 1.0, 0.0),
                         jnp.where(sel_wp, diffs64[3], 0.0)]))
        return (clk, state_cur, tag_cur, lru_cur, dd_cur, ver_cur,
                owner_cur, pmb_cur, pbc_cur, pm_ver_cur, aver_cur,
                stats_cur, hop_cur, guard, t_last), None

    carry0 = (st.clock[c], st.state, st.tag, st.lru, st.dd, st.ver,
              st.owner, st.pm_busy, pbc0, st.pm_ver, st.aver,
              st.stats, st.hop_stats, jnp.asarray(True), t_issue)
    (clk, state_cur, tag_cur, lru_cur, dd_cur, ver_cur, owner_cur,
     pmb_cur, pbc_cur, pm_ver_cur, aver_cur, stats_cur, hop_cur,
     guard, t_last), _ = jax.lax.scan(
        win_op, carry0, (jnp.arange(kmax), w_ops, w_addr, w_gap))

    no_ilv = tb.gt(win.others_min, t_last)
    # epoch-scheduled grids: the whole window must live in the head
    # op's epoch (boundary instants belong to the *next* epoch, so the
    # last issue time must be strictly below the next boundary)
    if next_epoch_bound is None:
        ep_ok = jnp.asarray(True)
    else:
        ep_ok = tb.lt(t_last, next_epoch_bound)
    live_ok = (win.elig & win.fab_ok & win.deep_ok & ep_ok & guard
               & no_ilv)
    # prioritized abort attribution (MACRO_ABORT_REASONS order): each
    # live candidate that failed to commit counts exactly one reason
    abort_vec = _abort_vec(win, ep_ok, no_ilv, guard)

    if NL > 0:
        pbc_kw = dict(lpbc=st.lpbc.at[my_leaf].set(pbc_cur))
    else:
        pbc_kw = dict(pbc_busy=pbc_cur)
    st_live = st._replace(
        clock=st.clock.at[c].set(clk), state=state_cur, tag=tag_cur,
        lru=lru_cur, dd=dd_cur, ver=ver_cur, owner=owner_cur,
        aver=aver_cur, pm_ver=pm_ver_cur, pm_busy=pmb_cur,
        stats=stats_cur, hop_stats=hop_cur, **pbc_kw)

    use_macro = live_ok | dead_ok
    k_adv = jnp.where(live_ok, k_live, k_cap)
    st_macro = jax.tree_util.tree_map(
        lambda a, b: jnp.where(live_ok, a, b), st_live, st_dead)
    return st_macro, use_macro, k_adv, abort_vec
