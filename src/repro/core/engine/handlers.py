"""Op handlers of the timed engine: one function per trace-op kind.

Each handler maps ``(ctx, MachineState) -> MachineState`` for the op the
selected core issues at time ``ctx.t``; the persist handler also returns
the (2,) bool of switch-chain legs (victim, policy drain) the cell
wanted (``chain.gated_chain``).  The step driver dispatches over the op
kind with ``jax.lax.switch`` on :data:`HANDLERS`, whose entries all
return ``(state, legs)``; *within* the PM-read and persist handlers a
second ``lax.switch`` dispatches over the **traced** scheme scalar
(NoPB / PB / PB_RF), so mixed-scheme grids share one XLA program.

PM write acks are modeled lazily: when a drain is scheduled its ack
arrival time at the switch is computed immediately (PM queueing
included) and stored per entry; any later event observes Drain->Empty
transitions whose ack time has passed (``policy.lazy_free``).  This
reproduces the paper's PI-buffer ack-priority rule (acks never wait
behind stalled writes) with one scan step per trace op.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import chain, channels, fabric, policy
from repro.core.engine import timebase as tb
from repro.core.params import Op, Scheme, spine_defer
from repro.core.engine.state import (DIRTY, DRAIN, H_COALESCES,
                                     H_FWD_CNT, H_FWD_SUM, H_READ_HITS,
                                     MachineState, S_COALESCES,
                                     S_DRAM_READS, S_PBCQ_SUM, S_PI_DETOURS,
                                     S_READ_CNT, S_READ_HITS, S_READ_SUM,
                                     S_STALL_TIME, S_VICTIM_CNT,
                                     persist_stats)


NOPB = int(Scheme.NOPB)
ALL_SCHEMES = tuple(int(s) for s in Scheme)

# clock of a core waiting at a barrier: above every op's issue time,
# below the "no op left" sentinel (the step's INF / 2 test)
WAITING = tb.bits(0.9e30)


class StepCtx(NamedTuple):
    """Per-step context handed to every handler."""

    c: jnp.ndarray          # ()  selected core
    op: jnp.ndarray         # ()  i32 the core's op (COMPUTE when not live)
    t: jnp.ndarray          # ()  op issue time (core clock + compute gap)
    addr: jnp.ndarray       # ()  target cache line
    scheme: jnp.ndarray     # ()  i32 traced scheme id (Scheme value)
    sc: Dict[str, jnp.ndarray]  # traced latency/policy scalars
    slot_ids: jnp.ndarray   # (P,) arange over PBE slots
    slot_active: jnp.ndarray  # (P,) live-slot mask (slot_ids < n_pbe)
    tenant: jnp.ndarray     # ()  i32 tenant id of the selected core
    tids: jnp.ndarray       # (C,) i32 per-core tenant ids (traced)
    n_live_t: jnp.ndarray   # ()  live cores in this op's tenant (barriers)
    n_banks: int            # static PM bank count
    n_track: int = 0        # static durability-tracked address count
    schemes: tuple = ALL_SCHEMES  # static scheme ids the grid holds
    axis_names: tuple = ()  # static vmap axes over the grid's cells


def by_scheme(schemes, scheme, nopb, buffered, operand):
    """``lax.switch`` over the volatile (NoPB) and the buffered (PB,
    PB_RF) leg of a handler.  A leg that no cell of the grid takes
    (``schemes``: the static ids of the grid's configs) is left out at
    trace time; under ``vmap`` a switch would run it for every cell."""
    if NOPB not in schemes:
        return buffered(operand)
    if set(schemes) == {NOPB}:
        return nopb(operand)
    return jax.lax.switch(jnp.minimum(scheme, 1), [nopb, buffered], operand)


def no_legs():
    """The chain legs of a handler that sends no packet down one."""
    return jnp.zeros((2,), bool)


def _tracked(ctx: StepCtx, addr):
    """Is ``addr`` inside the durability-tracked window [0, n_track)?"""
    return (addr >= 0) & (addr < ctx.n_track)


# ---------------------------------------------------------------- volatile
def handle_compute(ctx: StepCtx, st: MachineState) -> MachineState:
    return st._replace(clock=st.clock.at[ctx.c].set(ctx.t))


def handle_dram_read(ctx: StepCtx, st: MachineState) -> MachineState:
    stats = st.stats.at[ctx.tenant, S_DRAM_READS].add(1.0)
    return st._replace(
        clock=st.clock.at[ctx.c].set(tb.add(ctx.t, ctx.sc["dram_ns"])),
        stats=stats)


def handle_dram_write(ctx: StepCtx, st: MachineState) -> MachineState:
    # posted write: ~free for the core
    return st._replace(clock=st.clock.at[ctx.c].set(ctx.t))


# ----------------------------------------------------------------- PM read
def handle_pm_read(ctx: StepCtx, st: MachineState) -> MachineState:
    sc, t, addr = ctx.sc, ctx.t, ctx.addr
    ow = sc["ow_cpu_pm"]
    bank = channels.bank_of(addr, ctx.n_banks)

    def direct(st: MachineState) -> MachineState:
        # NoPB: the volatile switch forwards every read to PM.
        pm_start = channels.service_start(st.pm_busy, bank, tb.add(t, ow))
        resp = tb.add(tb.add(pm_start, sc["nvm_read"]), ow)
        stats = st.stats.at[
            ctx.tenant, jnp.asarray([S_READ_SUM, S_READ_CNT], jnp.int32)
        ].add(jnp.stack([tb.to_f64(tb.sub(resp, t)),
                         jnp.ones((), jnp.float64)]))
        return st._replace(
            clock=st.clock.at[ctx.c].set(resp),
            pm_busy=channels.reserve(st.pm_busy, bank, pm_start,
                                     sc["nvm_r_occ"]),
            stats=stats)

    def via_pb(st: MachineState) -> MachineState:
        # PB/PB_RF: the PBCS classifies the read; a live entry routes it
        # through the PI buffer to the PBC (read forwarding).
        pm_start_dir = channels.service_start(st.pm_busy, bank,
                                              tb.add(t, ow))

        state0 = policy.lazy_free(st.state, st.dd, t)
        # Fabric: a read routes through the issuing tenant's own leaf
        # switch — only that leaf's slot window is visible, and that
        # leaf's PBC front serves it.  NL == 0 (chain-only grid) keeps
        # the global window and the shared scalar clock, byte-identical.
        NL = st.lpbc.shape[0]
        if NL > 0:
            my_leaf = fabric.leaf_of_tenant(sc, ctx.tenant)
            leaf_act = ctx.slot_active & fabric.leaf_mask(
                sc, fabric.slot_leaf(sc, ctx.slot_ids), my_leaf)
            pbc_prev = st.lpbc[my_leaf]
        else:
            leaf_act = ctx.slot_active
            pbc_prev = st.pbc_busy
        has, idx = policy.pb_lookup(st.tag, state0, leaf_act, addr)
        # PI-buffer path: wait for the PBC (head-of-line blocking)
        arr = tb.add(t, sc["ow_cpu_sw1"])
        pbc_start = channels.pbc_start(
            pbc_prev, arr, tb.add(sc["pbc_read_ns"], sc["tag_ns"]))
        st_i = state0[idx]
        dd_i = st.dd[idx]
        served = (st_i == DIRTY) | (
            (st_i == DRAIN)
            & tb.gt(dd_i, tb.add(pbc_start, sc["fwd_margin"])))
        # forwarded to PM through the PO buffer after the detour; the
        # packet re-enters the routing pipeline (one extra pipe pass)
        pm_start_fwd = tb.maximum(
            st.pm_busy[bank],
            tb.add(tb.add(pbc_start, sc["switch_pipe"]), sc["ow_sw1_pm"]))
        # the three possible responses and the two bank holds, as one
        # vector operation each (elementwise: the same roundings)
        resp_dir, resp_pb, resp_fwd = tb.add(
            tb.add(jnp.stack([pm_start_dir, pbc_start, pm_start_fwd]),
                   jnp.stack([sc["nvm_read"], sc["data_ns"],
                              sc["nvm_read"]])),
            jnp.stack([ow, sc["ow_cpu_sw1"], ow]))
        free_fwd, free_dir = tb.add(
            jnp.stack([pm_start_fwd, pm_start_dir]), sc["nvm_r_occ"])

        # Read-forwarding checks below hop 1 (switch chain): when hop 1
        # has no live entry, the packet travels toward PM passing every
        # deeper switch's PBCS — the shallowest hop holding a visible
        # live entry serves it.  (A *stale* hop-1 Drain entry keeps its
        # legacy forward-to-PM path: the deep refinement is skipped.)
        D = st.dtag.shape[0]
        if D > 0:
            dhit0, resp_deep, dlru2, hrow = chain.deep_read(sc, st, addr, t)
            deep_hit = (sc["n_switches"] >= 2.0) & dhit0 & ~has
        else:
            deep_hit = jnp.asarray(False)
            resp_deep, dlru2, hrow = resp_dir, st.dlru, 0

        resp = jnp.where(has, jnp.where(served, resp_pb, resp_fwd),
                         jnp.where(deep_hit, resp_deep, resp_dir))
        pm_busy2 = st.pm_busy.at[bank].set(jnp.where(
            has,
            jnp.where(served, st.pm_busy[bank], free_fwd),
            jnp.where(deep_hit, st.pm_busy[bank], free_dir)))
        pbc_busy2 = jnp.where(
            has, channels.pbc_hold(pbc_prev, arr, sc["pbc_read_occ"]),
            pbc_prev)
        if NL > 0:
            pbc_kw = dict(lpbc=st.lpbc.at[my_leaf].set(pbc_busy2))
        else:
            pbc_kw = dict(pbc_busy=pbc_busy2)
        lru2 = st.lru.at[idx].set(jnp.where(has & served, t, st.lru[idx]))
        dlru3 = jnp.where(deep_hit, dlru2, st.dlru)
        hop_stats = st.hop_stats.at[0, H_READ_HITS].add(
            (has & served).astype(jnp.float64))
        if D > 0:
            hop_stats = hop_stats.at[hrow + 1, H_READ_HITS].add(
                deep_hit.astype(jnp.float64))
        stats = st.stats.at[
            ctx.tenant, jnp.asarray([S_READ_SUM, S_READ_CNT, S_READ_HITS,
                                     S_PI_DETOURS], jnp.int32)
        ].add(jnp.stack([tb.to_f64(tb.sub(resp, t)),
                         jnp.ones((), jnp.float64),
                         ((has & served) | deep_hit).astype(jnp.float64),
                         has.astype(jnp.float64)]))
        return st._replace(clock=st.clock.at[ctx.c].set(resp), state=state0,
                           lru=lru2, dlru=dlru3, pm_busy=pm_busy2,
                           stats=stats, hop_stats=hop_stats, **pbc_kw)

    return by_scheme(ctx.schemes, ctx.scheme, direct, via_pb, st)


# ----------------------------------------------------------------- persist
def _persist_with_buffer(ctx: StepCtx, st: MachineState):
    """Shared PB persist core: PBC service, lookup, allocation / victim
    selection, entry write — then the scheme's drain policy.  Returns
    the state and the chain legs the cell wanted (:func:`no_legs`).

    One traced body serves both buffered schemes: ``is_rf`` selects
    coalescing and the threshold/preset drain policy (PB_RF) vs the
    immediate write-through drain (PB) elementwise.  Tracing this once
    instead of once per scheme halves the vmap-executed switch-chain
    work per step (vmap runs every ``lax.switch`` branch), which is the
    dominant cost of the scan body at depth >= 2.
    """
    sc, t, addr = ctx.sc, ctx.t, ctx.addr
    is_rf = ctx.scheme == 2          # Scheme.PB_RF, traced
    crash = sc["crash_at"]
    bank = channels.bank_of(addr, ctx.n_banks)
    arr = tb.add(t, sc["ow_cpu_sw1"])
    # Fabric: the persist enters the issuing tenant's own leaf switch —
    # lookup/alloc/victim/drain are scoped to that leaf's slot window,
    # and that leaf's own PBC front serves the packet.  NL == 0 (no
    # fabric anywhere in the grid) keeps the global window and the
    # shared scalar clock, byte-identical to the chain engine; a chain
    # cell *inside* a fabric grid gets the same via the n_leaves < 2
    # mask bypass (every slot maps to leaf 0).
    NL = st.lpbc.shape[0]
    if NL > 0:
        my_leaf = fabric.leaf_of_tenant(sc, ctx.tenant)
        leaf_act = ctx.slot_active & fabric.leaf_mask(
            sc, fabric.slot_leaf(sc, ctx.slot_ids), my_leaf)
        pbc_prev = st.lpbc[my_leaf]
    else:
        leaf_act = ctx.slot_active
        pbc_prev = st.pbc_busy
    pbc_start = channels.pbc_start(pbc_prev, arr,
                                   tb.add(sc["pbc_proc_ns"], sc["tag_ns"]))
    state1 = policy.lazy_free(st.state, st.dd, pbc_start)
    match_dirty = leaf_act & (st.tag == addr) & (state1 == DIRTY)
    has_dirty = jnp.any(match_dirty)
    idx = jnp.argmax(match_dirty)

    # durability tracking: this persist's per-address version number
    A = st.aver.shape[0]
    tracked = _tracked(ctx, addr)
    a_idx = jnp.clip(addr, 0, A - 1)
    v_new = st.aver[a_idx] + 1
    aver2 = st.aver.at[a_idx].add(jnp.where(tracked, 1, 0))

    is_coalesce = jnp.logical_and(is_rf, has_dirty)
    # An in-flight (Drain) older version does NOT block the new persist
    # (write order, Section IV-A): the new version gets its own entry.
    # The switch->PM path is FIFO per bank, so drains of the same line
    # arrive at PM in version order without waiting for the previous ack.
    # Allocation is policy-driven (AllocPolicy lowering): per-tenant
    # occupancy feeds the quota gate and the weighted victim selection.
    occ = policy.tenant_occupancy(state1, ctx.slot_active, st.owner,
                                  st.stats.shape[0])
    (any_empty, empty_idx, any_dirty, victim_idx,
     earliest_idx) = policy.select_slot(sc, state1, leaf_act,
                                        st.lru, st.dd, st.owner,
                                        ctx.tenant, occ)

    # victim drain (only used when no Empty entry exists)
    victim_bank = channels.bank_of(st.tag[victim_idx], ctx.n_banks)
    victim_pm_start = tb.maximum(st.pm_busy[victim_bank],
                                 tb.add(pbc_start, sc["ow_sw1_pm"]))
    victim_end, victim_free = tb.add(
        victim_pm_start, jnp.stack([sc["nvm_write"], sc["nvm_w_occ"]]))
    victim_dd = tb.add(victim_end, sc["ow_sw1_pm"])
    needs_victim = (~is_coalesce) & (~any_empty) & any_dirty

    # the victim's in-flight write is durable at PM iff its ack beats the
    # crash (a later ack means the write is lost with the power)
    vic_tag = st.tag[victim_idx]
    vic_ok = (needs_victim & tb.le(victim_dd, crash) & (vic_tag >= 0)
              & (vic_tag < ctx.n_track))
    pm_ver1 = st.pm_ver.at[jnp.clip(vic_tag, 0, A - 1)].max(
        jnp.where(vic_ok, st.ver[victim_idx], 0))

    # ---- switch chain, victim leg (per-switch persistent buffers) -----
    # With >= 2 switches in the chain, a hop-1 drain is acked by hop 2's
    # persistent cells, not by PM: the victim packet travels the chain
    # FIRST (it leaves the PBC at pbc_start, ahead of the entry write),
    # so the slot frees at its true downstream ack.  D == 0 (no deep row
    # allocated anywhere in the grid) skips the chain at trace time.
    # Each leg runs only on grid steps where some cell sends it a packet
    # (``chain.gated_chain``); elsewhere it returns its inputs.
    D = st.dtag.shape[0]
    vic_emit = needs_victim & tb.le(pbc_start, crash)
    if D > 0:
        is_chain = sc["n_switches"] >= 2.0
        sends = (ctx.op == int(Op.PERSIST)) & (ctx.scheme != NOPB) & is_chain
        one_i = lambda v: jnp.asarray([v], jnp.int32)        # noqa: E731
        vic_batch = chain.Batch(
            active=vic_emit[None],
            addr=vic_tag[None], ver=st.ver[victim_idx][None],
            owner=st.owner[victim_idx][None], emit=pbc_start[None],
            ohop=one_i(0), oslot=victim_idx[None].astype(jnp.int32))
        (dd_v, rows_v, hpbc_v, hstats_v, pmb_v, pmv_v,
         pmw_v), want_v = chain.gated_chain(
            sc, ctx.scheme, chain.rows_of(st), st.hpbc, st.hop_stats,
            vic_batch, st.dd, st.pm_busy, st.pm_ver, sends=sends,
            axis_names=ctx.axis_names, n_banks=ctx.n_banks,
            n_track=ctx.n_track)
        vic_ack = jnp.where(vic_emit, dd_v[victim_idx], victim_dd)
        vic_wait = jnp.where(is_chain, vic_ack, victim_dd)
    else:
        vic_wait = victim_dd

    slot = jnp.where(any_empty, empty_idx,
                     jnp.where(any_dirty, victim_idx, earliest_idx))
    ta = jnp.where(any_empty, pbc_start,
                   jnp.where(any_dirty, vic_wait,
                             tb.maximum(pbc_start, st.dd[earliest_idx])))
    pm_busy1 = st.pm_busy.at[victim_bank].set(jnp.where(
        needs_victim, victim_free,
        st.pm_busy[victim_bank]))
    state2 = jnp.where(
        needs_victim & (ctx.slot_ids == victim_idx), DRAIN, state1)
    dd2 = jnp.where(
        needs_victim & (ctx.slot_ids == victim_idx), victim_dd, st.dd)

    # write the entry (new allocation or coalesce-in-place)
    wslot = jnp.where(is_coalesce, idx, slot)
    t_written = tb.add(jnp.where(is_coalesce, pbc_start, ta), sc["data_ns"])
    ack = tb.add(t_written, sc["ow_cpu_sw1"])
    # the persist's four time differences, as one vector operation
    diffs = tb.sub(jnp.stack([pbc_prev, ack, ta, t_written]),
                   jnp.stack([arr, t, pbc_start, arr]))
    over_now = policy.slo_over(sc, diffs[1])
    tight = policy.slo_tight(sc, st.stats[ctx.tenant], over_now)
    state3 = jnp.where(ctx.slot_ids == wslot, DIRTY, state2)
    tag3 = st.tag.at[wslot].set(addr)
    lru3 = st.lru.at[wslot].set(t_written)
    dd3 = dd2
    ver3 = st.ver.at[wslot].set(v_new)
    # the writer takes ownership (a cross-tenant coalesce included,
    # mirroring the oracle's PBEntry.tenant update)
    owner3 = st.owner.at[wslot].set(ctx.tenant.astype(st.owner.dtype))

    # Backpressure-aware drain scheduling (fabric): while the spine PB's
    # live occupancy — measured AFTER this op's victim leg landed, i.e.
    # what the leaf's drain batch would actually meet — is at/above the
    # topology's bp_high, the leaf's threshold/low-water drain-down
    # defers (holds its Dirty entries) instead of piling more fan-in
    # onto the congested spine.  Non-fabric configs lower bp_high = INF
    # (never defer); victim drains and PB's drain-immediate are exempt
    # (forward progress).
    if D > 0 and NL > 0:
        sp_live = fabric.spine_live(sc, rows_v["dstate"][0], ctx.slot_ids)
        defer = spine_defer(sp_live, sc["bp_high"])
    else:
        defer = None

    # Both drain policies run (cheap relative to the chain legs); the
    # traced scheme bit picks each output elementwise, bit-exactly.
    state4_pb, dd4_pb, pmb2_pb, pw_pb = policy.drain_immediate(
        sc, bank, ctx.slot_ids, wslot, t_written, state3, dd3, pm_busy1)
    state4_rf, dd4_rf, pmb2_rf, pw_rf = policy.drain_threshold_preset(
        sc, ctx.n_banks, leaf_act, t_written, state3, tag3, lru3,
        dd3, pm_busy1, owner=owner3, tenant=ctx.tenant, tight=tight,
        defer=defer)
    state4 = jnp.where(is_rf, state4_rf, state4_pb)
    dd4 = jnp.where(is_rf, dd4_rf, dd4_pb)
    pm_busy2 = jnp.where(is_rf, pmb2_rf, pmb2_pb)
    policy_writes = jnp.where(is_rf, pw_rf, pw_pb)

    # drains the policy just scheduled (Dirty -> Drain) whose PM ack
    # beats the crash make their versions durable at the device
    drained_now = (state4 == DRAIN) & (state3 == DIRTY)
    drain_ok = (drained_now & tb.le(dd4, crash) & (tag3 >= 0)
                & (tag3 < ctx.n_track))
    pm_ver2 = pm_ver1.at[jnp.clip(tag3, 0, A - 1)].max(
        jnp.where(drain_ok, ver3, 0))

    # Switch-commit gate: a persist that issued before the crash but
    # whose entry write lands only after it never reached the
    # persistent switch.  Its PB-table effects (allocation, coalesce,
    # policy drains) are discarded — otherwise it would overwrite a
    # surviving entry whose in-flight drain is lost, dropping an acked
    # version from the durable state.  The victim drain stands if the
    # PBC emitted it before the power loss (its entry then survives in
    # Drain when its ack is post-crash, so its version is never lost),
    # and a non-committed persist consumes no version number.  Resource
    # clocks (PBC/PM/core) stay as computed: the packet occupied them
    # until the power died, and the core is dead afterwards anyway.
    commit = tb.le(t_written, crash)
    vslot = ctx.slot_ids == victim_idx
    state5 = jnp.where(commit, state4,
                       jnp.where(vic_emit & vslot, DRAIN, st.state))
    tag5 = jnp.where(commit, tag3, st.tag)
    lru5 = jnp.where(commit, lru3, st.lru)
    dd5 = jnp.where(commit, dd4,
                    jnp.where(vic_emit & vslot, victim_dd, st.dd))
    ver5 = jnp.where(commit, ver3, st.ver)
    owner5 = jnp.where(commit, owner3, st.owner)
    aver3 = jnp.where(commit, aver2, st.aver)
    pm_ver3 = jnp.where(commit, pm_ver2, pm_ver1)
    pm_busy3 = jnp.where(commit, pm_busy2, pm_busy1)
    pm_writes_inc = (vic_emit.astype(jnp.float64)
                     + jnp.where(commit, policy_writes, 0.0))

    # ---- switch chain, policy-drain leg --------------------------------
    # The drains the policy just scheduled travel to hop 2 as one batch
    # (they leave the PBC together at t_written, after the victim leg);
    # under the chain the PM-path dd/pm values computed above are
    # per-field replaced by the cascade's downstream acks and landings.
    if D > 0:
        P = st.tag.shape[0]
        # the batch leaves the PBC in LRU order of the drained entries
        # (the wire order the oracle's drain-down replays)
        pol_active = drained_now & commit
        pol_order = tb.argsort(
            jnp.where(pol_active, lru3, tb.INF)).astype(jnp.int32)
        pol_batch = chain.Batch(
            active=pol_active[pol_order],
            addr=tag3[pol_order], ver=ver3[pol_order],
            owner=owner3[pol_order],
            emit=jnp.broadcast_to(t_written, (P,)),
            ohop=jnp.zeros((P,), jnp.int32),
            oslot=pol_order)
        (dd_c, rows_c, hpbc_c, hstats_c, pmb_c, pmv_c,
         pmw_c), want_p = chain.gated_chain(
            sc, ctx.scheme, rows_v, hpbc_v, hstats_v, pol_batch,
            jnp.where(commit, dd4, dd_v), pmb_v, pmv_v, sends=sends,
            axis_names=ctx.axis_names, n_banks=ctx.n_banks,
            n_track=ctx.n_track)
        legs = jnp.stack([want_v, want_p])
        dd5 = jnp.where(is_chain, dd_c, dd5)
        pm_ver3 = jnp.where(is_chain, pmv_c, pm_ver3)
        pm_busy3 = jnp.where(is_chain, pmb_c, pm_busy3)
        pm_writes_inc = jnp.where(is_chain, pmw_v + pmw_c, pm_writes_inc)
        chain_cols = {k: jnp.where(is_chain, rows_c[k], getattr(st, k))
                      for k in rows_c}
        chain_cols["hpbc"] = jnp.where(is_chain, hpbc_c, st.hpbc)
        hop_stats = jnp.where(is_chain, hstats_c, st.hop_stats)
    else:
        chain_cols = {}
        hop_stats = st.hop_stats
        legs = no_legs()
    # hop-1 telemetry row (chain row 0; maintained at every depth >= 1)
    hop_stats = hop_stats.at[0, H_FWD_CNT].add(commit.astype(jnp.float64))
    diffs64 = tb.to_f64(diffs)
    hop_stats = hop_stats.at[0, H_FWD_SUM].add(
        jnp.where(commit, diffs64[3], 0.0))
    hop_stats = hop_stats.at[0, H_COALESCES].add(
        (is_coalesce & commit).astype(jnp.float64))

    stall = jnp.where(is_coalesce, 0.0, diffs64[2])
    # Only a genuine Empty-shortage stall (ta > pbc_start) holds the PI
    # front beyond the pipelined issue interval.
    pbc_free = tb.maximum(
        channels.pbc_hold(pbc_prev, arr, sc["pbc_occ_ns"]),
        jnp.where(is_coalesce | tb.le(ta, pbc_start), tb.ZERO, ta))
    if NL > 0:
        pbc_kw = dict(lpbc=st.lpbc.at[my_leaf].set(pbc_free))
    else:
        pbc_kw = dict(pbc_busy=pbc_free)
    # A persist committed into the persistent switch is durable
    # regardless of the drain's fate (the paper's core claim); the core
    # only *observes* the ack if it lands before the crash, and ack beats
    # the crash only if the write committed first, so acked => durable.
    cols, vals = persist_stats(
        diffs64[1], over_now, pm_writes_inc, tb.le(ack, crash), commit,
        extra=((S_VICTIM_CNT, (~is_coalesce) & (~any_empty)),
               (S_PBCQ_SUM, jnp.where(tb.gt(pbc_prev, arr), diffs64[0],
                                      0.0)),
               (S_COALESCES, is_coalesce),
               (S_STALL_TIME, stall)))
    stats = st.stats.at[ctx.tenant, cols].add(vals)
    return st._replace(clock=st.clock.at[ctx.c].set(ack), tag=tag5,
                       state=state5, lru=lru5, dd=dd5, ver=ver5,
                       owner=owner5, aver=aver3, pm_ver=pm_ver3,
                       pm_busy=pm_busy3, stats=stats,
                       hop_stats=hop_stats, **pbc_kw, **chain_cols), legs


def handle_persist(ctx: StepCtx, st: MachineState):
    """The persist handler: the state and the chain legs the cell
    wanted (a volatile switch has no chain to send down)."""
    sc, t, addr = ctx.sc, ctx.t, ctx.addr

    def nopb(st: MachineState):
        # Volatile switch: the persist round-trips to PM.  Nothing is
        # durable until PM acks — a write whose ack lands after the
        # crash is lost (and the core never saw the ack either).
        ow = sc["ow_cpu_pm"]
        crash = sc["crash_at"]
        bank = channels.bank_of(addr, ctx.n_banks)
        pm_start = channels.service_start(st.pm_busy, bank, tb.add(t, ow))
        ack = tb.add(tb.add(pm_start, sc["nvm_write"]), ow)
        ok = tb.le(ack, crash)
        A = st.aver.shape[0]
        tracked = _tracked(ctx, addr)
        a_idx = jnp.clip(addr, 0, A - 1)
        v_new = st.aver[a_idx] + 1
        lat = tb.sub(ack, t)
        cols, vals = persist_stats(tb.to_f64(lat), policy.slo_over(sc, lat),
                                   1.0, ok, ok)
        stats = st.stats.at[ctx.tenant, cols].add(vals)
        return st._replace(
            clock=st.clock.at[ctx.c].set(ack),
            aver=st.aver.at[a_idx].add(jnp.where(tracked, 1, 0)),
            pm_ver=st.pm_ver.at[a_idx].max(
                jnp.where(tracked & ok, v_new, 0)),
            pm_busy=channels.reserve(st.pm_busy, bank, pm_start,
                                     sc["nvm_w_occ"]),
            stats=stats), no_legs()

    def buffered(st: MachineState):
        # PB and PB_RF share one traced body (is_rf inside selects the
        # coalesce rule and drain policy) so vmap executes the
        # expensive chain legs once per step instead of twice.
        return _persist_with_buffer(ctx, st)

    return by_scheme(ctx.schemes, ctx.scheme, nopb, buffered, st)


# ----------------------------------------------------------------- barrier
def handle_barrier(ctx: StepCtx, st: MachineState) -> MachineState:
    # Centralized barrier *per tenant*: independent hosts never
    # synchronize with each other, so only this tenant's cores arrive
    # and the last of them releases its tenant's waiters at its arrival
    # time.  With one tenant this is exactly the old global barrier.
    same = ctx.tids == ctx.tenant
    last = (st.bcount[ctx.tenant] + 1) >= ctx.n_live_t
    released = jnp.where(st.blocked & same, ctx.t,
                         st.clock).at[ctx.c].set(ctx.t)
    waiting = st.clock.at[ctx.c].set(WAITING)
    return st._replace(clock=jnp.where(last, released, waiting))


def _no_chain(handler):
    """``handler`` with the chain legs of an op that sends nothing."""
    return lambda ctx, st: (handler(ctx, st), no_legs())


# indexed by Op; each entry returns (state, chain legs wanted)
HANDLERS = [_no_chain(handle_compute), _no_chain(handle_dram_read),
            _no_chain(handle_dram_write), _no_chain(handle_pm_read),
            handle_persist, _no_chain(handle_barrier)]


# ---------------------------------------------------------------- recovery
def recovery_snapshot(st: MachineState, scheme, sc, slot_active,
                      n_banks: int, n_track: int,
                      schemes: tuple = ALL_SCHEMES):
    """Section V-D4 recovery pass over the crash-time machine state.

    Dispatches over the traced scheme like the op handlers: NoPB has no
    PBEs, so its durable state is exactly ``pm_ver`` and recovery is
    free; PB/PB_RF drain-all the *union* of surviving Dirty/Drain
    entries across every hop of the switch chain — a crash freezes each
    hop independently, and durability per address is the newest version
    held at any surviving hop (or PM).  Returns
    ``(durable_ver (A,) i32, n_recovered f64, recovery_ns f64,
    recovered_per_tenant (T,) f64, recovered_per_hop (D+1,) f64,
    recovered_per_leaf (max(NL,1),) f64)`` — the last three attribute
    each surviving entry to its owning tenant (recovery fairness,
    ROADMAP), to the hop holding it (the chain depth figure), and —
    for fan-out fabrics — to the leaf switch holding it (hop-1 slots
    scattered by their leaf window; the spine's survivors are
    ``per_hop[1]``).
    """
    crash = sc["crash_at"]
    A = st.pm_ver.shape[0]
    T = st.stats.shape[0]
    D = st.dtag.shape[0]
    NL = max(st.lpbc.shape[0], 1)
    zero = jnp.asarray(0.0, jnp.float64)
    zero_ns = jnp.asarray(tb.ZERO, tb.DTYPE)
    zero_t = jnp.zeros((T,), jnp.float64)
    zero_h = jnp.zeros((D + 1,), jnp.float64)
    zero_l = jnp.zeros((NL,), jnp.float64)

    def nopb(_):
        return st.pm_ver, zero, zero_ns, zero_t, zero_h, zero_l

    def pb(_):
        surviving = policy.surviving_entries(st.state, st.dd, slot_active,
                                             crash)
        in_range = surviving & (st.tag >= 0) & (st.tag < n_track)
        dv = st.pm_ver.at[jnp.clip(st.tag, 0, A - 1)].max(
            jnp.where(in_range, st.ver, 0))
        per_t = zero_t.at[jnp.clip(st.owner, 0, T - 1)].add(
            surviving.astype(jnp.float64))
        if st.lpbc.shape[0] > 0:
            sl = fabric.slot_leaf(sc, jnp.arange(st.tag.shape[0]))
            per_leaf = zero_l.at[sl].add(surviving.astype(jnp.float64))
        else:
            per_leaf = zero_l.at[0].set(
                jnp.sum(surviving.astype(jnp.float64)))
        B = n_banks
        banks = jnp.where(surviving, st.tag % B, 0)
        per_bank = jnp.zeros((B,), jnp.float64).at[banks].add(
            surviving.astype(jnp.float64))
        n = jnp.sum(surviving.astype(jnp.float64))
        per_hop = zero_h.at[0].set(n)
        slot_ids = jnp.arange(st.tag.shape[0])
        for j in range(D):
            row_live = (float(j) + 2.0) <= sc["n_switches"]
            sa = slot_ids < sc["deep_pbe"][j].astype(jnp.int32)
            # same survival rule per hop: Dirty cells persist; a Drain
            # entry survives iff its downstream ack is lost with the
            # power (placements are commit-gated, so wt <= crash always
            # holds — kept as written defence)
            surv_j = (row_live & sa & tb.le(st.dwt[j], crash)
                      & ((st.dstate[j] == DIRTY)
                         | ((st.dstate[j] == DRAIN)
                            & tb.gt(st.ddd[j], crash))))
            in_r = surv_j & (st.dtag[j] >= 0) & (st.dtag[j] < n_track)
            dv = dv.at[jnp.clip(st.dtag[j], 0, A - 1)].max(
                jnp.where(in_r, st.dver[j], 0))
            per_t = per_t.at[jnp.clip(st.downer[j], 0, T - 1)].add(
                surv_j.astype(jnp.float64))
            bj = jnp.where(surv_j, st.dtag[j] % B, 0)
            per_bank = per_bank.at[bj].add(surv_j.astype(jnp.float64))
            nj = jnp.sum(surv_j.astype(jnp.float64))
            per_hop = per_hop.at[j + 1].set(nj)
        n_total = jnp.sum(per_hop)
        cost = policy.recovery_burst_cost(sc, per_bank, n_total)
        return dv, n_total, cost, per_t, per_hop, per_leaf

    return by_scheme(schemes, scheme, nopb, pb, None)
