"""Switch-chain forwarding: per-switch persistent buffers (DESIGN.md §5).

The pooling topology promotes ``n_switches`` from a latency multiplier
into a simulated chain: hop 1 (the tenant-facing ack point) keeps the
flat legacy PB columns of :class:`~repro.core.engine.state.MachineState`,
and every deeper switch owns one row of the ``(D, P)`` deep-hop columns.
A hop-1 drain no longer writes PM directly — it travels one inter-switch
segment to hop 2's PBC, commits into hop 2's persistent cells (the ack
that frees the hop-1 entry returns from there), and later propagates
further down per the scheme's drain policy:

  * **PB** (drain-immediate): every hop forwards what it just committed
    — a store-and-forward pipeline whose entries transit in Drain;
  * **PB_RF**: every hop retains Dirty entries and runs its *own*
    threshold/preset drain-down (per-hop counts lowered as traced
    vectors, ``params.hop_drain_counts``), coalescing arrivals into an
    existing Dirty entry for the same line.

An arrival that finds a hop full (no coalesce, no Empty slot after
lazy-free) **bypasses** the hop and continues toward PM — capacity
pressure degrades the chain to write-through instead of deadlocking on
recursive victim cascades.  Packets that run out of switches land at PM
with the per-bank burst serialization of the legacy drain path.

Crash semantics: a packet whose downstream commit lands after
``crash_at`` dies on the wire — the target hop's table is untouched and
the origin entry survives in Drain (its ack time is past the crash), so
an acked persist is always recoverable from the deepest hop it reached
(the union rule of ``handlers.recovery_snapshot``).

Everything here is traced: the chain depth, per-hop capacities and
drain counts are scalars/vectors of ``sc``, so a mixed {workload x
scheme x depth x policy} sweep stays ONE XLA program.  Only the
grid-wide maximum depth (``D = n_deep_max``, a static array shape) is
compile-time; when every config in a grid is depth <= 1, ``D == 0`` and
the whole module is skipped at trace time — depth-1 programs are
byte-identical to the pre-chain engine.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import channels
from repro.core.engine import timebase as tb
from repro.core.engine.state import (DIRTY, DRAIN, EMPTY, H_BYPASS,
                                     H_COALESCES, H_FWD_CNT, H_FWD_SUM,
                                     MachineState)

F = jnp.float64


class Batch(NamedTuple):
    """Packets in flight between two adjacent switches (wire order)."""

    active: jnp.ndarray  # (Q,) bool
    addr: jnp.ndarray    # (Q,) i32
    ver: jnp.ndarray     # (Q,) i32
    owner: jnp.ndarray   # (Q,) i8 (the packed MachineState owner dtype;
                         #          `_place`'s injective pick() sums carry
                         #          it through without widening)
    emit: jnp.ndarray    # (Q,) time emission time at the previous switch
    ohop: jnp.ndarray    # (Q,) i32  origin hop (0 = hop-1 flat columns,
                         #           m > 0 = deep row m-1) for dd writeback
    oslot: jnp.ndarray   # (Q,) i32  origin PBE slot


def _last_writer(mask, oslot):
    """Keep only the last packet (batch order) targeting each origin slot.

    One cascade can emit two packets from the same hop-1 slot (the
    victim's old entry, then the reused slot's new entry drained by the
    drain-down); the slot's dd must be the later packet's ack.
    """
    q = jnp.arange(mask.shape[0])
    later = (oslot[None, :] == oslot[:, None]) & (q[None, :] > q[:, None]) \
        & mask[None, :]
    return mask & ~jnp.any(later, axis=1)


def _scatter_dd(dd1, ddd, batch: Batch, vals, mask):
    """Write per-packet ack times back to the origin entries' dd."""
    D = ddd.shape[0]
    m0 = _last_writer(mask & (batch.ohop == 0), batch.oslot)
    dd1 = dd1.at[batch.oslot].set(jnp.where(m0, vals, dd1[batch.oslot]))
    for m in range(1, D + 1):
        mm = _last_writer(mask & (batch.ohop == m), batch.oslot)
        ddd = ddd.at[m - 1, batch.oslot].set(
            jnp.where(mm, vals, ddd[m - 1, batch.oslot]))
    return dd1, ddd


def _pm_land(sc, pos, batch: Batch, pm_busy, pm_ver, n_banks, n_track):
    """Packets at switch ``pos`` with no deeper switch write through to PM.

    Same per-bank burst serialization as the legacy drain path; the ack
    returns up the chain to the origin switch.  Returns
    ``(pm_busy, pm_ver, dd_vals (Q,), n_writes)``.
    """
    crash = sc["crash_at"]
    A = pm_ver.shape[0]
    act = batch.active
    # remaining wire: switch pos -> PM through the switches below it
    rem = jnp.maximum(sc["n_switches"] - float(pos), 0.0)
    # ack back at the origin switch o: PM -> switch n -> ... -> switch o;
    # both wires as one vector operation each (elementwise roundings)
    o = batch.ohop + 1
    paths = tb.add(sc["link_ns"], tb.mul(sc["hop_ns"], jnp.concatenate([
        rem[None], jnp.maximum(sc["n_switches"] - o.astype(F), 0.0)])))
    path_down, path_up = paths[0], paths[1:]
    arr = tb.add(batch.emit, path_down)
    bank = batch.addr % n_banks
    same_bank = bank[None, :] == bank[:, None]
    q = jnp.arange(act.shape[0])
    earlier = q[None, :] < q[:, None]
    rank_b = jnp.sum((same_bank & earlier & act[None, :]).astype(F), axis=1)
    start = tb.add(tb.maximum(pm_busy[bank], arr),
                   tb.mul(sc["nvm_w_occ"], rank_b))
    write_end, bank_free = tb.add(
        start, jnp.stack([sc["nvm_write"], sc["nvm_w_occ"]])[:, None])
    dd_vals = tb.add(write_end, path_up)
    busy_after = tb.max(jnp.where(
        same_bank & act[None, :], bank_free[None, :], tb.ZERO), axis=1)
    pm_busy2 = tb.maximum(
        pm_busy, jnp.zeros_like(pm_busy).at[bank].max(
            jnp.where(act, busy_after, tb.ZERO)))
    ok = act & tb.le(dd_vals, crash) & (batch.addr >= 0) \
        & (batch.addr < n_track)
    pm_ver2 = pm_ver.at[jnp.clip(batch.addr, 0, A - 1)].max(
        jnp.where(ok, batch.ver, 0))
    return pm_busy2, pm_ver2, dd_vals, jnp.sum(act.astype(F))


def _drain_count(sc, j, scheme, dirty):
    """Row ``j``'s Dirty count and how many of them its drain-down
    forwards: every one under PB, down to the preset once the count
    reaches the threshold under PB_RF.  A count ``k <= 0`` (or no Dirty
    entry) drains nothing."""
    dirty_cnt = jnp.sum(dirty.astype(F))
    k_rf = jnp.where(dirty_cnt >= sc["deep_thr"][j],
                     dirty_cnt - sc["deep_pre"][j], 0.0)
    return dirty_cnt, jnp.where(scheme == 1, dirty_cnt, k_rf)


def _place(sc, j, scheme, rows, hpbc_j, batch: Batch, hop_stats):
    """Commit a batch into deep row ``j`` (switch j+2) and run its drain.

    ``rows`` holds the current (D, P) deep columns.  Returns ``(row
    updates dict, hpbc_j, hop_stats, dd_vals, ended, next Batch)``.  All
    packet addresses in a batch are distinct (each hop holds at most one
    Dirty entry per line), so coalesce matching is injective and Empty
    slots are assigned by rank without sequential scanning.  Placement
    mutations are gated on ``commit <= crash_at`` — a packet that
    commits after the power loss dies on the wire and must not clobber a
    surviving entry.
    """
    crash = sc["crash_at"]
    P = rows["dtag"].shape[1]
    slot_ids = jnp.arange(P, dtype=jnp.int32)
    slot_act = slot_ids < sc["deep_pbe"][j].astype(jnp.int32)
    act = batch.active
    any_act = jnp.any(act)

    arr = tb.add(batch.emit, sc["hop_ns"])
    starts, hpbc_j = channels.fifo_service(hpbc_j, arr, act,
                                           sc["pbc_occ_ns"])
    classify = tb.add(tb.add(starts, sc["pbc_proc_ns"]), sc["deep_tag"][j])
    commit = tb.add(classify, sc["deep_data"][j])

    # lazy-free observed once at the batch head (single settle point)
    t0 = jnp.where(any_act, tb.min(jnp.where(act, classify, tb.INF)),
                   tb.NEG)
    freed = (rows["dstate"][j] == DRAIN) & tb.le(rows["ddd"][j], t0)
    state0 = jnp.where(freed, EMPTY, rows["dstate"][j])

    co = act[:, None] & slot_act[None, :] \
        & (batch.addr[:, None] == rows["dtag"][j][None, :]) \
        & (state0 == DIRTY)[None, :]
    has_co = jnp.any(co, axis=1)
    alloc = act & ~has_co
    empty = slot_act & (state0 == EMPTY)
    erank = jnp.cumsum(empty.astype(jnp.int32)) - 1
    arank = jnp.cumsum(alloc.astype(jnp.int32)) - 1
    placed = alloc & (arank < jnp.sum(empty.astype(jnp.int32)))
    bypass = alloc & ~placed
    amat = placed[:, None] & empty[None, :] \
        & (arank[:, None] == erank[None, :])

    gate = tb.le(commit, crash)
    mat = (co | amat) & gate[:, None]
    upd = jnp.any(mat, axis=0)

    def pick(v, zero):
        # injective scatter: at most one packet row per slot column
        return jnp.sum(jnp.where(mat, v[:, None], zero), axis=0,
                       dtype=v.dtype)

    al = jnp.any(amat & gate[:, None], axis=0)
    co_upd = jnp.any(co & gate[:, None], axis=0)
    tag1 = jnp.where(al, pick(batch.addr, 0), rows["dtag"][j])
    state1 = jnp.where(al, DIRTY, state0)
    # Fan-in version ordering: with several leaves feeding this hop,
    # drains for one line can arrive out of version order (leaf A's v5
    # lands before leaf B's v3) — a coalesce keeps the *newest* of the
    # arriving and resident versions, and the owner follows whichever
    # version wins.  On a linear chain the per-hop per-line version
    # stream is monotone, so max(arriving, resident) == arriving and
    # this is bit-identical to the pre-fabric overwrite.
    ver_in = pick(batch.ver, 0)
    ver1 = jnp.where(al, ver_in,
                     jnp.where(co_upd,
                               jnp.maximum(ver_in, rows["dver"][j]),
                               rows["dver"][j]))
    keep_owner = co_upd & (rows["dver"][j] > ver_in)
    owner1 = jnp.where(upd & ~keep_owner, pick(batch.owner, 0),
                       rows["downer"][j])
    t_new = pick(commit, tb.ZERO)
    lru1 = jnp.where(upd, t_new, rows["dlru"][j])
    wt1 = jnp.where(upd, t_new, rows["dwt"][j])

    ended = has_co | placed            # packets that stop at this hop
    hop_stats = hop_stats.at[j + 1, H_FWD_CNT].add(
        jnp.sum((ended & gate).astype(F)))
    hop_stats = hop_stats.at[j + 1, H_FWD_SUM].add(
        jnp.sum(jnp.where(ended & gate,
                          tb.to_f64(tb.sub(commit, batch.emit)), 0.0)))
    hop_stats = hop_stats.at[j + 1, H_COALESCES].add(
        jnp.sum((has_co & gate).astype(F)))
    hop_stats = hop_stats.at[j + 1, H_BYPASS].add(
        jnp.sum((bypass & gate).astype(F)))

    # dd writeback: every committed packet acks its origin entry, gated
    # or not (a post-crash commit still yields a post-crash ack time —
    # exactly what keeps the origin entry alive through the crash)
    dd_vals = tb.add(commit, tb.mul(
        sc["hop_ns"], float(j + 2) - (batch.ohop.astype(F) + 1.0)))

    # this hop's own drain-down (evaluated once, after the batch settles)
    dirty = slot_act & (state1 == DIRTY)
    dirty_cnt, k = _drain_count(sc, j, scheme, dirty)
    key = jnp.where(dirty, lru1, tb.INF)
    rank = jnp.argsort(tb.argsort(key)).astype(F)
    to_drain = (rank < k) & dirty
    t_row = tb.maximum(
        tb.max(jnp.where(ended & gate, commit, tb.NEG)), tb.ZERO)
    state2 = jnp.where(to_drain, DRAIN, state1)

    # the drain-down set leaves in LRU order (the wire order the oracle
    # replays; downstream LRU stamps — and who bypasses a full hop —
    # depend on it)
    order = tb.argsort(key).astype(jnp.int32)
    nxt = Batch(
        active=jnp.concatenate([bypass, to_drain[order]]),
        addr=jnp.concatenate([batch.addr, tag1[order]]),
        ver=jnp.concatenate([batch.ver, ver1[order]]),
        owner=jnp.concatenate([batch.owner, owner1[order]]),
        emit=jnp.concatenate([jnp.where(bypass, classify, tb.ZERO),
                              jnp.broadcast_to(t_row, (P,))]),
        ohop=jnp.concatenate([batch.ohop,
                              jnp.full((P,), j + 1, jnp.int32)]),
        oslot=jnp.concatenate([batch.oslot, order]),
    )
    row = dict(dtag=tag1, dstate=state2, dlru=lru1, dver=ver1,
               downer=owner1, dwt=wt1)
    return row, hpbc_j, hop_stats, dd_vals, ended, nxt


def rows_of(st: MachineState) -> dict:
    """The deep-hop columns of the machine state as a mutable dict."""
    return dict(dtag=st.dtag, dstate=st.dstate, dlru=st.dlru, ddd=st.ddd,
                dver=st.dver, downer=st.downer, dwt=st.dwt)


def forward_chain(sc, scheme, rows, hpbc, hop_stats, batch: Batch, dd1,
                  pm_busy, pm_ver, *, n_banks: int, n_track: int):
    """Propagate a hop-1 drain batch down the whole chain.

    ``dd1`` is the hop-1 dd column the origin acks scatter into; ``rows``
    (see :func:`rows_of`) the deep columns the cascade threads through.
    Returns ``(dd1, rows, hpbc, hop_stats, pm_busy, pm_ver,
    n_pm_writes)``.  The loop is unrolled over the static deep row
    count; each iteration either commits the batch into its row
    (``row_live``, the traced depth covers it) or lands every packet at
    PM — selected per cell, so mixed depths share the program.
    """
    D = rows["dtag"].shape[0]
    rows = dict(rows)
    pm_writes = jnp.asarray(0.0, F)
    for j in range(D):
        row_live = (float(j) + 2.0) <= sc["n_switches"]
        row, hpbc_j, hs_place, ddv_p, ended, nxt = _place(
            sc, j, scheme, rows, hpbc[j], batch, hop_stats)
        pmb_l, pmv_l, ddv_l, n_l = _pm_land(
            sc, j + 1, batch, pm_busy, pm_ver, n_banks, n_track)
        # select: commit into the row vs write through to PM
        for kf, v in row.items():
            rows[kf] = rows[kf].at[j].set(
                jnp.where(row_live, v, rows[kf][j]))
        hpbc = hpbc.at[j].set(jnp.where(row_live, hpbc_j, hpbc[j]))
        hop_stats = jnp.where(row_live, hs_place, hop_stats)
        pm_busy = jnp.where(row_live, pm_busy, pmb_l)
        pm_ver = jnp.where(row_live, pm_ver, pmv_l)
        pm_writes = pm_writes + jnp.where(row_live, 0.0, n_l)
        dd_vals = jnp.where(row_live, ddv_p, ddv_l)
        dd_mask = batch.active & jnp.where(row_live, ended, True)
        dd1, rows["ddd"] = _scatter_dd(dd1, rows["ddd"], batch, dd_vals,
                                       dd_mask)
        batch = nxt._replace(active=jnp.where(row_live, nxt.active, False))
    # packets below the deepest allocated row write through to PM
    pmb_l, pmv_l, ddv_l, n_l = _pm_land(
        sc, D + 1, batch, pm_busy, pm_ver, n_banks, n_track)
    dd1, rows["ddd"] = _scatter_dd(dd1, rows["ddd"], batch, ddv_l,
                                   batch.active)
    return (dd1, rows, hpbc, hop_stats, pmb_l, pmv_l,
            pm_writes + n_l)


def grid_any(flag, axis_names: tuple):
    """``flag`` ORed over every cell of the grid: a ``lax.psum`` over the
    named ``vmap`` axes the cell runs under, which makes the result
    unbatched (a ``lax.cond`` on it stays a real branch); with no axes,
    the cell's own flag."""
    if not axis_names:
        return flag
    return jax.lax.psum(flag.astype(jnp.int32), axis_names) > 0


def would_drain(sc, scheme, rows):
    """Would any live deep row run its own drain-down on a call that
    brings it no packet?  Only when a threshold or preset changed since
    the row last settled (an epoch schedule): every call ends with each
    row drained down to what its counts allow."""
    D, P = rows["dtag"].shape
    slot_ids = jnp.arange(P, dtype=jnp.int32)
    fire = jnp.asarray(False)
    for j in range(D):
        row_live = (float(j) + 2.0) <= sc["n_switches"]
        dirty = (slot_ids < sc["deep_pbe"][j].astype(jnp.int32)) \
            & (rows["dstate"][j] == DIRTY)
        dirty_cnt, k = _drain_count(sc, j, scheme, dirty)
        fire = fire | (row_live & (dirty_cnt > 0.0) & (k > 0.0))
    return fire


def gated_chain(sc, scheme, rows, hpbc, hop_stats, batch: Batch, dd1,
                pm_busy, pm_ver, *, sends, axis_names: tuple,
                n_banks: int, n_track: int):
    """:func:`forward_chain` behind a grid-wide gate.

    ``sends`` is the cell's traced "this leg is mine to run" (a live
    buffered persist in a chain cell).  The cell *wants* the leg when it
    sends and the leg has work: an active packet in ``batch`` or a live
    row that would drain down unprompted (:func:`would_drain`).  The
    leg runs when some cell of the grid wants it (:func:`grid_any` over
    ``axis_names``); otherwise a ``lax.cond`` returns the inputs as they
    are, with 0.0 PM writes.  Returns ``forward_chain``'s outputs and
    the cell's want.

    Exact, because a call with no active packet and no firing row is
    the identity, bit for bit:

      * ``_place``: ``fifo_service`` leaves ``hpbc`` as it was; ``t0 =
        NEG`` frees nothing (a Drain entry's ack is a time >= 0);
        ``co``, ``amat``, ``mat`` and ``upd`` are all false, so each row
        column is selected back; ``to_drain`` is empty as no row fires;
        the ``hop_stats`` adds are ``+0.0`` to non-negative sums; the
        next row's batch is all inactive;
      * ``_pm_land``: ``maximum(pm_busy, 0)`` on times >= 0 and a masked
        ``max`` on versions >= 0, 0.0 writes;
      * ``_scatter_dd``: every mask is false.

    A cell that does not send has its leg output discarded by its
    caller (the op switch, ``handlers.by_scheme`` or ``where(is_chain,
    ...)``), so running or skipping the leg for it changes nothing.
    """
    want = sends & (jnp.any(batch.active) | would_drain(sc, scheme, rows))

    def run(a):
        rows, hpbc, hop_stats, dd1, pm_busy, pm_ver = a
        return forward_chain(sc, scheme, rows, hpbc, hop_stats, batch, dd1,
                             pm_busy, pm_ver, n_banks=n_banks,
                             n_track=n_track)

    def skip(a):
        rows, hpbc, hop_stats, dd1, pm_busy, pm_ver = a
        return (dd1, rows, hpbc, hop_stats, pm_busy, pm_ver,
                jnp.asarray(0.0, F))

    out = jax.lax.cond(grid_any(want, axis_names), run, skip,
                       (rows, hpbc, hop_stats, dd1, pm_busy, pm_ver))
    return out, want


def deep_read(sc, st: MachineState, addr, t):
    """Read-forwarding checks below hop 1 (shallowest live entry wins).

    Returns ``(hit, resp, dlru', hop_row)`` — whether any deep hop can
    serve the read, the response time at the core, the LRU columns with
    the serving entry touched, and the serving row index (for the
    per-hop read-hit telemetry).  An entry is visible only once its
    commit time has passed (``dwt <= t``) and servable under the same
    Dirty-or-late-Drain rule as hop 1.
    """
    D = st.dtag.shape[0]
    P = st.dtag.shape[1]
    slot_ids = jnp.arange(P, dtype=jnp.int32)
    hit = jnp.zeros((D,), bool)
    idxs = jnp.zeros((D,), jnp.int32)
    # every row's arrival and response as one vector operation each
    # (row j is switch j+2, j+1 hops below hop 1)
    path = tb.mul(sc["hop_ns"], jnp.arange(1, D + 1))
    arr = tb.add(tb.add(t, sc["ow_cpu_sw1"]), path)
    resp = tb.add(tb.add(tb.add(tb.add(tb.add(
        arr, sc["pbc_read_ns"]), sc["deep_tag"][:D]),
        sc["deep_data"][:D]), sc["ow_cpu_sw1"]), path)
    late = tb.add(arr, sc["fwd_margin"])
    for j in range(D):
        row_live = (float(j) + 2.0) <= sc["n_switches"]
        slot_act = slot_ids < sc["deep_pbe"][j].astype(jnp.int32)
        live = slot_act & (st.dtag[j] == addr) \
            & (st.dstate[j] != EMPTY) & tb.le(st.dwt[j], t)
        served = live & ((st.dstate[j] == DIRTY)
                         | ((st.dstate[j] == DRAIN)
                            & tb.gt(st.ddd[j], late[j])))
        has = jnp.any(served) & row_live
        # a Dirty entry supersedes a late-Drain one (same rule as the
        # hop-1 pb_lookup: the Dirty copy is the newer version)
        sd = served & (st.dstate[j] == DIRTY)
        idx = jnp.where(jnp.any(sd), jnp.argmax(sd),
                        jnp.argmax(served)).astype(jnp.int32)
        hit = hit.at[j].set(has)
        idxs = idxs.at[j].set(idx)
    first = jnp.argmax(hit)                       # shallowest serving hop
    any_hit = jnp.any(hit)
    dlru = st.dlru
    for j in range(D):
        serve_j = any_hit & (first == j)
        dlru = dlru.at[j, idxs[j]].set(
            jnp.where(serve_j, t, dlru[j, idxs[j]]))
    return any_hit, resp[first], dlru, first
