"""Pluggable PB policy layer: allocation, victim selection, drain policies.

This module is the *single home* of the persistence-policy logic that was
previously restated informally in three places — the timed scan, the
untimed oracle (``core.semantics``) and the checkpoint tier
(``persistence.manager``).  It provides:

  * the canonical scheme names / drain-threshold constants (re-exported
    from ``core.params`` so every layer reads one definition);
  * :func:`rf_drain_count` — the PB_RF threshold/preset + keep-one-free
    drain decision as a pure scalar function, used verbatim by the
    untimed oracle; its traced twin is :func:`rf_drain_k`;
  * the traced policy pieces of the timed engine: PB lookup
    (:func:`pb_lookup`), Empty/victim/earliest-Drain slot selection
    (:func:`empty_slot`, :func:`select_slot`), the serving-SLO
    tightening (:func:`slo_over`, :func:`slo_tight`) and the per-scheme
    drain policies (:func:`drain_immediate`,
    :func:`drain_threshold_preset`).

Each decision is made by one function here, called by the slot-at-a-time
handlers and by the macro replay (``engine.macro``) alike, so the two
paths compute it with the same operations in the same order.  All traced
functions are written against the carry arrays of
``engine.state.MachineState``.
"""
from __future__ import annotations

import jax.numpy as jnp

# Canonical scalar policy: defined once in the jax-free dependency leaf
# (core.params) so the untimed oracle and the checkpoint tier can import
# it without initializing jax; re-exported here as the policy facade.
from repro.core.params import (DEFAULT_DRAIN_PRESET,          # noqa: F401
                               DEFAULT_DRAIN_THRESHOLD, RF_EMPTY_SLACK,
                               RF_LOW_WATER_DRAINS, SCHEME_NAMES, Scheme,
                               preset_count, rf_drain_count,
                               threshold_count)
from repro.core.engine import timebase as tb
from repro.core.engine.state import (DIRTY, DRAIN, EMPTY, S_PERSIST_CNT,
                                     S_SLO_OVER)


# ---------------------------------------------------------------------------
# Traced policy pieces (operate on MachineState arrays)
# ---------------------------------------------------------------------------

def lazy_free(state, dd, now):
    """Observe Drain->Empty transitions whose PM ack time has passed."""
    freed = (state == DRAIN) & tb.le(dd, now)
    return jnp.where(freed, EMPTY, state)


def pb_lookup(tag, state, slot_active, addr):
    """Newest live entry for ``addr`` (a Dirty entry supersedes Drain).

    Returns (has_entry, idx): whether any live entry matches, and the
    index of the newest one.
    """
    match = slot_active & (tag == addr) & (state != EMPTY)
    has = jnp.any(match)
    idx = jnp.argmax(match & (state == DIRTY)) * jnp.any(
        match & (state == DIRTY)) + jnp.argmax(match) * (
        ~jnp.any(match & (state == DIRTY)))
    return has, idx


def tenant_occupancy(state, slot_active, owner, n_tenants_max: int):
    """Per-tenant live-PBE counts: ``occ[t]`` = non-Empty entries owned
    by tenant ``t`` (the quota / weighted-victim accounting base)."""
    mine = (jnp.clip(owner, 0, n_tenants_max - 1)[None, :]
            == jnp.arange(n_tenants_max)[:, None])
    return jnp.sum(jnp.where(slot_active & (state != EMPTY) & mine,
                             1.0, 0.0), axis=1)


def empty_slot(sc, state, slot_active, lru, tenant, occ):
    """The Empty leg of :func:`select_slot`: the LRU-oldest Empty slot,
    closed to a tenant at or over its quota (``occ[tenant] >=
    sc["quota"][tenant]``).  Returns ``(over_quota, any_empty,
    empty_idx)``."""
    over_quota = occ[tenant] >= sc["quota"][tenant]
    empty_mask = slot_active & (state == EMPTY) & ~over_quota
    return (over_quota, jnp.any(empty_mask),
            tb.argmin(jnp.where(empty_mask, lru, tb.INF)))


def select_slot(sc, state, slot_active, lru, dd, owner, tenant, occ):
    """Allocation / victim selection over the PBE array (AllocPolicy).

    Preference order of the persist handler: an Empty slot (LRU-oldest),
    else the LRU Dirty entry (victim drain), else the Drain entry whose
    PM ack lands earliest (pure wait) — refined by the traced
    :class:`~repro.core.params.AllocPolicy` lowering:

      * **quota** — a tenant at/over its quota (``occ[tenant] >=
        sc["quota"][tenant]``) may not take an Empty slot; its victim /
        wait candidates are restricted to its *own* entries, so it
        recycles its own footprint instead of growing it;
      * **weighted victim** — when no Empty slot exists and
        ``sc["victim_weighted"]`` is set, the victim search prefers
        Dirty entries of tenants at/over their share
        (``occ >= sc["share"]``), falling back to the global LRU Dirty.

    With the default policy (quota INF, weighted 0) every mask reduces
    to the pre-policy form, keeping results bit-identical.
    """
    T = occ.shape[0]
    over_quota, any_empty, empty_idx = empty_slot(sc, state, slot_active,
                                                  lru, tenant, occ)
    own = owner == tenant
    dirty_all = slot_active & (state == DIRTY)
    over_share = occ >= sc["share"]                       # (T,) bool
    hot = dirty_all & over_share[jnp.clip(owner, 0, T - 1)]
    use_hot = (sc["victim_weighted"] > 0.0) & jnp.any(hot)
    dirty_mask = jnp.where(over_quota, dirty_all & own,
                           jnp.where(use_hot, hot, dirty_all))
    any_dirty = jnp.any(dirty_mask)
    victim_idx = tb.argmin(jnp.where(dirty_mask, lru, tb.INF))
    drain_all = slot_active & (state == DRAIN)
    drain_mask = jnp.where(over_quota, drain_all & own, drain_all)
    earliest_idx = tb.argmin(jnp.where(drain_mask, dd, tb.INF))
    return any_empty, empty_idx, any_dirty, victim_idx, earliest_idx


def drain_immediate(sc, bank, slot_ids, wslot, t_written,
                    state3, dd3, pm_busy1):
    """PB scheme: drain the just-written entry at once (ack at switch).

    The channel FIFO preserves the version order of same-line drains.
    Returns (state4, dd4, pm_busy2, policy_writes).
    """
    pm_start2 = tb.maximum(pm_busy1[bank],
                           tb.add(t_written, sc["ow_sw1_pm"]))
    write_end, bank_free = tb.add(
        pm_start2, jnp.stack([sc["nvm_write"], sc["nvm_w_occ"]]))
    dd_new = tb.add(write_end, sc["ow_sw1_pm"])
    state4 = jnp.where(slot_ids == wslot, DRAIN, state3)
    dd4 = dd3.at[wslot].set(dd_new)
    pm_busy2 = pm_busy1.at[bank].set(bank_free)
    return state4, dd4, pm_busy2, jnp.asarray(1.0, jnp.float64)


def surviving_entries(state, dd, slot_active, crash_at):
    """Mask of PBEs that survive a power loss at ``crash_at``.

    A Dirty entry always survives (the PB cells are persistent).  A
    Drain entry survives iff its in-flight PM write is lost with the
    power, i.e. its ack would have landed only after the crash; an ack
    at or before the crash means the write reached PM and the entry is
    (lazily) Empty at the crash instant.
    """
    return slot_active & ((state == DIRTY) |
                          ((state == DRAIN) & tb.gt(dd, crash_at)))


def recovery_burst_cost(sc, per_bank, n):
    """Drain-all burst latency over aggregated per-bank survivor counts.

    Drains sharing a PM bank serialize at the bank's write occupancy
    and overlap across banks (the same burst model as
    :func:`drain_threshold_preset`); under a switch chain the counts
    aggregate the *union* of surviving entries across every hop, all
    re-drained in one recovery burst over the hop-1 drain path (the
    conservative longest path — deeper hops are strictly closer to PM).
    Latency is the time until the last re-drain is acked back at the
    switch, zero when nothing survived.
    """
    worst = jnp.max(per_bank)
    return jnp.where(
        n > 0,
        tb.add(tb.add(tb.mul(sc["nvm_w_occ"], jnp.maximum(worst - 1.0, 0.0)),
                      sc["nvm_write"]),
               tb.mul(sc["ow_sw1_pm"], 2)),
        tb.ZERO)


def slo_over(sc, lat):
    """1.0 where a persist's latency ``lat`` (a time word) is over its
    tenant's target (``DrainPolicy.latency_target_ns``), else 0.0.  With
    no target the lowered scalar is INF and this is always 0.0."""
    return tb.gt(lat, sc["lat_target"]).astype(jnp.float64)


def slo_tight(sc, stats_row, over):
    """The serving-SLO override of the drain-down: whether the tenant's
    running over-target fraction, *including this persist* (its
    :func:`slo_over` value ``over``), exceeds its tolerance.
    ``stats_row`` is the tenant's stats row before the persist."""
    cnt1 = stats_row[S_PERSIST_CNT] + 1.0
    over1 = stats_row[S_SLO_OVER] + over
    return over1 > sc["lat_tol"] * cnt1


def rf_drain_k(sc, state, slot_active, owner, tenant, tight):
    """PB_RF's drain-down decision: the Dirty entries in scope and how
    many of them (``k``, LRU first) drain now.  Returns ``(dirty_mask,
    k)``.  Traced twin of :func:`params.rf_drain_count
    <repro.core.params.rf_drain_count>`.

    Under a tenant-scoped :class:`~repro.core.params.DrainPolicy`
    (``sc["drain_scope"]`` set) the drain-down sees only the issuing
    tenant's Dirty entries and compares against *its* lowered counts
    (``sc["t_threshold"]/["t_preset"]``, anchored on its quota or fair
    share) — a noisy tenant's drain-down can no longer evict a quiet
    tenant's Dirty entries.  The keep-one-free low-water heuristic keeps
    watching the *global* Empty pool (it protects the shared PI front)
    but likewise drains only in-scope entries.

    ``tight`` (a traced bool, :func:`slo_tight`) runs the drain-down
    with threshold 1 / preset 0 — drain every in-scope Dirty entry ASAP
    so the next tail persist does not queue behind a full PB.
    """
    scoped = sc["drain_scope"] > 0.0
    in_scope = jnp.where(scoped, owner == tenant, True)
    dirty_mask = (state == DIRTY) & slot_active & in_scope
    dirty_cnt = jnp.sum(dirty_mask)
    empty_cnt = jnp.sum((state == EMPTY) & slot_active)
    thr = jnp.where(scoped, sc["t_threshold"][tenant],
                    sc["threshold_count"])
    pre = jnp.where(scoped, sc["t_preset"][tenant], sc["preset_count"])
    thr = jnp.where(tight, 1.0, thr)
    pre = jnp.where(tight, 0.0, pre)
    do_drain = dirty_cnt >= thr
    k_thresh = jnp.where(do_drain, dirty_cnt - pre, 0.0)
    k_low = jnp.where(empty_cnt <= sc["empty_slack"],
                      jnp.minimum(sc["low_water"], dirty_cnt),
                      0.0)
    return dirty_mask, jnp.maximum(k_thresh, k_low)


def drain_threshold_preset(sc, n_banks, slot_active, t_written,
                           state3, tag3, lru3, dd3, pm_busy1, *,
                           owner, tenant, tight, defer=None):
    """PB_RF: threshold/preset drain-down over LRU Dirty entries.

    The :func:`rf_drain_k` decision plus the per-bank burst
    serialization: drains sharing a PM bank are issued back-to-back at
    the bank's write occupancy, overlapping across banks.

    ``defer`` (a traced bool, or None to skip) is the fabric's
    backpressure override (``FabricTopology.bp_high``): while the
    downstream spine FIFO is congested the whole drain-down — both the
    threshold leg and the keep-one-free low-water leg — is deferred
    (``k = 0``); the Dirty entries stay put and the next persist
    re-evaluates.  A never-true ``defer`` (bp_high = INF) is bit-exact
    with ``defer=None``.  Returns (state4, dd4, pm_busy2,
    policy_writes).
    """
    B = n_banks
    dirty_mask, k = rf_drain_k(sc, state3, slot_active, owner, tenant,
                               tight)
    if defer is not None:
        k = jnp.where(defer, 0.0, k)
    key = jnp.where(dirty_mask, lru3, tb.INF)
    rank = jnp.argsort(tb.argsort(key)).astype(jnp.float64)
    to_drain = (rank < k) & dirty_mask
    banks = tag3 % B
    # rank among drained entries sharing a bank (serializes the burst per
    # PM bank, overlapping across banks)
    same_bank = banks[:, None] == banks[None, :]
    earlier = rank[None, :] < rank[:, None]
    rank_b = jnp.sum(
        (same_bank & earlier & to_drain[None, :]).astype(jnp.float64),
        axis=1)
    start_i = tb.add(tb.maximum(pm_busy1[banks],
                                tb.add(t_written, sc["ow_sw1_pm"])),
                     tb.mul(sc["nvm_w_occ"], rank_b))
    dd_j = tb.add(tb.add(start_i, sc["nvm_write"]), sc["ow_sw1_pm"])
    state4 = jnp.where(to_drain, DRAIN, state3)
    dd4 = jnp.where(to_drain, dd_j, dd3)
    busy_after = jnp.where(to_drain, tb.add(start_i, sc["nvm_w_occ"]),
                           tb.ZERO)
    per_bank = tb.max(
        jnp.where(same_bank & to_drain[None, :], busy_after[None, :],
                  tb.ZERO),
        axis=1)
    pm_busy2 = tb.maximum(
        pm_busy1, jnp.zeros((B,), tb.DTYPE).at[banks].max(per_bank))
    return state4, dd4, pm_busy2, k
