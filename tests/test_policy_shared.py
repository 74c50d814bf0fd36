"""The persist decisions the slot-at-a-time handlers and the macro replay
share: the PB_RF drain-down count (``policy.rf_drain_k``), the Empty leg
of slot selection (``policy.empty_slot``), the SLO tightening
(``policy.slo_over`` / ``policy.slo_tight``) and the per-persist stats row
(``state.persist_stats``).

Each is checked against what it stands for: the drain-down count against
its host twin ``params.rf_drain_count``, the Empty leg against
``select_slot``, and the stats row against the columns a persist adds.
That the replay and the handlers stay bit-equal through these functions
is pinned end to end by ``tests/test_macro.py``, ``tests/test_macro_gate.py``
and the crash differential.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import policy
from repro.core.engine import timebase as tb
from repro.core.engine.state import (DIRTY, DRAIN, EMPTY, N_LAT_BINS,
                                     N_STATS, S_ACKED, S_COALESCES,
                                     S_DURABLE, S_LAT_HIST0, S_PBCQ_SUM,
                                     S_PERSIST_CNT, S_PERSIST_SUM,
                                     S_PM_WRITES, S_READ_CNT, S_READ_SUM,
                                     S_SLO_OVER, S_STALL_TIME, S_VICTIM_CNT,
                                     lat_bin, persist_stats)
from repro.core.params import rf_drain_count

D, E, R = DIRTY, EMPTY, DRAIN
# eight PBE slots, the last one outside the live window (n_pbe = 7)
ACTIVE = np.array([True] * 7 + [False])
OWNER = np.array([0, 1, 0, 1, 0, 0, 1, 0], np.int8)
# one Empty live slot (<= empty_slack: the low-water leg fires) or four
STATES = {True: np.array([D, D, D, R, D, E, D, E], np.int8),
          False: np.array([D, E, D, E, E, E, D, D], np.int8)}


def _drain_sc():
    return {"drain_scope": 0.0, "threshold_count": 4.0, "preset_count": 2.0,
            "t_threshold": np.array([2.0, 5.0]),
            "t_preset": np.array([1.0, 3.0]),
            "low_water": 2.0, "empty_slack": 1.0}


@pytest.mark.parametrize("low", [True, False], ids=["low", "no_low"])
@pytest.mark.parametrize("tight", [True, False], ids=["tight", "loose"])
@pytest.mark.parametrize("scoped", [True, False], ids=["scoped", "global"])
def test_rf_drain_k_is_host_twin(scoped, tight, low):
    sc = _drain_sc()
    sc["drain_scope"] = float(scoped)
    state, tenant = STATES[low], 0
    with jax.enable_x64(True):
        mask, k = policy.rf_drain_k(
            {key: jnp.asarray(v) for key, v in sc.items()},
            jnp.asarray(state), jnp.asarray(ACTIVE), jnp.asarray(OWNER),
            jnp.asarray(tenant), jnp.asarray(tight))
    in_scope = OWNER == tenant if scoped else np.ones(8, bool)
    want_mask = (state == D) & ACTIVE & in_scope
    thr = sc["t_threshold"][tenant] if scoped else sc["threshold_count"]
    pre = sc["t_preset"][tenant] if scoped else sc["preset_count"]
    if tight:
        thr, pre = 1, 0
    want_k = rf_drain_count(
        int(want_mask.sum()), int(((state == E) & ACTIVE).sum()), thr, pre,
        low_water=int(sc["low_water"]), empty_slack=int(sc["empty_slack"]))
    np.testing.assert_array_equal(np.asarray(mask), want_mask)
    assert float(k) == want_k


@pytest.mark.parametrize("quota,state", [
    (8.0, STATES[False]),       # under quota, Empty slots free
    (2.0, STATES[False]),       # tenant 0 holds 2 live entries: closed
    (8.0, np.array([D, R, D, D, D, D, D, E], np.int8)),   # no live Empty
], ids=["under", "over", "full"])
def test_empty_slot_is_select_slot_empty_leg(quota, state):
    sc = {"quota": np.array([quota, 8.0]), "share": np.array([8.0, 8.0]),
          "victim_weighted": 0.0}
    lru = tb.from_host(np.array([50., 40., 30., 20., 10., 60., 70., 5.]))
    dd = tb.from_host(np.full(8, 100.0))
    with jax.enable_x64(True):
        args = [jnp.asarray(a) for a in (state, ACTIVE, lru)]
        sc_t = {key: jnp.asarray(v) for key, v in sc.items()}
        occ = policy.tenant_occupancy(args[0], args[1], jnp.asarray(OWNER),
                                      2)
        over, any_empty, idx = policy.empty_slot(sc_t, *args,
                                                 jnp.asarray(0), occ)
        sel = policy.select_slot(sc_t, *args, jnp.asarray(dd),
                                 jnp.asarray(OWNER), jnp.asarray(0), occ)
    live = (state != E) & ACTIVE
    np.testing.assert_array_equal(
        np.asarray(occ), [np.sum(live & (OWNER == t)) for t in (0, 1)])
    assert bool(over) == (np.sum(live & (OWNER == 0)) >= quota)
    assert (bool(any_empty), int(idx)) == (bool(sel[0]), int(sel[1]))
    free = (state == E) & ACTIVE & (not bool(over))
    assert bool(any_empty) == bool(free.any())
    if free.any():
        assert int(idx) == int(np.argmin(np.where(free, tb.to_host(lru),
                                                  np.inf)))


@pytest.mark.parametrize("lat,target,tol,over_before,want_over,want_tight", [
    (900.0, 500.0, 0.1, 0.0, 1.0, True),
    (900.0, 500.0, 0.2, 0.0, 1.0, False),   # 1 of 5 persists, this one too
    (400.0, 500.0, 0.1, 0.0, 0.0, False),
    (400.0, 1e30, 0.1, 3.0, 0.0, True),     # no target: over stays 0
], ids=["over", "at_tolerance", "under", "no_target"])
def test_slo_tight_counts_this_persist(lat, target, tol, over_before,
                                       want_over, want_tight):
    row = np.zeros(N_STATS)
    row[S_PERSIST_CNT], row[S_SLO_OVER] = 4.0, over_before
    sc = {"lat_target": tb.from_host(target), "lat_tol": np.float64(tol)}
    with jax.enable_x64(True):
        over = policy.slo_over(sc, jnp.asarray(tb.from_host(lat)))
        tight = policy.slo_tight(sc, jnp.asarray(row), over)
    assert (float(over), bool(tight)) == (want_over, want_tight)


def _row_after(cols, vals, stats):
    cols, vals = np.asarray(cols), np.asarray(vals)
    assert len(set(cols.tolist())) == len(cols)   # one add per column
    with jax.enable_x64(True):
        return np.asarray(jnp.asarray(stats).at[0, cols].add(vals))


@pytest.mark.parametrize("leg", ["nopb", "buffered", "masked"])
def test_persist_stats_row(leg):
    rng = np.random.default_rng(7)
    stats = np.round(rng.uniform(0.0, 50.0, (2, N_STATS)))
    lat = 1234.5
    want = np.zeros(N_STATS)
    want[[S_PERSIST_SUM, S_PERSIST_CNT, S_SLO_OVER, S_PM_WRITES, S_ACKED,
          S_DURABLE]] = [lat, 1.0, 1.0, 1.0, 1.0, 0.0]
    want[S_LAT_HIST0 + int(lat_bin(lat))] = 1.0
    with jax.enable_x64(True):
        if leg == "nopb":
            cols, vals = persist_stats(jnp.float64(lat), 1.0, 1.0, True,
                                       False)
        elif leg == "buffered":
            extra = ((S_VICTIM_CNT, True), (S_PBCQ_SUM, 7.0),
                     (S_COALESCES, False), (S_STALL_TIME, 3.0))
            for col, v in extra:
                want[col] = float(v)
            cols, vals = persist_stats(jnp.float64(lat), 1.0, 1.0, True,
                                       False, extra=extra)
        else:
            # a masked replay lane: garbage latency, every value off
            cols, vals = persist_stats(
                jnp.float64(-3.0e300), 1.0, 1.0, True, True,
                on=jnp.asarray(False),
                extra=((S_READ_SUM, 0.0), (S_READ_CNT, 0.0),
                       (S_PBCQ_SUM, 0.0)))
            want[:] = 0.0
    after = _row_after(cols, vals, stats)
    assert 0 <= np.asarray(cols).min() and np.asarray(cols).max() \
        < S_LAT_HIST0 + N_LAT_BINS
    # adds of exact values onto whole numbers: the row is exact
    assert np.array_equal(after[0].view(np.int64),
                          (stats[0] + want).view(np.int64))
    assert np.array_equal(after[1].view(np.int64), stats[1].view(np.int64))
