"""Switch chains with per-switch persistent buffers (pooling topologies).

Covers the acceptance properties of the chain promotion:
  (a) depth-1 results are bit-exact against the pre-chain engine — both
      standalone (the chain code is skipped at trace time) and as cells
      inside a mixed-depth grid (the per-field chain selects reduce to
      the identity), the PR 4 legacy-compat guard style;
  (b) a mixed {workload x scheme x depth 1..4 x policy} sweep compiles
      as ONE XLA program (depth, per-hop capacities and policies are
      traced);
  (c) the fig1 depth sweep emits the right series shapes — NoPB at
      every depth (0 = direct attach included), PB schemes only at
      depth >= 1;
  (d) per-hop stats rows follow the PR 3 NaN convention: a hop that saw
      zero traffic has NaN mean forward latency (never 0.0) and the
      figure scripts skip it;
  (e) ``pbe_per_hop`` construction-time validation;
  (f) the chain-leg gate's identity: a ``forward_chain`` call with no
      active packet and no row above its drain counts returns its
      inputs bit for bit, and a threshold lowered mid-run (the one case
      where a row drains down unprompted) keeps the gated engine equal
      to the ungated one.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_BUCKET
from repro.core import (AllocPolicy, DrainPolicy, PBPolicy, PCSConfig,
                        Schedule, Scheme, make_trace, simulate,
                        simulate_grid)
from repro.core.engine import chain, compile_count
from repro.core.engine import timebase as tb
from repro.core.engine.state import DIRTY, lower_scalars, scalars_from_config
from repro.core.engine.step import scan_cell

COUNT_FIELDS = ("persists", "pm_reads", "read_hits", "coalesces",
                "pm_writes", "pi_detours", "victim_drains",
                "acked_persists", "durable_persists", "recovery_entries")
FLOAT_FIELDS = ("runtime_ns", "persist_lat_ns", "read_lat_ns", "stall_ns",
                "recovery_ns")


def _assert_bit_exact(a, b, label):
    for f in COUNT_FIELDS:
        assert getattr(a, f) == getattr(b, f), (label, f)
    for f in FLOAT_FIELDS:
        va, vb = getattr(a, f), getattr(b, f)
        if math.isnan(va) and math.isnan(vb):
            continue
        assert va == vb, (label, f, va, vb)


@pytest.fixture(scope="module")
def chain_trace():
    return make_trace("radiosity", persist_budget=150)


# ---------------------------------------------------------------------------
# (a) depth-1 legacy-compat: bit-exact inside a mixed-depth grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", [Scheme.NOPB, Scheme.PB, Scheme.PB_RF])
def test_depth1_bit_exact_inside_mixed_depth_grid(chain_trace, scheme):
    """A depth-1 cell inside a grid that allocates deep-hop rows must
    reproduce its standalone (chain-free program) result bit-exactly:
    the chain promotion may not perturb single-switch behaviour."""
    cfg = PCSConfig(scheme=scheme)
    ref = simulate(chain_trace, cfg, bucket=TINY_BUCKET)
    cells = simulate_grid(
        [chain_trace],
        [cfg, PCSConfig(scheme=Scheme.PB_RF, n_switches=4)],
        bucket=TINY_BUCKET)[0]
    _assert_bit_exact(cells[0], ref, scheme.name)


def test_depth1_crash_cell_bit_exact_in_mixed_depth_grid(chain_trace):
    """Same guard under a crash point (the durability snapshot rides the
    chain-aware recovery pass)."""
    t_end = simulate(chain_trace, PCSConfig(scheme=Scheme.PB_RF),
                     bucket=TINY_BUCKET).runtime_ns
    cfg = PCSConfig(scheme=Scheme.PB_RF).with_crash(0.4 * t_end)
    ref = simulate(chain_trace, cfg, bucket=TINY_BUCKET, track_addrs=8)
    cells = simulate_grid(
        [chain_trace],
        [cfg, PCSConfig(scheme=Scheme.PB, n_switches=3).with_crash(
            0.4 * t_end)],
        bucket=TINY_BUCKET, track_addrs=8)[0]
    _assert_bit_exact(cells[0], ref, "crash")
    assert (cells[0].durable_ver == ref.durable_ver).all()


# ---------------------------------------------------------------------------
# (b) one-program mixed {workload x scheme x depth x policy} sweep
# ---------------------------------------------------------------------------

def test_mixed_depth_policy_sweep_single_compile(chain_trace):
    tr2 = make_trace("raytrace", persist_budget=150)
    pol = PBPolicy(alloc=AllocPolicy(victim="weighted"))
    configs = []
    for scheme in (Scheme.PB, Scheme.PB_RF):
        for d in (1, 2, 3, 4):
            configs.append(PCSConfig(scheme=scheme, n_switches=d))
            configs.append(PCSConfig(scheme=scheme, n_switches=d,
                                     policy=pol))
    configs.append(PCSConfig(scheme=Scheme.NOPB, n_switches=2))
    c0 = compile_count()
    cells = simulate_grid([chain_trace, tr2], configs, bucket=TINY_BUCKET)
    assert compile_count() - c0 == 1, (
        "a mixed {workload x scheme x depth x policy} sweep must lower "
        "to ONE XLA program")
    for row in cells:
        for cfg, r in zip(configs, row):
            assert r.persists > 0, cfg
            if cfg.scheme != Scheme.NOPB:
                assert r.n_hops == cfg.n_switches
                assert len(r.hop_results()) == cfg.n_switches


# ---------------------------------------------------------------------------
# (c) fig1 series shapes: NoPB at every depth, PB only at depth >= 1
# ---------------------------------------------------------------------------

def test_fig1_depth_sweep_series_shapes():
    from benchmarks.fig1_switch_depth import DEPTHS, plan

    labels, configs = plan()
    nopb = [(n, c) for (k, n, _), c in zip(labels, configs) if k == "nopb"]
    pb = [(k, n) for (k, n, _), c in zip(labels, configs) if k != "nopb"]
    # NoPB must appear at EVERY depth, 0 (direct attach) included
    assert [n for n, _ in nopb] == list(DEPTHS)
    assert all(c.scheme == Scheme.NOPB for _, c in nopb)
    # PB schemes only where a switch exists to host the buffer
    assert all(n >= 1 for _, n in pb)
    for key in ("pb", "pb_rf"):
        assert sorted(n for k, n in pb if k == key) == [
            n for n in DEPTHS if n >= 1]


def test_fig1_rows_cover_every_depth_and_skip_nan_hops(monkeypatch):
    """End-to-end shape regression on the emitted rows: one latency row
    per (scheme, depth) with NoPB at every depth, and no NaN per-hop
    row ever emitted."""
    from benchmarks import _shared, fig1_switch_depth

    monkeypatch.setattr(_shared, "SMOKE", True, raising=False)
    rows = fig1_switch_depth.run(depths=(0, 1, 2))
    names = [r[0] for r in rows]
    for n in (0, 1, 2):
        assert f"fig1_nopb_n{n}" in names
    for key in ("pb", "pb_rf"):
        assert f"fig1_{key}_n0" not in names
        for n in (1, 2):
            assert f"fig1_{key}_n{n}" in names
            # crashed replicas attribute survivors to each hop
            assert f"fig1_recov_{key}_n{n}_h1" in names
    for name, value, _ in rows:
        assert not (isinstance(value, float) and math.isnan(value)), name


# ---------------------------------------------------------------------------
# (d) NaN convention for per-hop rows (zero-traffic deep hops)
# ---------------------------------------------------------------------------

def test_deep_hops_with_zero_traffic_report_nan_not_zero(chain_trace):
    """A chain deep enough that traffic never reaches its tail: the
    per-hop mean forward latency is NaN (no traffic has no latency,
    not an infinitely fast one), and counts are 0."""
    # PB_RF with a roomy hop 1 under a light load: the drain-down never
    # triggers, so nothing is ever forwarded below hop 1
    cfg = PCSConfig(scheme=Scheme.PB_RF, n_switches=3,
                    pbe_per_hop=(256, 4, 4))
    r = simulate(make_trace("volrend_npl", persist_budget=40), cfg,
                 bucket=TINY_BUCKET)
    hops = r.hop_results()
    assert len(hops) == 3
    assert hops[0]["commits"] > 0 and not math.isnan(hops[0]["fwd_lat_ns"])
    for h in hops[1:]:
        assert h["commits"] == 0, h
        assert math.isnan(h["fwd_lat_ns"]), (
            "zero-traffic hop must report NaN, not a 0.0 ns mean")


# ---------------------------------------------------------------------------
# (e) construction-time validation
# ---------------------------------------------------------------------------

def test_pbe_per_hop_arity_must_match_depth():
    with pytest.raises(ValueError, match="one per switch"):
        PCSConfig(scheme=Scheme.PB, n_switches=2, pbe_per_hop=(4, 4, 4))


def test_pbe_per_hop_entries_positive():
    with pytest.raises(ValueError, match=">= 1"):
        PCSConfig(scheme=Scheme.PB, n_switches=2, pbe_per_hop=(4, 0))


def test_pbe_per_hop_rejected_for_nopb():
    with pytest.raises(ValueError, match="NOPB"):
        PCSConfig(scheme=Scheme.NOPB, n_switches=2, pbe_per_hop=(4, 4))


def test_pbe_per_hop_syncs_hop1_capacity():
    cfg = PCSConfig(scheme=Scheme.PB_RF, n_switches=3, pbe_per_hop=(8, 4, 2))
    assert cfg.n_pbe == 8
    assert cfg.hop_pbes == (8, 4, 2)
    assert cfg.max_hop_pbe == 8
    # defaulting: every hop inherits n_pbe
    assert PCSConfig(scheme=Scheme.PB, n_switches=2, n_pbe=4).hop_pbes \
        == (4, 4)


# ---------------------------------------------------------------------------
# (f) the chain-leg gate: an empty call is the identity
# ---------------------------------------------------------------------------

D_ID = 3         # deep rows: chains of up to 4 switches
PBE_ID = 16


@functools.lru_cache(maxsize=None)
def _state_cell(n_steps):
    return jax.jit(functools.partial(
        scan_cell, max_pbe=PBE_ID, n_steps=n_steps, pm_banks=4, n_track=0,
        n_deep_max=D_ID, return_state=True))


def _lowered(cfg, deep_counts=None):
    sc = lower_scalars(scalars_from_config(cfg, n_deep_max=D_ID))
    if deep_counts is not None:
        sc["deep_thr"], sc["deep_pre"] = (np.full((D_ID,), v, np.float64)
                                          for v in deep_counts)
    return {k: jnp.asarray(v) for k, v in sc.items()}


def _chain_state(tr, cfg, deep_counts=None):
    """The machine state after running ``tr`` on ``cfg``, and its sc."""
    n_steps = 1024 * ((tr.total_ops + 1023) // 1024)
    with jax.enable_x64(True):
        sc = _lowered(cfg, deep_counts)
        out = _state_cell(n_steps)(
            jnp.asarray(tr.ops), jnp.asarray(tr.addrs),
            jnp.asarray(tr.gaps), jnp.asarray(tr.lengths),
            jnp.asarray(int(cfg.scheme), jnp.int32), sc)
    return out[-1], sc


def _empty_call(sc, scheme, st, n_packets):
    """``forward_chain`` of a batch whose packets are all inactive."""
    rng = np.random.default_rng(n_packets)
    batch = chain.Batch(
        active=jnp.zeros((n_packets,), bool),
        addr=jnp.asarray(rng.integers(0, 64, n_packets), jnp.int32),
        ver=jnp.asarray(rng.integers(1, 9, n_packets), jnp.int32),
        owner=jnp.zeros((n_packets,), st.owner.dtype),
        emit=tb.from_host(rng.uniform(0.0, 1e5, n_packets)),
        ohop=jnp.zeros((n_packets,), jnp.int32),
        oslot=jnp.arange(n_packets, dtype=jnp.int32) % PBE_ID)
    fn = jax.jit(functools.partial(chain.forward_chain, n_banks=4,
                                   n_track=64))
    return fn(sc, scheme, chain.rows_of(st), st.hpbc, st.hop_stats, batch,
              st.dd, st.pm_busy, st.pm_ver)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int64) if x.dtype == np.float64 else x


def _deep_dirty(st):
    return int(np.sum(np.asarray(st.dstate) == DIRTY))


ID_CASES = {
    "pb": (PCSConfig(scheme=Scheme.PB, n_switches=4), None, None),
    "pb_rf": (PCSConfig(scheme=Scheme.PB_RF, n_switches=4), None, None),
    # power lost at 40% of the run: the rest of the stream is dead
    "crashed": (PCSConfig(scheme=Scheme.PB_RF, n_switches=3), 0.4, None),
    # a preset at or above the threshold: a row at the threshold drains
    # nothing, and one above the preset keeps its fraction
    "pre_ge_thr": (PCSConfig(scheme=Scheme.PB_RF, n_switches=4), None,
                   (3.0, 4.5)),
}


@pytest.mark.parametrize("name", list(ID_CASES))
def test_empty_forward_chain_is_the_identity(chain_trace, name):
    """On states left by chain runs, a call with no active packet gives
    back every input bit for bit and writes nothing to PM, and the
    gate's drain-down term says no row would drain."""
    cfg, crash_frac, deep_counts = ID_CASES[name]
    if crash_frac is not None:
        t_end = simulate(chain_trace, cfg, bucket=TINY_BUCKET).runtime_ns
        cfg = cfg.with_crash(crash_frac * t_end)
    st, sc = _chain_state(chain_trace, cfg, deep_counts)
    scheme = jnp.asarray(int(cfg.scheme), jnp.int32)
    with jax.enable_x64(True):
        assert not bool(chain.would_drain(sc, scheme, chain.rows_of(st)))
        for q in (1, PBE_ID):
            dd1, rows, hpbc, hop_stats, pm_busy, pm_ver, n_pm = _empty_call(
                sc, scheme, st, q)
            want = chain.rows_of(st)
            for k in want:
                assert np.array_equal(_bits(rows[k]), _bits(want[k])), (k, q)
            for got, was in ((dd1, st.dd), (hpbc, st.hpbc),
                             (hop_stats, st.hop_stats),
                             (pm_busy, st.pm_busy), (pm_ver, st.pm_ver)):
                assert np.array_equal(_bits(got), _bits(was)), q
            assert _bits(n_pm) == _bits(np.float64(0.0))
    if name != "pb":
        # PB forwards what it commits; the others hold Dirty entries
        assert _deep_dirty(st) > 0, name


def test_lowered_threshold_makes_a_row_drain_unprompted(chain_trace):
    """The identity needs its premise: with the threshold dropped below
    a row's Dirty count (an epoch switch), the drain-down term fires and
    an empty call does move the row."""
    cfg = PCSConfig(scheme=Scheme.PB_RF, n_switches=4)
    st, _ = _chain_state(chain_trace, cfg)
    assert _deep_dirty(st) > 0
    scheme = jnp.asarray(int(cfg.scheme), jnp.int32)
    with jax.enable_x64(True):
        sc = _lowered(cfg, (1.0, 0.0))
        assert bool(chain.would_drain(sc, scheme, chain.rows_of(st)))
        rows = _empty_call(sc, scheme, st, 1)[1]
    assert not np.array_equal(rows["dstate"], st.dstate)


def _same(a, b):
    """Every ``SimResult`` field equal, NaN equal to NaN."""
    for f in a.__dataclass_fields__:
        x, y = getattr(a, f), getattr(b, f)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            if x is None or y is None or not np.array_equal(
                    x, y, equal_nan=x.dtype.kind == "f"):
                return False
        elif not (x == y or (isinstance(x, float) and math.isnan(x)
                             and math.isnan(y))):
            return False
    return True


@pytest.fixture
def legs(monkeypatch):
    """Switch the chain-leg gate: ``"every"`` runs every leg on every
    step (the engine before the gate), ``"blind"`` gates on the batch
    alone, without the drain-down term.  The jitted programs are dropped
    at each switch, so no call reuses one traced under another mode."""
    def use(mode):
        monkeypatch.undo()
        jax.clear_caches()
        if mode == "every":
            monkeypatch.setattr(chain, "grid_any",
                                lambda flag, axis_names: jnp.asarray(True))
        elif mode == "blind":
            monkeypatch.setattr(chain, "would_drain",
                                lambda sc, scheme, rows: jnp.asarray(False))
    yield use
    monkeypatch.undo()
    jax.clear_caches()


def test_lowered_deep_threshold_mid_run_gated_equals_ungated(legs):
    """A schedule that lowers every hop's threshold mid-run: rows that
    settled under the old counts drain down on the next leg call,
    arrivals or not.  The gate sees that (``would_drain``) and the
    gated engine equals the ungated one; a gate blind to it does not."""
    tr = make_trace("radiosity", persist_budget=120)
    t_end = simulate(tr, PCSConfig(scheme=Scheme.PB_RF),
                     bucket=TINY_BUCKET).runtime_ns
    cfg = PCSConfig(scheme=Scheme.PB_RF, n_switches=3, policy=PBPolicy(
        drain=DrainPolicy(threshold=Schedule((0.5 * t_end,), (1.0, 0.25)),
                          preset=0.125)))
    gated = simulate(tr, cfg, bucket=TINY_BUCKET)
    legs("every")
    every = simulate(tr, cfg, bucket=TINY_BUCKET)
    legs("blind")
    blind = simulate(tr, cfg, bucket=TINY_BUCKET)
    assert _same(gated, every)
    assert not _same(blind, every)
