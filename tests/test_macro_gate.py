"""The grid-wide macro gate (``engine.macro.macro_gate``, ``engine.step``).

The step skips the macro replay on grid steps where no cell can commit a
window, and gives the abort vector the replay would have given.  So the
gated engine must stay bit-identical to ``macro=False`` and keep the
replay's exact abort counts, on grids where the gate is mostly shut
(cores interleave), mostly open (one core a cell), on crashed grids
(dead runs), fabric grids and epoch-scheduled grids (which keep the full
replay), through ``simulate_grid``, ``simulate_cells`` and the
single-cell program.  The per-reason counts below are those of the
ungated engine (every step replayed) on the same inputs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DrainPolicy, FabricTopology, PBPolicy, PCSConfig,
                        Schedule, Scheme, Trace)
from repro.core.engine import (last_macro_abort_reasons, simulate,
                               simulate_cells, simulate_grid, spans)
from repro.core.engine.handlers import StepCtx
from repro.core.engine.macro import (MACRO_ABORT_REASONS, macro_gate,
                                     macro_step, macro_window)
from repro.core.engine import timebase as tb
from repro.core.engine.state import (init_state, lower_scalars,
                                     scalars_from_config)
from repro.core.params import MACRO_KMAX, Op
from repro.core.traces import fuzz_crash_ns, fuzz_trace, leaf_placement

BUCKET = 1024
N_CORES = 4
N_SLOTS = 120


def _probe(seed: int, n_ops: int = 1000) -> Trace:
    """Fig. 1's probe on core 0 of ``N_CORES``: persists and reads in
    turn on distinct lines, gaps 1-3 us; the other cores stay empty, so
    the grid shares the interleaving grids' program shape."""
    rng = np.random.default_rng(seed)
    ops = np.zeros((N_CORES, n_ops), np.int32)
    addrs = np.zeros((N_CORES, n_ops), np.int32)
    gaps = np.zeros((N_CORES, n_ops), np.float32)
    ops[0] = np.where(np.arange(n_ops) % 2 == 0, int(Op.PERSIST),
                      int(Op.PM_READ))
    addrs[0] = rng.permutation(4 * n_ops)[:n_ops]
    gaps[0] = rng.uniform(1000.0, 3000.0, n_ops)
    lengths = np.zeros((N_CORES,), np.int32)
    lengths[0] = n_ops
    return Trace(ops=ops, addrs=addrs, gaps=gaps, lengths=lengths,
                 name=f"probe{seed}")


# four cores issuing in turn: another core's next op lands inside
# almost every window, so the gate stays shut
ILV = [fuzz_trace(s, n_cores=N_CORES, n_slots=N_SLOTS, n_addrs=64)[0]
       for s in (1, 2, 3)]
# one core a trace: nothing interleaves, so the gate stays open
PROBE = [_probe(s) for s in (4, 5, 6)]
BASE = [PCSConfig(scheme=Scheme.NOPB), PCSConfig(scheme=Scheme.PB),
        PCSConfig(scheme=Scheme.PB_RF),
        PCSConfig(scheme=Scheme.NOPB, n_switches=0)]
# power lost at a third of the run: the rest of each stream is dead
CRASHED = [c.with_crash(fuzz_crash_ns(N_SLOTS // 3)) for c in BASE]
FABRIC = [
    PCSConfig(scheme=Scheme.PB, n_cores=N_CORES, n_tenants=4,
              fabric=FabricTopology(2, (4, 4), 4,
                                    leaf_placement(4, 2, "packed"))),
    PCSConfig(scheme=Scheme.PB_RF, n_cores=N_CORES, n_tenants=4,
              fabric=FabricTopology(2, (4, 4), 4,
                                    leaf_placement(4, 2, "spread"))),
    PCSConfig(scheme=Scheme.NOPB, n_cores=N_CORES, n_tenants=4,
              n_switches=2),
    PCSConfig(scheme=Scheme.PB, n_cores=N_CORES, n_tenants=4),
]
EPOCH = [
    PCSConfig(scheme=Scheme.PB_RF, policy=PBPolicy(drain=DrainPolicy(
        threshold=Schedule((fuzz_crash_ns(N_SLOTS // 2),), (0.75, 0.5)),
        preset=0.25))),
    PCSConfig(scheme=Scheme.PB), PCSConfig(scheme=Scheme.NOPB),
    PCSConfig(scheme=Scheme.PB_RF),
]

# the ungated engine's per-reason abort counts (MACRO_ABORT_REASONS
# order) and committed slots on each grid
EXPECTED = {"interleave": ((424, 0, 0, 0, 964, 4), 48),
            "probe": ((0, 0, 0, 0, 0, 2907), 9093),
            "crashed": ((128, 0, 0, 0, 352, 0), 952),
            "fabric": ((433, 498, 0, 0, 487, 0), 22),
            "epoch": ((428, 0, 0, 59, 905, 1471), 2577)}
GRIDS = {"interleave": (ILV, BASE), "probe": (PROBE, BASE),
         "crashed": (ILV, CRASHED), "fabric": (ILV, FABRIC),
         "epoch": (ILV + PROBE[:1], EPOCH)}


def _assert_identical(a, b, label):
    for f in a.__dataclass_fields__:
        x, y = getattr(a, f), getattr(b, f)
        if isinstance(x, np.ndarray):
            assert y is not None and np.array_equal(x, y), (label, f)
        else:
            both_nan = (isinstance(x, float) and isinstance(y, float)
                        and np.isnan(x) and np.isnan(y))
            assert x == y or both_nan, (label, f, x, y)


@functools.lru_cache(maxsize=None)
def _run(name, macro=True):
    """``(cells, Call)`` of one grid, run once per process."""
    traces, configs = GRIDS[name]
    cells = simulate_grid(traces, configs, bucket=BUCKET, macro=macro)
    return cells, spans.calls()[-1]


@pytest.mark.parametrize("name", list(GRIDS))
def test_gated_grid_equals_macro_off(name):
    on, rec = _run(name)
    off, _ = _run(name, macro=False)
    for i, row in enumerate(on):
        for j, cell in enumerate(row):
            _assert_identical(cell, off[i][j], (name, i, j))
    aborts, macro_ops = EXPECTED[name]
    assert rec.abort_reasons == dict(zip(MACRO_ABORT_REASONS, aborts))
    assert rec.macro_ops == macro_ops


@pytest.mark.parametrize("name, lo, hi", [("interleave", 0.0, 0.2),
                                          ("probe", 0.9, 1.0)])
def test_gate_open_share(name, lo, hi):
    """The gate opens on few steps of the interleaving grid and on
    nearly all of the one-core grid; never on more steps than ran."""
    _, rec = _run(name)
    assert 0 < rec.macro_gate_steps <= rec.steps
    assert lo <= rec.macro_gate_steps / rec.steps <= hi
    _, off = _run(name, macro=False)
    assert off.macro_gate_steps == 0


def test_epoch_grid_keeps_the_full_replay():
    """An epoch-scheduled grid replays on every step it runs."""
    _, rec = _run("epoch")
    assert rec.macro_gate_steps == rec.steps


def test_cells_and_single_cell_follow_the_grid():
    """The flat pairing and the one-cell program (a per-cell branch)
    give the grid's cells bit for bit, and together the grid's counts."""
    pairs = [(ILV[0], BASE[0]), (ILV[1], BASE[2]), (ILV[2], CRASHED[1]),
             (PROBE[0], BASE[1]), (PROBE[1], BASE[3]), (PROBE[2], CRASHED[0])]
    got = simulate_cells([t for t, _ in pairs], [c for _, c in pairs],
                         bucket=BUCKET)
    rec = spans.calls()[-1]
    assert rec.macro_gate_steps <= rec.steps
    want = simulate_cells([t for t, _ in pairs], [c for _, c in pairs],
                          bucket=BUCKET, macro=False)
    for k, (a, b) in enumerate(zip(got, want)):
        _assert_identical(a, b, ("cells", k))
    # one cell alone, per-cell gate: each equals its paired cell, and
    # the abort counts add up to the paired call's
    total = dict.fromkeys(MACRO_ABORT_REASONS, 0)
    for k, (tr, cfg) in enumerate(pairs):
        one = simulate(tr, cfg, bucket=BUCKET)
        _assert_identical(one, want[k], ("single", k))
        for r, n in last_macro_abort_reasons().items():
            total[r] += n
    assert total == rec.abort_reasons


# ------------------------------------------------------ the pre-filter
N_FUZZ = 4096


def _words(x):
    """float64 times as time words (the CPU can bitcast them)."""
    return jax.lax.bitcast_convert_type(x, tb.DTYPE)


def _fuzz_window(key, scheme, sc):
    """One random step's inputs: four cores' clocks and next issue
    times, a window of persists and reads at core 0's cursor, busy PM
    banks and PBC, a crash point that may fall inside the window."""
    ks = jax.random.split(key, 11)
    C, L, B = N_CORES, 2 * MACRO_KMAX, 4
    P = int(sc["n_pbe"])
    st = init_state(C, P, B)
    clock = jax.random.uniform(ks[0], (C,), jnp.float64, 0.0, 4000.0)
    gaps = jax.random.uniform(ks[1], (C, L), jnp.float64, 0.0, 600.0)
    ops = jnp.where(jax.random.bernoulli(ks[2], 0.5, (C, L)),
                    int(Op.PERSIST), int(Op.PM_READ)).astype(jnp.int32)
    addrs = jax.random.permutation(ks[3], 4 * L)[:C * L].reshape(
        C, L).astype(jnp.int32)
    mlen = jax.random.randint(ks[4], (C, L), 1, MACRO_KMAX + 1, jnp.int8)
    lengths = jnp.full((C,), L, jnp.int32).at[0].set(
        jax.random.randint(ks[5], (), 1, L + 1, jnp.int32))
    st = st._replace(
        clock=_words(clock),
        pm_busy=_words(clock[0] + jax.random.uniform(
            ks[6], (B,), jnp.float64, -500.0, 1500.0)),
        pbc_busy=_words(clock[0] + jax.random.uniform(
            ks[7], (), jnp.float64, -500.0, 1500.0)))
    # the other cores issue around the span a window can cover
    others = clock[0] + jax.random.uniform(ks[8], (C,), jnp.float64,
                                           0.0, 6000.0)
    t0 = clock[0] + gaps[0, 0]
    tsel = others.at[0].set(t0)
    crash = jnp.where(jax.random.bernoulli(ks[9], 0.2),
                      t0 + jax.random.uniform(ks[10], (), jnp.float64,
                                              -300.0, 3000.0), 1e30)
    sc = dict(sc, crash_at=_words(crash))
    c, i = jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)
    valid = jnp.asarray(True)
    live = t0 <= crash
    t0, tsel, gaps = _words(t0), _words(tsel), _words(gaps)
    ctx = StepCtx(c=c, op=ops[0, 0], t=t0, addr=addrs[0, 0], scheme=scheme,
                  sc=sc, slot_ids=jnp.arange(P), slot_active=jnp.arange(P) < P,
                  tenant=jnp.asarray(0, jnp.int32),
                  tids=jnp.zeros((C,), jnp.int32),
                  n_live_t=jnp.asarray(C, jnp.int32), n_banks=B, n_track=0)
    win = macro_window(ctx, gaps, lengths, mlen, tsel, valid, live, i,
                       kmax=MACRO_KMAX)
    want, ab_skip = macro_gate(win, sc, t0, valid, live)
    _, use, _, ab = macro_step(ctx, st, ops, addrs, win, valid, live, t0,
                               i, kmax=MACRO_KMAX)
    return want, ab_skip, use, ab


@pytest.mark.parametrize("cfg, reason", [
    (PCSConfig(scheme=Scheme.NOPB), "interleave"),
    (PCSConfig(scheme=Scheme.PB), "interleave"),
    (PCSConfig(scheme=Scheme.PB_RF), "interleave"),
    (PCSConfig(scheme=Scheme.NOPB, n_switches=0), "interleave"),
    (PCSConfig(scheme=Scheme.NOPB, n_switches=3), "interleave"),
    (PCSConfig(scheme=Scheme.PB, n_switches=2), "deep"),
], ids=["nopb", "pb", "pb_rf", "nopb_d0", "nopb_d3", "pb_d2"])
def test_prefilter_no_means_the_replay_cannot_commit(cfg, reason):
    """Wherever the pre-filter says no, ``macro_step`` itself commits
    nothing and gives the skip's abort vector, on fuzzed windows."""
    with jax.enable_x64(True):
        sc = {k: jnp.asarray(v) for k, v in
              lower_scalars(scalars_from_config(cfg)).items()}
        scheme = jnp.asarray(int(cfg.scheme), jnp.int32)
        run = jax.jit(jax.vmap(lambda k: _fuzz_window(k, scheme, sc)))
        want, ab_skip, use, ab = (np.asarray(x) for x in run(
            jax.random.split(jax.random.PRNGKey(7), N_FUZZ)))
    no = ~want
    assert not use[no].any()
    assert np.array_equal(ab[no], ab_skip[no])
    # both sides of the filter, a commit, and the reason the skip
    # stands in for are all reached
    assert no.any() and want.any() and use.any()
    assert ab[no][:, MACRO_ABORT_REASONS.index(reason)].any()
