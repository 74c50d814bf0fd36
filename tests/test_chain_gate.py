"""The grid-wide chain-leg gates (``engine.chain.gated_chain``).

The persist handler runs each switch-chain leg (the victim packet, then
the policy's drain batch) only on grid steps where some cell sends it a
packet or has a row that would drain down unprompted; elsewhere the leg
returns its inputs.  So the gated engine must equal the ungated one (every
leg run on every step, as before the gate) bit for bit: on a Fig. 1
probe grid (0-4 switches, crashed replicas), a multi-core chain grid
dense enough to force victims, a fabric grid with spine backpressure and
an epoch-scheduled chain grid, with macro on and off, through
``simulate_grid``, ``simulate_cells`` and the single-cell program.  The
counter ``Call.chain_leg_steps`` says on how many steps each leg ran.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AllocPolicy, DrainPolicy, FabricTopology, PBPolicy,
                        PCSConfig, Schedule, Scheme, Trace)
from repro.core.engine import (chain, simulate, simulate_cells,
                               simulate_grid, spans)
from repro.core.params import Op
from repro.core.traces import fuzz_crash_ns, fuzz_trace, leaf_placement

BUCKET = 1024
N_PROBE = 240
N_SLOTS = 120


def _probe(seed: int) -> Trace:
    """Fig. 1's probe: one core, persists and reads in turn on distinct
    lines, gaps 1-3 us."""
    rng = np.random.default_rng(seed)
    ops = np.where(np.arange(N_PROBE) % 2 == 0, int(Op.PERSIST),
                   int(Op.PM_READ)).astype(np.int32)[None]
    addrs = rng.permutation(4 * N_PROBE)[:N_PROBE].astype(np.int32)[None]
    gaps = rng.uniform(1000.0, 3000.0, N_PROBE).astype(np.float32)[None]
    return Trace(ops=ops, addrs=addrs, gaps=gaps,
                 lengths=np.asarray([N_PROBE], np.int32),
                 name=f"probe{seed}")


def _dense(seed: int, n_tenants: int = 1) -> Trace:
    return fuzz_trace(seed, n_cores=4, n_slots=N_SLOTS, n_addrs=64,
                      p_persist=0.8, n_tenants=n_tenants)[0]


PROBES = [_probe(s) for s in (1, 2)]
# power lost at half the probe's nominal span (2 us a gap)
HALF = 0.5 * N_PROBE * 2000.0
PROBE_CFGS = (
    [PCSConfig(scheme=Scheme.NOPB, n_switches=n) for n in (0, 2, 4)]
    + [PCSConfig(scheme=s, n_switches=n)
       for s in (Scheme.PB, Scheme.PB_RF) for n in (1, 2, 3, 4)]
    + [PCSConfig(scheme=Scheme.PB, n_switches=2).with_crash(HALF),
       PCSConfig(scheme=Scheme.PB_RF, n_switches=4).with_crash(HALF)])

# a buffer that never drains by itself: a full hop 1 evicts a victim on
# every new line, down chains whose deep hops fill and are bypassed
HOLD = PBPolicy(drain=DrainPolicy(threshold=1.0, preset=1.0,
                                  low_water_drains=0, empty_slack=0))
DENSE = [_dense(s) for s in (1, 2)]
DENSE_CFGS = [
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=4, n_switches=3,
              pbe_per_hop=(4, 2, 2), policy=HOLD),
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=4, n_switches=2, policy=HOLD),
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=4, n_switches=3,
              pbe_per_hop=(4, 2, 2), policy=HOLD).with_crash(
                  fuzz_crash_ns(N_SLOTS // 3)),
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=8, n_cores=4, n_tenants=2,
              n_switches=3, policy=PBPolicy(alloc=AllocPolicy(
                  victim="weighted", tenant_quota=(2, 2)))),
    # one switch: its victims take no leg, though the grid has a chain
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=4, policy=HOLD),
    PCSConfig(scheme=Scheme.PB, n_pbe=4, n_switches=3),
    PCSConfig(scheme=Scheme.NOPB, n_switches=2),
]

TENANTS = [_dense(s, n_tenants=4) for s in (3, 4)]


def _fabric(scheme, how, bp_high=None):
    return PCSConfig(scheme=scheme, n_pbe=8, n_cores=4, n_tenants=4,
                     fabric=FabricTopology(2, (4, 4), 4,
                                           leaf_placement(4, 2, how),
                                           bp_high=bp_high))


FABRIC_CFGS = [
    _fabric(Scheme.PB, "packed"), _fabric(Scheme.PB_RF, "spread"),
    _fabric(Scheme.PB_RF, "packed", bp_high=2.0),
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=8, n_cores=4, n_tenants=4,
              n_switches=2, pbe_per_hop=(8, 4)),
    PCSConfig(scheme=Scheme.NOPB, n_cores=4, n_tenants=4, n_switches=2),
]

# every hop's threshold drops mid-run: rows that settled under the old
# counts drain down unprompted at the next leg call
LOWER = PBPolicy(drain=DrainPolicy(
    threshold=Schedule((fuzz_crash_ns(N_SLOTS // 2),), (1.0, 0.25)),
    preset=0.125))
EPOCH_CFGS = [
    PCSConfig(scheme=Scheme.PB_RF, n_switches=3, policy=LOWER),
    PCSConfig(scheme=Scheme.PB_RF, n_pbe=4, n_switches=2, policy=LOWER),
    PCSConfig(scheme=Scheme.PB, n_switches=2),
    PCSConfig(scheme=Scheme.NOPB),
]

GRIDS = {"probe": (PROBES, PROBE_CFGS), "dense": (DENSE, DENSE_CFGS),
         "fabric": (TENANTS, FABRIC_CFGS),
         "epoch": (DENSE[:1] + PROBES[:1], EPOCH_CFGS)}


def _assert_identical(a, b, label):
    for f in a.__dataclass_fields__:
        x, y = getattr(a, f), getattr(b, f)
        if isinstance(x, np.ndarray):
            assert y is not None and np.array_equal(
                x, y, equal_nan=x.dtype.kind == "f"), (label, f)
        else:
            both_nan = (isinstance(x, float) and isinstance(y, float)
                        and np.isnan(x) and np.isnan(y))
            assert x == y or both_nan, (label, f, x, y)


def _every_leg(run):
    """``run()`` on the engine before the gate: every leg on every step.
    The jitted programs are dropped on the way in and out, so neither
    side reuses a program the other traced."""
    with pytest.MonkeyPatch.context() as mp:
        jax.clear_caches()
        mp.setattr(chain, "grid_any",
                   lambda flag, axis_names: jnp.asarray(True))
        try:
            return run()
        finally:
            jax.clear_caches()


@functools.lru_cache(maxsize=None)
def _run(name, macro, gated=True):
    """``(cells, Call)`` of one grid, run once per process."""
    traces, configs = GRIDS[name]

    def run():
        cells = simulate_grid(traces, configs, bucket=BUCKET, macro=macro)
        return cells, spans.calls()[-1]
    return run() if gated else _every_leg(run)


@pytest.mark.parametrize("name, macro", [("probe", True), ("dense", False),
                                         ("fabric", True), ("epoch", False)])
def test_gated_grid_equals_every_leg_run(name, macro):
    """Every field of every cell equals the ungated engine's (macro
    off there, which the macro tests pin to macro on), on as many grid
    steps."""
    got, rec = _run(name, macro)
    want, ref = _run(name, False, gated=False)
    for i, row in enumerate(got):
        for j, cell in enumerate(row):
            _assert_identical(cell, want[i][j], (name, i, j))
    assert rec.steps == ref.steps


def test_probe_grid_runs_the_policy_leg_on_persist_steps_only():
    """On the probe grid the deep cells persist in lockstep on every
    other step and no victim is ever drawn: the victim leg never runs,
    the policy leg on exactly the persist steps."""
    cells, rec = _run("probe", True)
    assert rec.chain_leg_steps == (0, N_PROBE // 2)
    assert all(c.victim_drains == 0 for row in cells for c in row)


def test_dense_grid_runs_the_victim_leg():
    cells, rec = _run("dense", False)
    victims, policy = rec.chain_leg_steps
    assert 0 < victims <= rec.steps and 0 < policy <= rec.steps
    assert cells[0][0].victim_drains > 0


def test_chain_free_grid_has_no_leg_counter():
    simulate_grid(PROBES[:1], [PCSConfig(scheme=Scheme.PB_RF)],
                  bucket=BUCKET)
    assert spans.calls()[-1].chain_leg_steps is None


def test_cells_and_single_cell_follow_the_ungated_grid():
    """The flat pairing (one vmap axis) and the one-cell program (a
    per-cell branch) give the ungated grid's cells bit for bit."""
    picks = [("probe", 0, 6), ("probe", 1, 12), ("dense", 0, 0),
             ("dense", 1, 2), ("dense", 0, 4), ("epoch", 0, 0)]
    want = [_run(n, False, gated=False)[0][i][j] for n, i, j in picks]
    traces = [GRIDS[n][0][i] for n, i, _ in picks]
    configs = [GRIDS[n][1][j] for n, _, j in picks]
    got = simulate_cells(traces, configs, bucket=BUCKET)
    assert spans.calls()[-1].chain_leg_steps[0] > 0
    for k, (a, b) in enumerate(zip(got, want)):
        _assert_identical(a, b, ("cells", picks[k]))
    for k in (2, 5):
        one = simulate(traces[k], configs[k], bucket=BUCKET)
        _assert_identical(one, want[k], ("single", picks[k]))
