"""The batched engine front-end: one program for the whole paper grid.

Acceptance for the core.engine refactor: ``simulate_grid`` runs a
mixed-scheme {7 workloads x NoPB/PB/PB_RF} grid with exactly ONE XLA
compilation (the scheme is traced, not static), and every per-cell
``SimResult`` matches what ``simulate()`` returns for that cell.  The
grid itself comes from the session-scoped ``paper_grid`` fixture
(conftest.py) so its single compilation is shared across the suite.

The padding-invariant tests assert directly on the final
:class:`MachineState` (``scan_cell(..., return_state=True)``): padded
cores issue no ops and padded steps change no stats.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_BUCKET
from repro.core import Op, PCSConfig, Scheme, Trace, make_trace
from repro.core.engine import simulate, simulate_grid, simulate_sweep
from repro.core.engine.state import lower_scalars, scalars_from_config
from repro.core.engine.step import scan_cell

FIELDS = ("runtime_ns", "persist_lat_ns", "read_lat_ns", "persists",
          "pm_reads", "read_hits", "coalesces", "pm_writes", "stall_ns",
          "pi_detours", "victim_drains")


def _assert_cells_equal(a, b, label):
    for f in FIELDS:
        va, vb = getattr(a, f), getattr(b, f)
        if isinstance(va, int):
            assert va == vb, (label, f, va, vb)
        else:
            assert va == pytest.approx(vb, rel=1e-12), (label, f, va, vb)


def test_mixed_scheme_grid_single_compile(paper_grid):
    names, configs, cells, compiles = paper_grid
    assert compiles == 1, (
        "mixed-scheme grid must lower to exactly one XLA program")
    assert len(cells) == len(names) and all(
        len(row) == len(configs) for row in cells)
    for row in cells:
        for cell in row:
            assert cell.persists > 0 and cell.runtime_ns > 0


def test_grid_cells_match_simulate_spotcheck(paper_grid, tiny_traces):
    """Three representative cells re-run standalone; the exhaustive
    21-cell sweep is the slow variant below."""
    names, configs, cells, _ = paper_grid
    picks = [("radiosity", 2), ("cholesky", 0), ("fft", 1)]
    for name, j in picks:
        i = names.index(name)
        ref = simulate(tiny_traces[name], configs[j], bucket=TINY_BUCKET)
        _assert_cells_equal(cells[i][j], ref, (name, j))


@pytest.mark.slow
def test_grid_cells_match_simulate_exhaustive(paper_grid, tiny_traces):
    names, configs, cells, _ = paper_grid
    for i, name in enumerate(names):
        for j, cfg in enumerate(configs):
            ref = simulate(tiny_traces[name], cfg, bucket=TINY_BUCKET)
            _assert_cells_equal(cells[i][j], ref, (name, cfg.scheme.name))


def test_grid_results_invariant_to_bucket(paper_grid, tiny_traces):
    """Padding steps are no-ops: shape-bucket choice changes nothing."""
    names, configs, cells, _ = paper_grid
    i = names.index("radiosity")
    b = simulate(tiny_traces["radiosity"], configs[2],
                 bucket=2 * TINY_BUCKET)
    _assert_cells_equal(cells[i][2], b, "bucket")


def test_sweep_allows_mixed_schemes(tiny_traces):
    """simulate_sweep no longer refuses mixed-scheme config lists."""
    tr = tiny_traces["raytrace"]
    cfgs = [PCSConfig(scheme=Scheme.NOPB),
            PCSConfig(scheme=Scheme.PB, n_pbe=8),
            PCSConfig(scheme=Scheme.PB_RF, n_pbe=32)]
    sweep = simulate_sweep(tr, cfgs, bucket=TINY_BUCKET)
    assert len(sweep) == 3
    for cfg, r in zip(cfgs, sweep):
        ref = simulate(tr, cfg, max_pbe=32, bucket=TINY_BUCKET)
        _assert_cells_equal(r, ref, cfg.scheme.name)


def _one_core_trace():
    ops = [int(Op.PERSIST), int(Op.PM_READ)] * 8
    addrs = list(range(16))
    return Trace(ops=np.array([ops], np.int32),
                 addrs=np.array([addrs], np.int32),
                 gaps=np.full((1, 16), 2000.0, np.float32),
                 lengths=np.array([16], np.int32), name="c1")


@pytest.mark.slow
def test_grid_pads_heterogeneous_core_counts(tiny_traces):
    """Traces with different core counts share one stacked program; the
    padded cores never issue ops and never count toward barriers."""
    tr1 = _one_core_trace()
    tr8 = tiny_traces["radiosity"]                      # 8 cores
    cfg = PCSConfig(scheme=Scheme.PB)
    cells = simulate_grid([tr1, tr8], [cfg], bucket=TINY_BUCKET)
    _assert_cells_equal(cells[0][0],
                        simulate(tr1, cfg, bucket=TINY_BUCKET), "c1")
    _assert_cells_equal(cells[1][0],
                        simulate(tr8, cfg, bucket=TINY_BUCKET), "c8")


def test_grid_rejects_mixed_pm_banks(tiny_traces):
    tr = tiny_traces["radiosity"]
    with pytest.raises(ValueError, match="pm_banks"):
        simulate_grid([tr], [PCSConfig(pm_banks=4), PCSConfig(pm_banks=8)],
                      bucket=TINY_BUCKET)


def test_barrier_workload_in_grid(paper_grid, tiny_traces):
    """A barrier-heavy trace (FFT) completes and matches its single-cell
    run inside a stacked grid (regression: barrier release threshold must
    count only live cores)."""
    names, configs, cells, _ = paper_grid
    i = names.index("fft")
    ref = simulate(tiny_traces["fft"], configs[2], bucket=TINY_BUCKET)
    _assert_cells_equal(cells[i][2], ref, "fft-in-grid")
    assert ref.runtime_ns > 0


# --------------------------------------------------------------------------
# Padding invariants, asserted on MachineState itself (not end-to-end)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted_cell(max_pbe, n_steps, pm_banks):
    import jax
    return jax.jit(functools.partial(
        scan_cell, max_pbe=max_pbe, n_steps=n_steps, pm_banks=pm_banks,
        n_track=0, return_state=True))


def _scan_state(tr, cfg, n_steps, extra_cores=0):
    """Run scan_cell with optional padded cores; return the final state."""
    C, L = tr.ops.shape
    ops = np.zeros((C + extra_cores, L), np.int32)
    addrs = np.zeros((C + extra_cores, L), np.int32)
    gaps = np.zeros((C + extra_cores, L), np.float32)
    lengths = np.zeros((C + extra_cores,), np.int32)
    ops[:C], addrs[:C], gaps[:C], lengths[:C] = (tr.ops, tr.addrs, tr.gaps,
                                                 tr.lengths)
    with jax.enable_x64(True):
        sc = {k: jnp.asarray(v) for k, v in
              lower_scalars(scalars_from_config(cfg)).items()}
        out = _jitted_cell(cfg.n_pbe, n_steps, cfg.pm_banks)(
            jnp.asarray(ops), jnp.asarray(addrs), jnp.asarray(gaps),
            jnp.asarray(lengths), jnp.asarray(int(cfg.scheme), jnp.int32),
            sc)
        state = jax.tree_util.tree_map(np.asarray, out[-1])
    return state


@pytest.fixture(scope="module")
def _barrier_trace():
    ops = np.array([[int(Op.PERSIST), int(Op.BARRIER), int(Op.PM_READ),
                     int(Op.PERSIST)],
                    [int(Op.PERSIST), int(Op.BARRIER), int(Op.PERSIST),
                     int(Op.COMPUTE)]], np.int32)
    addrs = np.array([[1, 0, 1, 2], [3, 0, 4, 0]], np.int32)
    gaps = np.full((2, 4), 3000.0, np.float32)
    return Trace(ops=ops, addrs=addrs, gaps=gaps,
                 lengths=np.array([4, 4], np.int32), name="pad")


@pytest.mark.parametrize("scheme", [Scheme.NOPB, Scheme.PB, Scheme.PB_RF])
def test_padded_cores_issue_no_ops(_barrier_trace, scheme):
    """A zero-length core leaves no trace in MachineState: its clock and
    cursor stay zero, it never arrives at a barrier, and every machine
    array (PB tables, resources, stats) matches the unpadded run."""
    cfg = PCSConfig(scheme=scheme, n_pbe=4)
    n = int(_barrier_trace.lengths.sum())
    st_ref = _scan_state(_barrier_trace, cfg, n_steps=n)
    st_pad = _scan_state(_barrier_trace, cfg, n_steps=n, extra_cores=2)
    assert np.all(np.asarray(st_pad.clock[2:]) == 0.0)
    assert np.all(np.asarray(st_pad.ptr[2:]) == 0)
    assert not np.any(np.asarray(st_pad.blocked[2:]))
    np.testing.assert_array_equal(np.asarray(st_pad.clock[:2]),
                                  np.asarray(st_ref.clock))
    for field in ("tag", "state", "lru", "dd", "ver", "pm_busy", "pbc_busy",
                  "bcount", "stats"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st_pad, field)),
            np.asarray(getattr(st_ref, field)), err_msg=field)


@pytest.mark.parametrize("scheme", [Scheme.PB, Scheme.PB_RF])
def test_padded_steps_change_no_state(_barrier_trace, scheme):
    """Steps past stream exhaustion are provable no-ops: running the scan
    longer changes no MachineState field at all."""
    cfg = PCSConfig(scheme=scheme, n_pbe=4)
    n = int(_barrier_trace.lengths.sum())
    st_exact = _scan_state(_barrier_trace, cfg, n_steps=n)
    st_longer = _scan_state(_barrier_trace, cfg, n_steps=n + 17)
    for field in st_exact._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_longer, field)),
            np.asarray(getattr(st_exact, field)), err_msg=field)
