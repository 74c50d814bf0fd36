"""Abstract traces of the real engine cell for the jaxpr-based passes.

One small exemplar cell exercises every traced axis: PB_RF over a
2-leaf fan-out fabric (deep-hop rows live for the spine, per-leaf PBC
column live, finite backpressure watermark), 2 tenants with quotas +
weighted victim, a tenant-scoped drain policy with a latency target, a
finite crash point, durability tracking and macro-stepping.  Tracing it with
``jax.make_jaxpr`` is seconds (no XLA compile), so the passes run at
test speed.

The trace arrays are tiny but cover every op kind — the handler
dispatch is a ``lax.switch`` over all six handlers, so every handler
body (and therefore every ``sc`` consumer) is traced regardless of
which ops the exemplar trace actually issues.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np


def _example_inputs():
    from repro.core.engine.state import scalars_from_config
    from repro.core.params import (AllocPolicy, DrainPolicy, FabricTopology,
                                   Op, PBPolicy, PCSConfig, Schedule,
                                   Scheme, MACRO_KMAX)
    from repro.core.traces import plan_runs

    # the 2-leaf fabric (finite backpressure watermark) keeps the fabric
    # operands (n_leaves/leaf_of_t/leaf_base/bp_high) live under DCE and
    # derives the same (8, 4) hop capacities as the old explicit chain;
    # every schedulable knob is a 2-EPOCH Schedule (one shared boundary)
    # so DCE proves the epoch_bounds vector and the stacked per-epoch
    # rows feed the results, not just epoch 0's slice
    bound = 2.5e4
    cfg = PCSConfig(
        scheme=Scheme.PB_RF, n_cores=4,
        n_tenants=2, crash_at_ns=5.0e4,
        fabric=FabricTopology(n_leaves=2, leaf_pbe=(4, 4), spine_pbe=4,
                              placement=Schedule((bound,),
                                                 ((0, 1), (1, 0))),
                              bp_high=3.0),
        policy=PBPolicy(
            drain=DrainPolicy(
                per_tenant=True,
                threshold=Schedule((bound,), (0.75, 0.5)),
                preset=0.25,
                latency_target_ns=Schedule((bound,), (450.0, 300.0))),
            alloc=AllocPolicy(victim="weighted",
                              tenant_quota=Schedule((bound,),
                                                    ((4, 4), (3, 5))))))
    sc = scalars_from_config(cfg, n_tenants_max=2, n_deep_max=1,
                             n_leaves_max=2, n_epochs_max=2)

    C, L = 4, 16 + MACRO_KMAX
    kinds = [Op.PERSIST, Op.PM_READ, Op.DRAM_READ, Op.DRAM_WRITE,
             Op.COMPUTE, Op.PERSIST, Op.PM_READ, Op.BARRIER]
    ops = np.zeros((C, L), np.int32)
    addrs = np.zeros((C, L), np.int32)
    gaps = np.zeros((C, L), np.float32)
    for c in range(C):
        for i in range(16):
            ops[c, i] = int(kinds[i % len(kinds)])
            addrs[c, i] = (c * 16 + i) % 8
            gaps[c, i] = 10.0
    lengths = np.full((C,), 16, np.int32)
    mlen = plan_runs(ops, addrs, gaps, MACRO_KMAX)
    statics = dict(max_pbe=8, n_steps=32, pm_banks=2, n_track=4,
                   n_tenants_max=2, n_deep_max=1, n_leaves_max=2,
                   macro=True)
    # device arrays, as simulate_grid stages them: numpy closures would
    # reject tracer indices during abstract tracing
    import jax.numpy as jnp
    buffers = tuple(jnp.asarray(b) for b in (ops, addrs, gaps, lengths,
                                             mlen))
    return buffers, statics, sc


@functools.lru_cache(maxsize=2)
def trace_engine(return_state: bool = False):
    """``(closed_jaxpr, operand_names)`` of one exemplar engine cell.

    ``operand_names`` aligns positionally with ``jaxpr.invars``:
    ``"scheme"`` followed by the sorted ``sc`` keys (dict pytrees
    flatten in sorted-key order).  Cached per flag — the retrace pass
    wants the results-only program (dead telemetry prunes back to its
    inputs), the dtype pass wants the final carry too.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine.state import lower_scalars
    from repro.core.engine.step import scan_cell

    (ops, addrs, gaps, lengths, mlen), statics, sc = _example_inputs()

    def cell(scheme, sc):
        return scan_cell(ops, addrs, gaps, lengths, scheme, sc,
                         mlen=mlen, return_state=return_state, **statics)

    with jax.enable_x64(True):
        sc_j = {k: jnp.asarray(v) for k, v in lower_scalars(sc).items()}
        closed = jax.make_jaxpr(cell)(jnp.asarray(2, jnp.int32), sc_j)
    names = ["scheme"] + sorted(sc_j)
    if len(names) != len(closed.jaxpr.invars):
        raise RuntimeError(
            f"operand-name alignment broke: {len(names)} names vs "
            f"{len(closed.jaxpr.invars)} invars")
    return closed, names


def scalar_keys() -> List[str]:
    """Every key ``scalars_from_config`` lowers (the sweepable surface)."""
    _, _, sc = _example_inputs()
    return sorted(sc)


def final_state_shapes() -> Dict[str, Tuple[str, Tuple[int, ...]]]:
    """column -> (dtype, shape) of the scan carry AFTER a full cell run
    (``jax.eval_shape``: abstract, no compile).  Catches a handler that
    silently widens a packed column just as well as an init-time
    regression — the carry must round-trip every step."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine.state import lower_scalars
    from repro.core.engine.step import scan_cell

    (ops, addrs, gaps, lengths, mlen), statics, sc = _example_inputs()

    def final_state(scheme, sc):
        out = scan_cell(ops, addrs, gaps, lengths, scheme, sc,
                        mlen=mlen, return_state=True, **statics)
        return out[-1]

    with jax.enable_x64(True):
        sc_j = {k: jnp.asarray(v) for k, v in lower_scalars(sc).items()}
        st = jax.eval_shape(final_state, jnp.asarray(2, jnp.int32), sc_j)
    return {k: (str(v.dtype), tuple(v.shape))
            for k, v in st._asdict().items()}
