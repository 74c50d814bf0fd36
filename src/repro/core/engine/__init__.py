"""Composable timed PCS engine (DESIGN.md §3).

Replaces the monolithic ``_simulate`` scan with individually testable
pieces:

  * ``state``    — machine-state pytree, stats layout, config lowering
  * ``channels`` — PM bank + PBC resource model (next-free scalars)
  * ``policy``   — allocation, victim selection, drain policies; the one
                   home of the scheme/threshold constants shared with
                   the untimed oracle and the checkpoint tier
  * ``handlers`` — per-op handlers with traced-scheme ``lax.switch``
  * ``step``     — clock-merge step driver + the scan
  * ``spans``    — the engine's spans and counters (compile counter,
                   macro hit rate, scan segments, jax.monitoring seconds)
  * ``macro``    — guarded macro-step mini-interpreter (homogeneous-run
                   speculation; bit-exact commit-or-abort)
  * ``fabric``   — fan-out fabric helpers (leaf partition of the hop-1
                   slot axis, spine backpressure signal)
  * ``grid``     — ``simulate_grid`` / ``simulate_cells`` batched
                   front-ends and the ``simulate`` / ``simulate_sweep``
                   compat wrappers
"""
from repro.core.engine.grid import (simulate, simulate_cells,  # noqa: F401
                                    simulate_grid, simulate_sweep)
from repro.core.engine.spans import (compile_count,  # noqa: F401
                                     last_macro_abort_reasons,
                                     last_macro_hit_rate)
from repro.core.engine.state import SimResult  # noqa: F401

__all__ = ["SimResult", "simulate", "simulate_cells", "simulate_grid",
           "simulate_sweep", "compile_count", "last_macro_hit_rate",
           "last_macro_abort_reasons"]
