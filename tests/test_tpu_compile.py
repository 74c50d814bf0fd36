"""Ahead-of-time compiles for one TPU v5e chip, with no chip attached.

The TPU compiler is installed next to the CPU backend, so the engine's
two grid programs at the paper's trace budget and the three Pallas
kernels at their benchmark widths are compiled here for a described
chip: what the chip's compiler would refuse (a block it cannot tile, a
primitive it cannot lower, a program that does not fit the 16 GB of
device memory) fails here first.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers must
all collect the same tests.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3
PAPER_BUDGET = 100_000
PAPER_BUCKET = 16384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def paper_inputs():
    """Stacked paper-budget inputs of the default {7 workloads x NoPB/PB/
    PB_RF} grid, as ``simulate_grid`` stages them."""
    from repro.core import PCSConfig, Scheme, WORKLOADS, make_trace
    from repro.core.engine.grid import (_plan_runs, _stack_configs,
                                        _stack_traces)
    traces = [make_trace(n, persist_budget=PAPER_BUDGET) for n in WORKLOADS]
    configs = [PCSConfig(scheme=s)
               for s in (Scheme.NOPB, Scheme.PB, Scheme.PB_RF)]
    ops, addrs, gaps, lengths, n_steps = _stack_traces(traces, PAPER_BUCKET)
    mlen = _plan_runs(ops, addrs, gaps)
    n_tenants_max = max(c.n_tenants for c in configs)
    sc, schemes, max_pbe, pm_banks, n_deep, n_leaves = _stack_configs(
        configs, None, n_tenants_max)
    statics = dict(max_pbe=max_pbe, n_steps=n_steps, pm_banks=pm_banks,
                   n_track=0, n_tenants_max=n_tenants_max,
                   n_deep_max=n_deep, n_leaves_max=n_leaves, macro=True)
    return (ops, addrs, gaps, lengths, mlen), schemes, sc, statics


def _spec(a, sharding, dtype=None):
    return jax.ShapeDtypeStruct(np.shape(a), dtype or np.asarray(a).dtype,
                                sharding=sharding)


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (used, mem)


@pytest.mark.parametrize("program", ["grid", "cells"])
def test_engine_compiles_for_v5e_at_paper_budget(one_chip, paper_inputs,
                                                 program):
    from repro.core.engine.grid import _run_cells, _run_grid
    buffers, schemes, sc, statics = paper_inputs
    if program == "cells":
        # the flat pairing at the same input shapes: trace i with the
        # i-th config, cycling the schemes
        pick = np.arange(len(buffers[0])) % len(schemes)
        schemes = schemes[pick]
        sc = {k: v[pick] for k, v in sc.items()}
    fn = _run_grid if program == "grid" else _run_cells
    with jax.enable_x64(True):
        args = [_spec(b, one_chip) for b in buffers]
        args.append(_spec(schemes, one_chip))
        args.append({k: _spec(v, one_chip, jnp.float64)
                     for k, v in sc.items()})
        compiled = fn.lower(*args, **statics).compile()
    _assert_fits(compiled)


def _kernel_cases():
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ssd_scan import ssd_scan_pallas
    from repro.kernels.tat_lookup import tat_lookup_pallas
    f32, i32 = jnp.float32, jnp.int32
    return {
        "tat_lookup": (
            functools.partial(tat_lookup_pallas, interpret=False),
            [((4096,), i32), ((64,), i32), ((64,), i32)]),
        "flash_attention": (
            functools.partial(flash_attention_pallas, causal=True,
                              interpret=False),
            [((1, 4, 1024, 128), f32)] * 3),
        "ssd_scan": (
            functools.partial(ssd_scan_pallas, interpret=False),
            [((1, 1024, 8, 64), f32), ((1, 1024, 8), f32), ((8,), f32),
             ((1, 1024, 128), f32), ((1, 1024, 128), f32)]),
    }


@pytest.mark.parametrize("kernel", ["tat_lookup", "flash_attention",
                                    "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = _kernel_cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)
