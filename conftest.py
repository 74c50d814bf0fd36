# Tests and benches must see the real (single) CPU device; only the
# dry-run module sets --xla_force_host_platform_device_count=512, and it
# does so before any jax import inside its own process.
import os

import pytest

from repro.compile_cache import use_compile_cache

assert "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""), (
    "run pytest without the dry-run's XLA_FLAGS; smoke tests expect 1 device")

# Persistent XLA compile cache: the suite is dominated by compiles of the
# same engine programs run after run, so cache them across processes.
# First run pays the compiles; warm runs skip the XLA backend work.
use_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (big-model smoke, exhaustive grids); "
        "excluded from `make test`, included in `make test-all` / tier-1")


# --------------------------------------------------------------------------
# Shared tiny-trace set + the one-compilation paper grid.
#
# XLA recompiles dominated the suite (every distinct trace shape built its
# own program); these session-scoped fixtures build the 7 paper workloads
# once at a reduced persist budget and run the whole mixed-scheme
# {workload x scheme} grid through ONE compiled simulate_grid program that
# every engine test then shares.
# --------------------------------------------------------------------------
TINY_BUDGET = 200
TINY_BUCKET = 512
TINY_TRACE_KW = {"fft": {"m": 9}}   # shrink the FFT read volume


@pytest.fixture(scope="session")
def tiny_traces():
    from repro.core import WORKLOADS, make_trace
    return {name: make_trace(name, persist_budget=TINY_BUDGET,
                             **TINY_TRACE_KW.get(name, {}))
            for name in WORKLOADS}


@pytest.fixture(scope="session")
def paper_grid(tiny_traces):
    """One compiled {7 workloads x NoPB/PB/PB_RF} grid, shared by tests.

    Returns ``(names, configs, cells, compiles)`` where ``compiles`` is
    the number of XLA programs the grid cost (the one-program acceptance
    test asserts it is exactly 1).
    """
    from repro.core import PCSConfig, Scheme, simulate_grid
    from repro.core.engine import compile_count

    names = list(tiny_traces)
    traces = [tiny_traces[n] for n in names]
    configs = [PCSConfig(scheme=s)
               for s in (Scheme.NOPB, Scheme.PB, Scheme.PB_RF)]
    c0 = compile_count()
    cells = simulate_grid(traces, configs, bucket=TINY_BUCKET)
    return names, configs, cells, compile_count() - c0
