"""The engine's spans and counters (``engine.spans``): one ``engine.call``
span per front-end call with its stages nested under it, a bounded log,
the per-cell scan segment counter, the macro telemetry folded into the
call record, and the ``jax.monitoring`` listener's set-up seconds."""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Op, PCSConfig, Scheme, Trace
from repro.core.engine import (last_macro_abort_reasons,
                               last_macro_hit_rate, simulate, simulate_cells,
                               simulate_grid, spans)
from repro.core.engine.macro import MACRO_ABORT_REASONS
from repro.core.engine.step import CHUNK

BUCKET = 1024
STAGES = ["engine.stack", "engine.plan_runs", "engine.lower_configs",
          "engine.put", "engine.scan", "engine.fetch", "engine.unpack"]


def _trace(lengths, seed, name):
    """A trace whose cores run ``lengths`` ops: persists, PM reads and
    compute over a few lines, gaps 1-3 us."""
    rng = np.random.default_rng(seed)
    L = max(lengths)
    ops = np.zeros((len(lengths), L), np.int32)
    addrs = np.zeros((len(lengths), L), np.int32)
    gaps = np.zeros((len(lengths), L), np.float32)
    for c, n in enumerate(lengths):
        ops[c, :n] = rng.choice([int(Op.PERSIST), int(Op.PM_READ),
                                 int(Op.COMPUTE)], n)
        addrs[c, :n] = rng.integers(0, 64, n)
        gaps[c, :n] = rng.uniform(1000.0, 3000.0, n)
    return Trace(ops=ops, addrs=addrs, gaps=gaps,
                 lengths=np.asarray(lengths, np.int32), name=name)


# mixed lengths: 430, 40 and 300 ops in all, over one or two cores
TRACES = [_trace([300, 130], 1, "long"), _trace([40], 2, "short"),
          _trace([150, 150], 3, "even")]
CONFIGS = [PCSConfig(scheme=Scheme.NOPB), PCSConfig(scheme=Scheme.PB)]


def _call_spans(rec):
    return [s for s in spans.spans() if s.call_id == rec.call_id]


@pytest.fixture(scope="module")
def plain_grid():
    simulate_grid(TRACES, CONFIGS, bucket=BUCKET, macro=False)
    return spans.calls()[-1]


def test_spans_nest_under_one_call(plain_grid):
    got = _call_spans(plain_grid)
    # children close before the root, in stage order
    assert [s.name for s in got] == STAGES + ["engine.call"]
    root = got[-1]
    assert root.parent is None
    for s in got[:-1]:
        assert s.parent == "engine.call"
        assert root.t0_ns <= s.t0_ns <= s.t1_ns <= root.t1_ns
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(got[:-2], got[1:-1]))


def test_one_call_per_front_end_call():
    n0 = len([s for s in spans.spans() if s.name == "engine.call"])
    ids0 = {c.call_id for c in spans.calls()}
    simulate_cells(TRACES[:2], CONFIGS, bucket=BUCKET, macro=False)
    simulate_grid([], CONFIGS)      # nothing to run: no call
    roots = [s for s in spans.spans() if s.name == "engine.call"]
    assert len(roots) == n0 + 1
    rec = spans.calls()[-1]
    assert rec.call_id not in ids0
    assert [s.name for s in _call_spans(rec)] == STAGES + ["engine.call"]
    # one cell a pair: (long, NoPB) and (short, PB)
    assert rec.cells == 2 and rec.trace_ops == 470
    assert rec.segments.tolist() == [4, 1]


def test_span_needs_an_open_call():
    with pytest.raises(AssertionError):
        with spans.span("stray"):
            pass
    assert all(s.name != "stray" for s in spans.spans())


def test_log_stays_bounded():
    for _ in range(spans.CALL_LOG + 10):
        with spans.call() as rec:
            for _ in range(spans.SPAN_LOG // spans.CALL_LOG):
                with spans.span("filler"):
                    pass
    assert len(spans.spans()) == spans.SPAN_LOG
    assert spans.spans()[-1] == _call_spans(rec)[-1]
    assert len(spans.calls()) == spans.CALL_LOG
    assert spans.calls()[-1] is rec


def test_segments_count_each_cells_own_stream(plain_grid):
    """With ``macro=False`` a step commits one op, so each cell runs
    ``ceil(total_ops / CHUNK)`` segments; the grid runs its slowest."""
    rec = plain_grid
    want = np.repeat([math.ceil(t.total_ops / CHUNK) for t in TRACES],
                     len(CONFIGS))
    assert rec.cells == len(TRACES) * len(CONFIGS)
    assert rec.segments.tolist() == want.tolist()
    assert rec.steps == int(want.max()) * CHUNK    # BUCKET % CHUNK == 0
    assert rec.trace_ops == sum(t.total_ops for t in TRACES) * 2
    assert rec.macro_ops == 0


def test_single_cell_segments_and_tail():
    # a bucket that is no multiple of CHUNK leaves a tail segment
    bucket = 3 * CHUNK + 40
    simulate(TRACES[0], CONFIGS[0], bucket=bucket, macro=False)
    rec = spans.calls()[-1]
    n_steps = bucket * math.ceil(TRACES[0].total_ops / bucket)
    assert rec.cells == 1 and rec.segments.tolist() == [4]
    assert n_steps % CHUNK and rec.steps == 4 * CHUNK + n_steps % CHUNK


def test_macro_telemetry_reads_as_before():
    simulate_grid(TRACES, CONFIGS, bucket=BUCKET, macro=False)
    assert last_macro_hit_rate() == 0.0
    assert last_macro_abort_reasons() == dict.fromkeys(MACRO_ABORT_REASONS,
                                                       0)
    simulate_grid(TRACES, CONFIGS, bucket=BUCKET)
    rec = spans.calls()[-1]
    assert rec.trace_ops == 1540
    assert last_macro_hit_rate() == rec.macro_ops / 1540
    reasons = last_macro_abort_reasons()
    assert list(reasons) == list(MACRO_ABORT_REASONS)
    assert reasons == rec.abort_reasons and reasons is not rec.abort_reasons
    # macro-steps only ever shorten a cell's scan
    full = [math.ceil(t.total_ops / CHUNK) for t in TRACES for _ in CONFIGS]
    assert all(k <= f for k, f in zip(rec.segments.tolist(), full))
    # an empty call leaves the latest record as it was
    simulate_grid([], CONFIGS)
    assert last_macro_hit_rate() == rec.macro_ops / 1540


def test_call_counts_its_compiles():
    trs = [_trace([70, 20], 4, "a"), _trace([33], 5, "b")]
    simulate_grid(trs, CONFIGS, bucket=256, macro=False)
    assert spans.calls()[-1].compiles == 1
    simulate_grid(trs, CONFIGS, bucket=256, macro=False)
    assert spans.calls()[-1].compiles == 0


def test_jax_seconds_count_outermost_events_once():
    """An outer ``jit`` traces inner ones inside its own trace; only the
    outermost event is counted, so the three kinds never sum to more
    than the wall time they fall in."""
    @jax.jit
    def inner(x):
        return jnp.clip(x, 0.0, 1.0) + jnp.floor_divide(x, 3.0)

    @jax.jit
    def outer(x):
        return jax.lax.fori_loop(0, 3, lambda i, c: inner(c) * 2.0, x)

    before = spans.jax_seconds()
    t0 = time.perf_counter()
    outer(jnp.arange(5.0) * 1.2345678).block_until_ready()
    wall = time.perf_counter() - t0
    after = spans.jax_seconds()
    delta = {k: after[k] - before[k] for k in before}
    assert set(delta) == {"trace", "lower", "compile"}
    assert delta["trace"] > 0 and delta["lower"] > 0
    assert delta["compile"] > 0
    assert sum(delta.values()) <= wall
    # a call's record keeps the totals as they were when it began
    before = spans.jax_seconds()
    simulate_grid(TRACES, CONFIGS, bucket=BUCKET, macro=False)
    assert spans.calls()[-1].jax_s == before
