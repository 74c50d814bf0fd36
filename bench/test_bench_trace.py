"""The trace reduction on a trace recorded on one TPU v5e.

``fixtures/empty_sweep.xplane.pb`` is one sweep of a ``splash7``
program (seven traces x NoPB and PB) with empty streams, recorded in one
profiler session between the ``sweep_start`` and ``sweep_end`` markers.
The expected values were read off the trace's events by hand:

* ``sweep_start`` at 47,494,619 ns, ``sweep_end`` at 69,496,928 ns;
* 272 events on ``XLA Ops``, the first op from 61,748,121 ns, the last
  ending at 61,872,664 ns; one container, ``%while.1075`` (2,189 ns),
  holds other ops; the 271 leaf ops add up to 123,658 ns and do not
  overlap;
* the longest leaf ops: ``%fusion.114`` 25,278 ns, ``%fusion.115``
  21,140 ns, ``%fusion.113`` 13,072 ns;
* the session's first event is 47,494,619 ns (the start marker).
"""
from pathlib import Path

import pytest

import trace_reduce

FIXTURE = str(Path(__file__).parent / "fixtures" / "empty_sweep.xplane.pb")
NS = 1e-9


MARK_START, MARK_END = 47494619, 69496928
FIRST_OP, LAST_OP, BUSY = 61748121, 61872664, 123658


@pytest.fixture(scope="module")
def sessions():
    """The fixture read as both sessions of one traced sweep: it holds
    the whole (empty) sweep, so each session sees all of the program."""
    return (trace_reduce.reduce(FIXTURE, "start"),
            trace_reduce.reduce(FIXTURE, "end"))


def test_start_window(sessions):
    start, _ = sessions
    assert start["front_s"] == pytest.approx((FIRST_OP - MARK_START) * NS)
    assert start["session_s"] == pytest.approx((LAST_OP - MARK_START) * NS)
    assert start["program_seen_s"] == pytest.approx((LAST_OP - FIRST_OP) * NS)
    assert start["program_busy_s"] == pytest.approx(BUSY * NS)
    assert start["unseen_s"] == 0.0


def test_end_window(sessions):
    _, end = sessions
    assert end["back_s"] == pytest.approx((MARK_END - LAST_OP) * NS)
    assert end["session_s"] == pytest.approx((MARK_END - MARK_START) * NS)
    assert end["program_seen_s"] == pytest.approx((LAST_OP - FIRST_OP) * NS)
    assert end["program_busy_s"] == pytest.approx(BUSY * NS)


def test_metrics_and_breakdown(sessions):
    import harness
    # the marker-to-marker time as the host clock reads a sweep
    red = trace_reduce.combine(*sessions, (MARK_END - MARK_START) * NS)
    run = {"sweeps": [((MARK_END - MARK_START) * NS, 0.0)], "trace": red}
    scan = harness.load_metric("scan_ms").read(run)
    assert scan == pytest.approx((LAST_OP - FIRST_OP) * 1e-6)
    host = harness.load_metric("host_ms").read(run)
    assert host == pytest.approx(
        ((FIRST_OP - MARK_START) + (MARK_END - LAST_OP)) * 1e-6)
    assert red["busy_s"] == pytest.approx(BUSY * NS)
    assert red["window_s"] == pytest.approx((MARK_END - MARK_START) * NS)
    idle = harness.load_metric("device_idle_share").read(run)
    assert idle == pytest.approx(
        100.0 * (1.0 - BUSY / (MARK_END - MARK_START)))
    ops = red["breakdown"]["device_ops"]
    assert [n for n, _ in ops[:3]] == ["%fusion.114", "%fusion.115",
                                       "%fusion.113"]
    assert ops[0][1] == pytest.approx(2 * 25278 * NS)
    assert "%while.1075" not in dict(ops)
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) == trace_reduce.TOP
    # the longest gap is the host front end, seen in both sessions
    assert [n.split(":")[0] for n, _ in gaps[:2]] == [
        "start window, before the program", "end window, before the program"]
    assert gaps[0][1] == pytest.approx((FIRST_OP - MARK_START) * NS)
    assert any(n.startswith("end window, after the program")
               and d == pytest.approx((MARK_END - LAST_OP) * NS)
               for n, d in gaps)


def test_leaves_leave_out_containers():
    ev = [(0, 10, "%while.1"), (1, 3, "%a"), (3, 5, "%b"), (12, 13, "%c")]
    assert list(trace_reduce.leaves(ev)) == [False, True, True, True]


def test_bodiless_control_flow_is_unseen():
    """A ``while`` with no recorded op inside is neither busy nor idle;
    a leaf that is not control flow is busy."""
    ev = [(0, 10, "%while.1 = (s32[]) while(%t)"), (1, 3, "%a"),
          (12, 40, "%while.2 = (s32[]) while(%u)"),
          (41, 50, "%conditional.3 = s32[] conditional(%p)"),
          (50, 60, "%fusion.4 = f32[8] fusion(%x)"),
          (60, 61, "%whiles.5 = f32[8] fusion(%y)")]
    leaf = trace_reduce.leaves(ev)
    assert list(trace_reduce.bodiless(ev, leaf)) == [
        False, False, True, True, False, False]


def test_combine_weights_the_program_by_its_span():
    """Host time outside the program is idle; inside it, the busy share
    the two sessions saw, over the span they did not see."""
    start = {"front_s": 0.02, "program_seen_s": 0.10, "program_busy_s": 0.09,
             "unseen_s": 0.05, "ops": {"%a": 0.09}, "gaps": []}
    end = {"back_s": 0.01, "program_seen_s": 0.10, "program_busy_s": 0.07,
           "unseen_s": 0.0, "ops": {"%a": 0.07}, "gaps": []}
    red = trace_reduce.combine(start, end, 4.03)
    assert red["span_s"] == pytest.approx(4.0)
    assert red["busy_s"] == pytest.approx(4.0 * 0.8)
    assert red["window_s"] == 4.03
    assert red["unseen_s"] == pytest.approx(0.05)
    with pytest.raises(RuntimeError):
        trace_reduce.combine(dict(start, program_seen_s=0.0),
                             dict(end, program_seen_s=0.0), 4.03)
