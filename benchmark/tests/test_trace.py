"""The trace reduction on a small trace recorded on the chip.

``small_trace.xplane.pb`` (16 KB) was recorded on one TPU v5 lite chip in
PR 26: six calls of a jitted ``tanh(x @ x).sum()`` on a 2048x2048 bf16
matrix, each inside a ``TraceAnnotation("bench_step")``, with sleeps of
0, 20, 40, 0, 20 ms between them.  The python tracer was off."""

import os

import jax
import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "small_trace.xplane.pb")
trace = harness.load_module("", "trace")


def _ops_by_hand():
    """The XLA Ops events straight from the file, no reduction code."""
    profile = jax.profiler.ProfileData.from_file(RECORDED)
    (plane,) = [p for p in profile.planes if p.name == "/device:TPU:0"]
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    return sorted((e.start_ns, e.start_ns + e.duration_ns) for e in line.events)


def test_busy_window_and_idle_share():
    got = trace.reduce(RECORDED)
    events = _ops_by_hand()
    assert len(events) == 18            # copy-start, copy-done, fusion; x 6
    # nothing overlaps on this trace, so busy is the plain sum
    assert all(a >= prev_b for (a, _), (_, prev_b) in zip(events[1:], events))
    busy = sum(b - a for a, b in events)
    window = events[-1][1] - events[0][0]
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert got["window_s"] == pytest.approx(window / 1e9, rel=1e-12)
    assert got["idle_pct_worst"] == pytest.approx(
        100.0 * (1.0 - busy / window), rel=1e-9)
    # as read on the chip when it was recorded
    assert got["busy_s"] == pytest.approx(0.000541274, rel=1e-6)
    assert got["window_s"] == pytest.approx(0.086090452, rel=1e-6)


def test_top_operations_are_named_shortly():
    ops = trace.reduce(RECORDED)["device_ops"]
    assert len(ops) == 3 and ops[0][0] == "fusion kOutput"
    assert ops[0][1] == pytest.approx(0.000541177, rel=1e-6)
    assert {name for name, _ in ops} == {"fusion kOutput", "copy-start",
                                         "copy-done"}


def test_longest_gaps_are_the_sleeps_and_blame_the_host():
    gaps = trace.reduce(RECORDED)["idle_gaps"]
    assert [round(seconds, 3) for _, seconds in gaps[:5]] == [
        0.041, 0.022, 0.021, 0.001, 0.001]
    assert all(who == "python: bench_step" for who, _ in gaps[:3])
    assert len(gaps) <= 10


def test_union_merges_overlaps_and_nesting():
    covered, gaps = trace._union([(0, 10), (5, 12), (20, 30), (22, 25),
                                  (30, 31)])
    assert covered == 12 + 11
    assert gaps == [(8, 12, 20)]


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(RECORDED, device_prefix="/device:GPU:")
