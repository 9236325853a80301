"""The reduction from trace events to the per-layer numbers."""

import json
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def ev(line, name, start_ms, dur_ms, dev=DEV):
    return [dev, line, name, start_ms * 1e6, dur_ms * 1e6]


def test_busy_is_the_union_of_op_intervals():
    events = {"device": [ev("XLA Ops", "a", 0, 4), ev("XLA Ops", "b", 2, 4),
                         ev("XLA Ops", "c", 10, 1),
                         ev("XLA Modules", "jit_f(1)", 0, 11)],
              "host": []}
    s = trace.reduce(events, window_s=0.02)
    assert s.busy_s == pytest.approx(0.007)
    assert s.op_s == pytest.approx({"a": 0.004, "b": 0.004, "c": 0.001})
    assert s.exec_s == pytest.approx({"f": 0.011})
    assert s.exec_runs == {"f": 1}


def test_busy_is_averaged_over_devices():
    events = {"device": [ev("XLA Ops", "a", 0, 4),
                         ev("XLA Ops", "a", 0, 2, dev="/device:TPU:1")],
              "host": []}
    s = trace.reduce(events, window_s=0.01)
    assert s.devices == 2 and s.busy_s == pytest.approx(0.003)


def test_gaps_are_named_by_the_innermost_host_span():
    events = {"device": [ev("XLA Ops", "a", 0, 1), ev("XLA Ops", "a", 5, 1),
                         ev("XLA Ops", "a", 7, 1)],
              "host": [["bench.step", 0.0, 10e6],
                       ["bench.submit", 2e6, 3e6]]}
    s = trace.reduce(events, window_s=0.01)
    # between the ops, and after the last one up to the span's end
    assert s.gaps == [("bench.submit", pytest.approx(0.004)),
                      ("bench.step", pytest.approx(0.002)),
                      ("bench.step", pytest.approx(0.001))]
    # host time of the step span: 10 ms minus 3 ms of device work
    assert trace.host_ms_per_span(s, "bench.step") == pytest.approx(7.0)
    assert trace.host_ms_per_span(s, "bench.fit") is None


def test_union_and_overlap():
    m = trace.union([(3, 4), (0, 1), (0.5, 2), (4, 5)])
    np.testing.assert_allclose(m, [[0, 2], [3, 5]])
    assert trace.overlap(m, 1, 3.5) == pytest.approx(1.5)
    assert trace.overlap(m, 5, 9) == 0.0
    assert len(trace.union([])) == 0


def test_executable_names():
    assert trace.executable_name("jit_run_solve_slots(1234)") == \
        "run_solve_slots"
    assert trace.executable_name("jit__write_slot_data") == \
        "_write_slot_data"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "steady_trace.json")) as f:
        return json.load(f)


def naive_union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def test_recorded_chip_trace(recorded):
    """A recorded excerpt of a chip trace (one service chunk between two
    host spans): the reduction against a naive recount."""
    s = trace.reduce(recorded, window_s=0.05)
    ops = naive_union((e[3] * 1e-9, (e[3] + e[4]) * 1e-9)
                      for e in recorded["device"] if e[1] == "XLA Ops")
    assert s.busy_s == pytest.approx(sum(b - a for a, b in ops))
    mod = [e for e in recorded["device"] if e[1] == "XLA Modules"]
    assert s.exec_runs == {"run_chunk_slots": 1}
    assert s.exec_s["run_chunk_slots"] == pytest.approx(mod[0][4] * 1e-9)
    spans = [(h[1] * 1e-9, (h[1] + h[2]) * 1e-9) for h in recorded["host"]]
    gaps = [b[0] - a[1] for a, b in zip(ops, ops[1:])]
    gaps += [ops[0][0] - spans[0][0], spans[-1][1] - ops[-1][1]]
    gaps = sorted(gaps, reverse=True)
    assert [g for _, g in s.gaps] == pytest.approx(gaps[:trace.GAPS])
    assert {who for who, _ in s.gaps} == {"bench.step"}
    host = []
    for _, start, dur in recorded["host"]:
        a, b = start * 1e-9, (start + dur) * 1e-9
        inside = sum(max(0.0, min(y, b) - max(x, a)) for x, y in ops)
        host.append(1e3 * (b - a - inside))
    assert trace.host_ms_per_span(s, "bench.step") == \
        pytest.approx(sum(host) / len(host))


def test_largest_array_of_hlo_text():
    text = ("%fusion.179 = f32[128,1048576]{1,0:T(8,128)} fusion("
            "bf16[256,1048576]{1,0} %p.1, s32[128]{0} %idx)")
    assert trace.largest_array(text) == ["bf16", 256 * 1048576]
    assert trace.largest_array("%copy.17") is None
    assert trace.largest_array("pred[] %c, f32[] %x") == ["pred", 1]


def test_arrays_are_attributed_to_their_executable():
    big, small = ["f32", 256 << 20], ["f32", 1 << 20]
    events = {"device": [
        ev("XLA Modules", "jit__transform(1)", 0, 2) + [None],
        ev("XLA Ops", "%t", 0.5, 1) + [["f32", 512 << 20]],
        ev("XLA Modules", "jit_run_solve_slots(2)", 3, 10) + [None],
        ev("XLA Ops", "%g", 4, 1) + [big],
        ev("XLA Ops", "%v", 6, 1) + [small],
        ev("XLA Ops", "%after", 14, 1) + [["f32", 1 << 30]]],
        "host": []}
    s = trace.reduce(events, window_s=0.02)
    assert s.exec_array == {"_transform": ["f32", 512 << 20],
                            "run_solve_slots": big}


def test_wider_type_wins_a_tie():
    events = {"device": [
        ev("XLA Modules", "jit_f(1)", 0, 10) + [None],
        ev("XLA Ops", "%a", 1, 1) + [["bf16", 64]],
        ev("XLA Ops", "%b", 2, 1) + [["f32", 64]],
        ev("XLA Ops", "%c", 3, 1) + [["bf16", 64]]], "host": []}
    assert trace.reduce(events, 0.01).exec_array == {"f": ["f32", 64]}
