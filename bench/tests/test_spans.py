"""Self-checks of the span reduction (``benchlib.spans``) and of the
round split tool (``tools/step_split.py``), on hand-built spans and on a
window recorded on the CPU."""

import numpy as np
import pytest

import step_split
from benchlib import spans

T = ("host", 0)                     # one thread


def _nested():
    # a window [0, 100] holding two steps; the first has a dispatch and
    # two readbacks nested in it; the harness's bench.step wraps each
    return [(0, 100, "bench.window", T),
            (5, 50, "bench.step", T), (6, 48, "spindle.stream.step", T),
            (10, 20, "spindle.stream.dispatch", T),
            (22, 40, "spindle.stream.readback", T),
            (42, 46, "spindle.stream.readback", T),
            (60, 90, "bench.step", T), (61, 89, "spindle.stream.step", T),
            (62, 64, "spindle.stream.dispatch", T),
            (70, 80, "spindle.stream.readback", T)]


def test_self_time_is_less_the_nested_spans():
    tab = spans.table(_nested(), 0, 100)
    step = tab["spindle.stream.step"]
    assert step.count == 2
    assert step.total_s == pytest.approx(70e-9)
    # (42 - 10 - 18 - 4) + (28 - 2 - 10)
    assert step.self_s == pytest.approx(26e-9)
    assert step.max_s == pytest.approx(42e-9)
    assert tab["bench.step"].self_s == pytest.approx(5e-9)
    assert tab["spindle.stream.readback"] == (3, pytest.approx(32e-9),
                                              pytest.approx(32e-9),
                                              pytest.approx(18e-9))
    assert tab["bench.window"].self_s == pytest.approx(25e-9)
    # a span on another thread nests in nothing of this one
    other = spans.table(_nested() + [(30, 35, "spindle.stream.readback",
                                      ("host", 1))], 0, 100)
    assert other["spindle.stream.step"].self_s == pytest.approx(26e-9)
    assert other["spindle.stream.readback"].self_s == pytest.approx(37e-9)


def test_gaps_split_by_the_innermost_span():
    sp = [s for s in _nested() if s[2] != "bench.window"]
    pieces = spans.innermost(sp, 0, 100, "bench.window")
    assert pieces[0] == (0, 5, "bench.window")
    assert pieces[-1] == (90, 100, "bench.window")
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    # the device busy over [15, 30] and [65, 75]
    busy = np.asarray([[15, 30], [65, 75]], float)
    gaps = spans.idle_gaps(busy, 0, 100)
    assert gaps.tolist() == [[0, 15], [30, 65], [75, 100]]
    got = spans.split_gaps(gaps, pieces)
    want = {"bench.window": 5 + 10 + 10,     # [0,5] [50,60] [90,100]
            "bench.step": 1 + 2 + 1 + 1,     # [5,6] [48,50] [60,61] [89,90]
            "spindle.stream.step": 4 + 2 + 2 + 1 + 1 + 9,
            "spindle.stream.dispatch": 5 + 2,
            "spindle.stream.readback": 10 + 4 + 5}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(75e-9)   # all the gaps


def test_program_starts_inside_steps():
    steps = [(6, 48, "spindle.stream.step", T),
             (61, 89, "spindle.stream.step", T)]
    off = spans.outside_ns(np.asarray([10.0, 62.0, 55.0, 1.0, 95.0]),
                           steps)
    assert off.tolist() == [0.0, 0.0, -6.0, -5.0, 6.0]


def test_split_of_a_window_recorded_here():
    out = step_split.measure(
        "testbed.saturated", 2 ** 33 + 5, 0.5, True, require_chip=False,
        config_override={"n_nodes": 4, "n_senders": 4},
        trace_names={"device_prefix": "/host:CPU",
                     "op_line": "tf_XLAPjRtCpuClient"})
    assert out["readbacks_per_round"] == 6.0
    assert out["step_spans"] == out["window_rounds"] > 10
    tab = out["spans"]
    assert tab["spindle.stream.readback"][0] == 2 * out["step_spans"]
    assert tab["spindle.stream.dispatch"][0] == out["step_spans"]
    assert 50 < out["split_share_of_round_pct"] <= 100
    assert sum(g[2] for g in out["idle_gaps"]) == pytest.approx(100)
