"""Rehearse the multicast cells on the CPU at a small size: the plain
reference agrees with the program, and every breakage of the timed path
reads ``correct`` false.  ``testbed.steady`` is the open-loop mix that
``BENCHMARK.json`` does not list yet; it is rehearsed as an entry of
its own."""

import numpy as np
import pytest

import run as bench_run
import control
from benchlib import harness

SMALL = {"n_nodes": 4, "n_senders": 4}
STEADY = {"name": "testbed.steady", "config": "spindle_testbed",
          "traffic": "steady", "chips": 1}
ENTRIES = {"testbed.saturated": None, "testbed.steady": STEADY}
CELLS = sorted(ENTRIES)


@pytest.mark.parametrize("cell", CELLS)
def test_small_cell_is_correct(cell):
    run, line = bench_run.run_cell(cell, 2 ** 33 + 5, 0.5, False,
                                   require_chip=False,
                                   config_override=SMALL,
                                   entry=ENTRIES[cell])
    assert line["correct"] is True
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert list(line)[-1] == "checks"
    assert run.attempted > 0 and run.failed == 0
    assert run.values["window_rounds"] > 10


def test_full_testbed_matches_reference():
    _, line = bench_run.run_cell("testbed.saturated", 3, 0.3, False,
                                 require_chip=False)
    assert line["correct"] is True


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    _, line = bench_run.run_cell(cell, 11, 0.3, False, require_chip=False,
                                 config_override=SMALL,
                                 wrap_program=control.FAULTS[fault](),
                                 entry=ENTRIES[cell])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_end_to_end_metrics_only_where_listed():
    _, sat = bench_run.run_cell("testbed.saturated", 1, 0.2, False,
                                require_chip=False, config_override=SMALL)
    run, steady = bench_run.run_cell("testbed.steady", 1, 0.2, False,
                                     require_chip=False,
                                     config_override=SMALL, entry=STEADY)
    assert set(sat["metrics"]) == {"delivered_msgs_per_s", "setup_s"}
    assert set(steady["metrics"]) == {"setup_s"}       # not a cell yet
    assert harness.reader("delivery_p95_ms").read(run) > 0
    assert np.isfinite(sat["metrics"]["delivered_msgs_per_s"]["value"])
