"""Readers of the program's own counters, on hand-built runs and on a whole
traced run of the chat cell at a CPU size."""
import time

import pytest

from bench import harness
from bench.spec import load_limits, metric_reader
from bench.tests.conftest import tiny_cell

BLOCKED = "sidecar_blocked_share.chat"


def _run(stats0, stats1, seconds=30.0):
    return harness.Run(cell=None, shapes=None, arch=None, chips=1, peaks={},
                       reqs=[], win0=100.0, win1=100.0 + seconds,
                       stats0=stats0, stats1=stats1)


def test_blocked_share_is_the_windows_change_over_its_seconds():
    read = metric_reader(BLOCKED)
    run = _run({"sidecar": {"blocked_s": 1.0}},
               {"sidecar": {"blocked_s": 2.5}})
    assert read(run) == pytest.approx(5.0)
    never = _run({"sidecar": {"blocked_s": 0.0}},
                 {"sidecar": {"blocked_s": 0.0}})
    assert read(never) == 0.0


@pytest.mark.parametrize("stats", [{}, {"sidecar": {"completed": 3}}])
def test_blocked_share_reads_nothing_without_the_counter(stats):
    """A program that does not count blocked submits gives no reading."""
    assert metric_reader(BLOCKED)(_run(stats, stats)) is None


def test_traced_run_reports_blocked_share():
    name = "smollm-360m.chat-prefix-sat"
    cell = tiny_cell(name)
    out = harness.run_cell(cell, 2**32 + 11, 3.0, True, time.perf_counter(),
                           require_tpu=False, limits=load_limits(name))
    share = out["metrics"][BLOCKED]
    assert share["unit"] == "%" and 0.0 <= share["value"] <= 100.0
