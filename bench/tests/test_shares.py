"""The shares of a peak, ``mfu`` and ``paged_decode_roofline``, on a whole
tiny traced run of each cell on the CPU: each reads a value in (0, 100].
And where a backlog's trace lies."""
import time

import pytest

from bench import harness, trace
from bench.spec import load_limits, metric_reader
from bench.tests.conftest import CELLS, tiny_cell


def _traced_run(name, monkeypatch):
    """The result object and the ``Run`` its readers saw."""
    runs = []
    real = harness.metric_reader

    def capture(metric):
        read = real(metric)

        def wrapped(run):
            runs.append(run)
            return read(run)
        return wrapped

    monkeypatch.setattr(harness, "metric_reader", capture)
    out = harness.run_cell(tiny_cell(name), 2**32 + 21, 3.0, True,
                           time.perf_counter(), require_tpu=False,
                           limits=load_limits(name))
    return out, runs[0]


@pytest.mark.parametrize("name", CELLS)
def test_shares_read_within_a_hundred(name, monkeypatch):
    out, run = _traced_run(name, monkeypatch)
    mfu = out["metrics"]["mfu"]
    assert mfu["unit"] == "%" and 0.0 < mfu["value"] <= 100.0
    # A CPU trace has no device plane, so the roofline finds no decode
    # program to read and is left out, never reported as 0.
    assert "paged_decode_roofline" not in out["metrics"]
    # Given the decode program the whole window as its device time, the
    # work the window's decode steps served is a share of it.
    steps = run.stats1["steps"] - run.stats0["steps"]
    assert steps > 0
    run.trace = trace.Summary(window_s=run.seconds, busy_s=run.seconds,
                              devices=1,
                              modules={"jit_step": (run.seconds, steps)},
                              top_ops=[], idle_gaps=[])
    run.trace_t0, run.trace_t1 = run.win0, run.win1
    share = metric_reader("paged_decode_roofline")(run)
    assert share is not None and 0.0 < share <= 100.0


def test_backlog_trace_holds_an_admission(monkeypatch):
    """A backlog's window opens with every slot mid-decode; its trace starts
    once a request has finished, so the admission into the freed slot, and
    with it ``prefill_ms``, falls inside the trace."""
    name = next(c for c in CELLS
                if tiny_cell(c).traffic["arrival"]["kind"] == "backlog")
    monkeypatch.setattr(harness, "TRACE_S", 1.0)
    _, run = _traced_run(name, monkeypatch)
    assert run.win0 < run.trace_t0 < run.trace_t1 <= run.win1
    assert any(r.done and r.times[-1] < run.trace_t0 for r in run.reqs)
    assert run.prefills(run.trace_t0, run.trace_t1)
