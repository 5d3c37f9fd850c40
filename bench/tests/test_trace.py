"""bench/trace.py on a small trace in the profiler's own format."""
import pytest
from jax.profiler import ProfileData

from bench import trace
from bench.spec import BENCH


@pytest.fixture(scope="module")
def profile():
    text = (BENCH / "testdata" / "small_trace.pbtxt").read_text()
    text = "\n".join(l for l in text.splitlines() if not l.startswith("#"))
    return ProfileData.from_text_proto(text)


def test_busy_union_and_idle_share(profile):
    s = trace.read_profile(profile, chips=1)
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(5e-6)
    assert s.idle_share == pytest.approx(0.5)


def test_busy_averages_over_chips(profile):
    s = trace.read_profile(profile, chips=2)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((5e-6 + 1e-6) / 2)


def test_program_time_by_pattern(profile):
    s = trace.read_profile(profile, chips=1)
    sec, n = s.program(r"jit_step$")
    assert (n, sec) == (2, pytest.approx(4e-6))
    assert s.program(r"jit_admit$") == (pytest.approx(1e-6), 1)
    assert s.program(r"jit_nothing$") == (0.0, 0)


def test_top_ops_and_gap_attribution(profile):
    s = trace.read_profile(profile, chips=1)
    assert s.top_ops[0] == ("fusion.1", pytest.approx(3e-6))
    # [6, 9) lies mostly in the harness's sleep; [3, 5) half in a step.
    assert s.idle_gaps[0] == ("bench.sleep", pytest.approx(3e-6))
    assert s.idle_gaps[1] == ("bench.step", pytest.approx(2e-6))


def test_union_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_missing_window_is_an_error():
    p = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        trace.read_profile(p, chips=1)
