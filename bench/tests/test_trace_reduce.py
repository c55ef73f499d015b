"""The trace reduction on a small recorded trace (no chip needed)."""
import os
import sys

import pytest

jax = pytest.importorskip("jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce  # noqa: E402
import work  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def pd():
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        return jax.profiler.ProfileData.from_text_proto(f.read())


WINDOW = (500.0, 10500.0)


def test_profile_start_is_the_wall_clock_at_time_zero(pd):
    assert trace_reduce.profile_start(pd) == 1_000_000_000


def test_busy_union_and_idle_gaps(pd):
    # ops cover [1000, 4000) and [7000, 10000) inside [500, 10500)
    r = trace_reduce.reduce(pd, WINDOW,
                            samples=[(600.0, "a:f"), (5000.0, "b:g"),
                                     (5500.0, "b:g"), (6000.0, "a:f")])
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(6000e-9)
    # gaps: [4000, 7000) 3000 ns, [500, 1000) 500, [10000, 10500) 500
    assert r["idle_gaps"][0] == ["b:g", pytest.approx(3000e-9)]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [3000e-9, 500e-9, 500e-9])
    assert r["idle_gaps"][1][0] == "a:f"
    assert r["idle_gaps"][2][0] == "unsampled"


def test_pallas_time_bytes_and_entries(pd):
    r = trace_reduce.reduce(pd, WINDOW)
    assert r["pallas_s"] == pytest.approx(2000e-9)
    assert r["pallas_bytes"] == 8 * 128 * 4 * 2 + 16 * 4
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops == {"jit_glue/fusion": pytest.approx(3000e-9),
                   "jit_round/pallas:custom-call": pytest.approx(2000e-9),
                   "jit_round/fusion": pytest.approx(2000e-9)}


def test_window_clips_device_time(pd):
    r = trace_reduce.reduce(pd, (2000.0, 8000.0))
    assert r["busy_s"] == pytest.approx(3000e-9)        # [2000,4000)+[7000,8000)
    assert r["pallas_s"] == pytest.approx(1000e-9)


def test_union_clips_and_merges():
    assert trace_reduce.union([(0, 5), (3, 8), (10, 12), (11, 20)], 1, 15) \
        == [(1, 8), (10, 15)]
    assert trace_reduce.gaps([(1, 8), (10, 15)], 0, 16) == \
        [(0, 1), (8, 10), (15, 16)]


def test_no_device_ops_reads_nothing():
    empty = jax.profiler.ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }')
    assert trace_reduce.reduce(empty, WINDOW) == {}
    assert work.hlo_bytes("not hlo") == 0
