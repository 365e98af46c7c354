"""The trace reduction on a small recorded trace (`data/small.xplane.pb`:
two reconstruct dispatches each of EC(8,3) at (2, 8, 131072) and EC(4,2) at
(1, 4, 16384), recorded on a TPU v5 lite by `record_trace.py`, PR 26), and
its interval arithmetic on hand-made inputs."""

import os

import pytest

from harness import trace

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_recorded_trace_reduces_to_its_four_executions():
    planes = trace.read_xplane(SMALL)
    assert len(planes) == 1  # one chip
    mods = planes[0]["modules"]
    assert [round(d) for _s, d, _n in mods] == [21072, 3653, 20920, 3689]  # ns, as recorded
    r = trace.reduce_planes(planes, 0.0, 200e6)
    assert r["executions"] == 4
    assert r["busy_s"] == pytest.approx((21072 + 3653 + 20920 + 3689) / 1e9)
    assert r["window_s"] == pytest.approx(0.2)
    assert 99.9 < 100.0 * (1 - r["busy_s"] / r["window_s"]) < 100.0
    # the Pallas kernel (`body`) is what takes most of a reconstruct
    assert r["device_ops"][0][0] == "body"
    assert sum(v for _k, v in r["device_ops"]) <= r["busy_s"] * 1.001


def test_clipping_to_the_window_cuts_an_execution_in_two():
    planes = trace.read_xplane(SMALL)
    s0, d0, _ = planes[0]["modules"][0]
    r = trace.reduce_planes(planes, s0 + d0 / 2, s0 + d0 + 1000)
    assert r["busy_s"] == pytest.approx(d0 / 2 / 1e9, rel=1e-6)


def test_union_gaps_and_idle_attribution():
    busy = trace.union([(10, 20), (15, 30), (50, 60), (60, 61)])
    assert busy == [(10, 30), (50, 61)]
    assert trace.gaps(busy, 0, 100) == [(0, 10), (30, 50), (61, 100)]
    assert trace.clip(busy, 20, 55) == [(20, 30), (50, 55)]
    samples = [(5, "a"), (12, "busy-not-counted"), (40, "b"), (45, "b"), (99, "a")]
    assert trace.idle_by_host_activity(busy, 0, 100, samples, 0.02) == [["a", 0.04], ["b", 0.04]]


def test_two_devices_average_their_busy_time():
    planes = [{"modules": [(0, 100, "m")], "ops": []}, {"modules": [(0, 300, "m")], "ops": []}]
    assert trace.reduce_planes(planes, 0, 1000)["busy_s"] == pytest.approx(200e-9)


def test_a_trace_with_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes([], 0, 1)


def test_op_kind_drops_numbers_and_shapes():
    assert trace.op_kind("%fusion.475 = s32[4]{0:T(128)S(1)} fusion(), kind=kLoop") == "fusion"
    assert trace.op_kind("%shift-left_or_fusion.3 = u32[1]") == "shift-left_or_fusion"
    assert trace.op_kind("%body.1 = u8[2,1,131072] custom-call(...)") == "body"
