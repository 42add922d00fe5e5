"""Per-layer readers on hand-made counters and traces."""
import numpy as np
import pytest

from bench.harness import layer_reader
from bench.trace import DeviceOps, summarize


class FakeSim:
    def __init__(self, calls):
        self._calls = calls

    def lanes(self):
        return self._calls


def test_lane_fill_counts_trips_of_finished_lanes_as_idle():
    calls = [np.array([5, 6, 7, 8]), np.array([2, 2])]
    fill = layer_reader("sim.lane_fill")({"driver": FakeSim(calls)})
    # useful 26 + 4 events; capacity 4 lanes x 8 trips + 2 lanes x 2
    assert fill == pytest.approx(30 / 36)


def test_lane_fill_of_equal_lanes_is_one():
    calls = [np.array([4, 4, 4])] * 3
    assert layer_reader("sim.lane_fill")({"driver": FakeSim(calls)}) == 1.0


def test_us_per_trip_is_busy_time_over_the_most_frequent_op():
    # device 0: 3 trips of (cond, body) in 600 ns of busy time; device 1:
    # 2 trips in 800 ns; an op outside the loop runs once.
    d0 = [(0, 50, "copy.1")] + [(100 + 200 * i, 200 + 200 * i, name)
                                for i in range(3)
                                for name in ("cond.1",)] + [
        (200 + 200 * i, 250 + 200 * i, "body.2") for i in range(3)]
    d1 = [(0, 200, "cond.1"), (200, 400, "body.2"), (400, 600, "cond.1"),
          (600, 800, "body.2")]
    s = summarize([DeviceOps("/device:TPU:0", d0),
                   DeviceOps("/device:TPU:1", d1)],
                  [(0, 1000, "bench.window")])
    assert s.trips() == [3, 2]
    got = layer_reader("sim.us_per_trip")({"trace": s})
    busy0 = 50 + 3 * 100 + 3 * 50
    assert got == pytest.approx((busy0 / 3 + 800 / 2) / 2 * 1e-3)


def test_readers_return_nothing_without_a_trace():
    ctx = {"driver": FakeSim([np.array([1])]),
           "trace": None, "device_kind": "TPU v5 lite"}
    for name in ("sim.us_per_trip", "sim.idle_share"):
        assert layer_reader(name)(ctx) is None
