"""The rw1024.seeds8 cell's loop and check, in-process at P=16 on the
CPU: sound runs are correct, and each fault of its timed path is not."""
import numpy as np
import pytest

from bench.tests import sim_faults
from bench.tests.tiny import driver_for, sim_window, tiny_cell


@pytest.fixture(scope="module")
def drv():
    d = driver_for(tiny_cell("rma_rw_p1024", "seeds8"))
    d.setup()
    return d


def test_a_sound_window_is_correct(drv):
    ok, checks = sim_window(drv)
    assert ok, checks
    assert checks == {"runs_differing": 0, "time_gap": 0.0,
                      "gate_failures": 0}
    assert drv.end_to_end()["sim_runs_per_s"] > 0


def test_the_sample_holds_every_lane_position_and_the_longest_run(drv):
    sim_window(drv)
    events = np.stack(drv.lanes())
    sample = drv.sample()
    assert sorted(s for _, s in sample) == list(range(events.shape[1]))
    assert max(events[c, s] for c, s in sample) == events.max()
    assert all(0 <= c < events.shape[0] for c, _ in sample)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_a_broken_timed_path_is_not_correct(drv, fault):
    call = sim_faults.plant(drv, sim_faults.FAULTS[fault])
    try:
        ok, checks = sim_window(drv)
    finally:
        drv.call = call
    assert not ok, checks


def test_the_control_fails_the_comparison():
    from bench.reference import lock_sim

    cell = tiny_cell("rma_rw_p1024", "seeds8")
    seeds = [5, 6, 7]
    want = lock_sim.run(cell.cfg, seeds)
    control = lock_sim.run(cell.cfg, seeds, exclusive=False)
    from bench.systems.lock_sim import compare_run
    got = [compare_run(dict(c, t_recover=3.4e38, t_crash=3.4e38), w)
           for c, w in zip(control, want)]
    assert all(d for d, _ in got)
