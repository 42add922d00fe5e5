"""The rw1024.crash8 cell's loop and check, in-process on the CPU at
P=16 (and the crash reference at P=32 over two nodes): sound runs are
correct, the reference agrees with the program on seeded crashes, and
each fault of the timed path is not correct."""
import itertools

import numpy as np
import pytest

from bench import harness
from bench.reference import lock_sim_crash
from bench.systems import lock_sim_crash as crash_cell
from bench.tests import sim_faults
from bench.tests.tiny import driver_for, sim_window, tiny_cell

CONFIG, TRAFFIC = "rma_rw_p1024_crash1", "crash8"


def tiny_crash_cell() -> harness.Cell:
    """The cell at P=16, whose runs end at about 61 us (1,116 us at
    P=1024): its crash points move to the same share of a run, so that
    some plans fire and some do not, their victim having finished
    first."""
    cell = tiny_cell(CONFIG, TRAFFIC)
    points = cell.mix["points"]
    points["crash_us"] = [t * 61 / 1116 for t in points["crash_us"]]
    return cell


@pytest.fixture(scope="module")
def drv():
    d = driver_for(tiny_crash_cell())
    d.setup()
    return d


def test_a_sound_window_is_correct(drv):
    ok, checks = sim_window(drv)
    assert ok, checks
    assert checks == {"runs_differing": 0, "time_gap": 0.0,
                      "gate_failures": 0}
    assert drv.end_to_end()["sim_runs_per_s"] > 0
    crashed = np.concatenate([np.asarray(m.n_crashed)
                              for _, m, _, _ in drv.calls])
    assert set(crashed.tolist()) <= {0, 1} and crashed.any()
    reclaims = np.concatenate([np.asarray(m.reclaims)
                               for _, m, _, _ in drv.calls])
    assert (reclaims > 0).any()


def _compare(cfg, session, call, victims):
    m = session.run_batch(call.seeds, faults=_plan(session, victims,
                                                   call.crash_t))
    want = lock_sim_crash.run(cfg, call.seeds, victims, call.crash_t)
    out = []
    for s, ref in enumerate(want):
        got = {f: np.asarray(x)[s] for f, x in zip(m._fields, m)}
        out.append((crash_cell.compare_run(got, ref), ref))
    return out


def _plan(session, victims, times):
    from repro.core.engine import FaultPlan

    return FaultPlan.lanes(session.spec.P, victims, times)


def test_the_reference_equals_the_program_on_seeded_crashes(drv):
    gen = drv.traffic
    calls = gen.calls(drv.mix, 2**40 + 9)
    fired = []
    for _ in range(3):
        call = next(calls)
        victims = gen.victims(call, drv.is_writer)
        for (differ, gap), ref in _compare(drv.cfg, drv.session, call,
                                           victims):
            assert not differ and gap == 0.0
            fired.append(ref["n_crashed"])
    assert set(fired) == {0, 1}


def test_the_reference_equals_the_program_over_two_nodes():
    """P=32 on two nodes of 16, a quarter of them writers: victims on
    both nodes, so that a second node's queue is recovered too."""
    from repro.core import Session
    from bench.systems.lock_sim import lock_spec

    cell = tiny_crash_cell()
    cell.cfg["lock"].update(P=32, fanout=[2], writer_fraction=0.25,
                            T_DC=4, T_L=[4, 2], T_R=8)
    session = Session(lock_spec(cell.cfg), target_acq=4)
    writers = np.flatnonzero(session.is_writer)
    readers = np.flatnonzero(~session.is_writer)
    assert {w // 16 for w in writers} == {0, 1}
    call = crash_cell_call(seeds=[7, 2**31 - 5, 123456789, 42],
                           crash_t=[0.5, 6.0, 15.0, 0.8])
    victims = [writers[0], writers[-1], writers[len(writers) // 2],
               readers[-1]]
    got = _compare(cell.cfg, session, call, victims)
    assert all(not d and g == 0.0 for (d, g), _ in got)
    assert sum(ref["reclaims"] for _, ref in got) >= 2


def test_a_repair_with_no_successor_behind_the_dead_costs_its_next_word():
    """P=32 on four nodes: a REC_TAILFIX whose dead node has no
    successor is charged an atomic on the tail and a read of the dead
    node's NEXT word, as the reference charges it. A read of entity 0's
    STATUS word, on another node, puts these four runs up to 39% apart
    from the reference."""
    from repro.core import Session
    from bench.systems.lock_sim import lock_spec

    cell = tiny_crash_cell()
    cell.cfg["lock"].update(P=32, fanout=[4], writer_fraction=0.25,
                            T_DC=4, T_L=[4, 2], T_R=8)
    session = Session(lock_spec(cell.cfg), target_acq=4)
    call = crash_cell_call(seeds=[1406688236, 1758908238, 567529613,
                                  1250624116],
                           crash_t=[13.729598, 1.9550818, 0.8097645,
                                    30.705364])
    got = _compare(cell.cfg, session, call, [29, 29, 29, 24])
    assert all(not d and g == 0.0 for (d, g), _ in got)
    assert all(ref["reclaims"] > 0 for _, ref in got)


def crash_cell_call(seeds, crash_t):
    gen = harness.traffic_generator({"generator": "crash_points"})
    n = len(seeds)
    return gen.Call(np.asarray(seeds, np.int32), np.zeros(n, bool),
                    np.zeros(n), np.asarray(crash_t, np.float32))


def test_the_reference_refuses_what_the_configuration_does_not_state():
    cell = tiny_crash_cell()
    for failure in ({"crashes_per_schedule": 2}, {"revive": True}):
        cfg = dict(cell.cfg, failure=dict(cell.cfg["failure"], **failure))
        with pytest.raises(lock_sim_crash.Unsupported):
            lock_sim_crash.run(cfg, [1], [0], [1.0])
    cfg = dict(cell.cfg, lock=dict(cell.cfg["lock"], kind="rma_mcs"))
    with pytest.raises(lock_sim_crash.Unsupported):
        lock_sim_crash.run(cfg, [1], [0], [1.0])


# ------------------------------------------------------ planted faults
def plans_dropped(drv, inputs):
    """The call runs its seeds without their plans."""
    return drv.session.run_batch(inputs.seeds)


def recovery_skipped(drv, inputs):
    """The fault loop runs with every recovery pc sent to the trap."""
    from repro.core import engine

    s = drv.session
    table = s.fault_table
    s.fault_table = engine.prune(
        s.program.build(s.env),
        engine.unreachable_pcs(s.program, s.env, faults=False), faults=True)
    try:
        return drv.traffic.submit(s, inputs)
    finally:
        s.fault_table = table


def reclaim_before_the_lease(drv, inputs):
    """A run that reclaimed reports its reclaim half a lease after the
    crash."""
    m = drv.traffic.submit(drv.session, inputs)
    lease = np.float32(drv.cfg["failure"]["lease_us"])
    t_c, t_r = np.asarray(m.t_crash), np.array(m.t_recover)
    early = np.asarray(m.reclaims) > 0
    t_r[early] = t_c[early] + lease / 2
    return m._replace(t_recover=t_r)


def answer_altered(drv, inputs):
    return sim_faults.answer_altered(
        drv, drv.traffic.submit(drv.session, inputs))


# Each fault, and the check that has to catch it: a dropped plan
# leaves every run sound, and only the reference sees that a victim
# still running at its crash time did not crash.
CALL_FAULTS = {"plans_dropped": (plans_dropped, "runs_differing"),
               "recovery_skipped": (recovery_skipped, "gate_failures"),
               "reclaim_before_the_lease": (reclaim_before_the_lease,
                                            "gate_failures"),
               "answer_altered": (answer_altered, None)}


@pytest.mark.parametrize("fault", sorted(CALL_FAULTS))
def test_a_broken_timed_path_is_not_correct(drv, fault):
    planted, caught_by = CALL_FAULTS[fault]
    call = drv.call
    drv.call = lambda inputs: planted(drv, inputs)
    try:
        ok, checks = sim_window(drv)
    finally:
        drv.call = call
    assert not ok, checks
    if caught_by is not None:
        assert checks[caught_by] > 0, checks


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_batch_is_not_correct(drv, fault):
    call = sim_faults.plant(drv, sim_faults.FAULTS[fault])
    try:
        ok, checks = sim_window(drv)
    finally:
        drv.call = call
    assert not ok, checks


# ----------------------------------------------------------- generator
def test_the_generator_repeats_from_the_seed_and_keeps_its_roles(drv):
    gen, mix = drv.traffic, drv.mix
    seed = 2**33 + 5
    a, b = gen.calls(mix, seed), gen.calls(mix, seed)
    seen = set()
    for _ in range(5):
        x, y = next(a), next(b)
        for field in ("seeds", "writer", "pick", "crash_t"):
            assert np.array_equal(getattr(x, field), getattr(y, field))
        victims = gen.victims(x, drv.is_writer)
        lanes = np.arange(mix["seeds_per_call"])
        writer_lane = lanes < mix["writer_lanes"]
        assert (drv.is_writer[victims] == writer_lane).all()
        assert np.array_equal(x.crash_t, np.float32(
            mix["points"]["crash_us"]))
        assert not seen & set(gen.lane_seeds(x))
        seen |= set(gen.lane_seeds(x))
    other = next(gen.calls(mix, seed + 1))
    assert not np.array_equal(other.seeds, next(gen.calls(mix, seed)).seeds)
    bad = dict(mix, points=dict(mix["points"], pick=[0.5]))
    with pytest.raises(ValueError, match="crash points"):
        next(gen.calls(bad, seed))


def test_the_gate_holds_each_guarantee(drv):
    """Each guarantee alone fails the gate on an otherwise sound call."""
    # A call in which some plan did not fire, its victim done before it.
    gen = drv.traffic
    for call in itertools.islice(gen.calls(drv.mix, 2**35 + 1), 8):
        m = gen.submit(drv.session, call)
        if (np.asarray(m.n_crashed) == 0).any():
            break
    else:
        pytest.fail("every plan of 8 calls fired")
    victims = drv.traffic.victims(call, drv.is_writer)
    target = int(drv.cfg["workload"]["target_acq"])
    lease = np.float32(drv.cfg["failure"]["lease_us"])
    gate = crash_cell.gate
    assert not gate(m, victims, call.crash_t, target, lease).any()
    survivor = (victims + 1) % drv.cfg["lock"]["P"]
    acq = np.array(m.per_proc_acq)
    acq[np.arange(acq.shape[0]), survivor] -= 1
    broken = [m._replace(violations=np.asarray(m.violations) + 1),
              m._replace(n_crashed=np.asarray(m.n_crashed) + 1),
              m._replace(per_proc_acq=acq),
              m._replace(t_crash=np.asarray(call.crash_t) - 0.5)]
    for b in broken:
        assert gate(b, victims, call.crash_t, target, lease).all()
    # A run whose plan did not fire has a victim at its target, no crash
    # time and no reclaim.
    spared = np.asarray(m.n_crashed) == 0
    lanes = np.arange(acq.shape[0])
    short = np.array(m.per_proc_acq)
    short[lanes, victims] -= 1
    reclaims = np.array(m.reclaims)
    reclaims[spared] = 1
    for b in [m._replace(per_proc_acq=short),
              m._replace(reclaims=reclaims)]:
        assert gate(b, victims, call.crash_t, target, lease)[spared].all()
