"""Per-lane fault plans through `Session.run_batch(faults=)`.

A call with plans runs the full step loop (fault branch and recovery
handlers), one plan per lane, compiled once per Session; each lane is
bitwise equal to `engine.run_sim` under that lane's plan. A call
without plans is the crash-free loop, unchanged.
"""
import functools

import numpy as np
import pytest

from repro.core import LockSpec, Session, engine, spans
from repro.core.engine import FaultPlan

P = 16
SEEDS = np.array([3, 11, 2**31 - 2, 40])
SPECS = {
    "rma_rw": LockSpec(kind="rma_rw", P=P, fanout=(4,), T_DC=4,
                       T_L=(4, 4), T_R=8, writer_fraction=0.25),
    "rma_mcs": LockSpec(kind="rma_mcs", P=P, fanout=(4,), T_L=(4, 4)),
    "d_mcs": LockSpec(kind="d_mcs", P=P),
    "fompi_rw": LockSpec(kind="fompi_rw", P=P, writer_fraction=0.25),
}


def assert_bitwise(a, b):
    for name, x, y in zip(a._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert x.tobytes() == y.tobytes(), name


def lane(m, s):
    return engine.Metrics(*(np.asarray(x)[s] for x in m))


@functools.lru_cache(maxsize=None)
def session(kind) -> Session:
    return Session(SPECS[kind], target_acq=2)


def plan_for(sess, role, times=None) -> FaultPlan:
    """Four lanes, each crashing the lane-th process of `role`, at times
    before any process of that role is done (readers finish first)."""
    if times is None:
        times = (0.5, 1.5, 3.0, 6.0) if role == "writer" else \
            (0.1, 0.3, 0.6, 0.9)
    writers = np.flatnonzero(sess.is_writer)
    procs = writers if role == "writer" else np.flatnonzero(~sess.is_writer)
    return FaultPlan.lanes(P, procs[np.arange(4) % procs.size], times)


CASES = [(k, "writer") for k in SPECS] + [("rma_rw", "reader"),
                                          ("fompi_rw", "reader")]


@pytest.mark.parametrize("kind,role", CASES)
def test_run_batch_with_plans_equals_run_sim_per_lane(kind, role):
    sess = session(kind)
    plan = plan_for(sess, role)
    m = sess.run_batch(SEEDS, faults=plan)
    assert (np.asarray(m.n_crashed) == 1).all()
    assert (np.asarray(m.violations) == 0).all()
    assert np.asarray(m.completed).all()
    for s, seed in enumerate(SEEDS):
        one = engine.run_sim(sess.program, sess.env, sess.layout,
                             seed=int(seed), max_events=sess.max_events,
                             fault=FaultPlan(plan.crash_t[s],
                                             plan.revive_t[s]))
        assert_bitwise(lane(m, s), one)


def test_plans_under_the_sanitizer_run_lane_by_lane():
    sess = session("d_mcs")
    plan = plan_for(sess, "writer")
    want = sess.run_batch(SEEDS[:2], faults=FaultPlan(plan.crash_t[:2],
                                                      plan.revive_t[:2]))
    with engine.runtime_checks(True):
        got = sess.run_batch(SEEDS[:2], faults=FaultPlan(
            plan.crash_t[:2], plan.revive_t[:2]))
    assert_bitwise(got, want)


def test_run_sim_batch_shares_its_plan_through_the_lane_dispatch():
    sess = session("rma_rw")
    victim = int(np.flatnonzero(sess.is_writer)[0])
    m = engine.run_sim_batch(sess.program, sess.env, sess.layout,
                             seeds=SEEDS, max_events=sess.max_events,
                             fault=FaultPlan.single(P, victim, 1.5))
    lanes = FaultPlan.lanes(P, [victim] * SEEDS.size, [1.5] * SEEDS.size)
    assert_bitwise(m, sess.run_batch(SEEDS, faults=lanes))


def test_without_plans_the_crash_free_loop_runs_unchanged():
    sess = Session(SPECS["rma_rw"], target_acq=2)
    built = [r for r in spans.records() if r.name == "session.handlers"]
    assert built[-1].counters.get("program.pruned_pcs") == 5
    m = sess.run_batch(SEEDS)
    scopes = spans.op_scopes().values()
    assert not any("/fault/" in s or "/recovery/" in s for s in scopes)
    assert_bitwise(m, engine.run_sim_batch(sess.program, sess.env,
                                           sess.layout, seeds=SEEDS,
                                           max_events=sess.max_events))
    assert sess.fault_table is None


def test_calls_with_other_plans_trace_once_and_run_the_fault_program():
    sess = Session(SPECS["rma_rw"], target_acq=2)
    counted = spans.counters()
    sess.run_batch(SEEDS, faults=plan_for(sess, "writer"))
    assert spans.counters()["program.pruned_pcs"] - \
        counted["program.pruned_pcs"] == 1           # TRAP7 only
    scopes = spans.op_scopes().values()
    assert any("/fault/" in s for s in scopes)
    assert any("/handlers/recovery/pc.REC_INHERIT/" in s for s in scopes)
    before = spans.counters()["jit.traces"]
    m = sess.run_batch(SEEDS, faults=plan_for(sess, "reader"))
    assert spans.counters()["jit.traces"] == before
    assert (np.asarray(m.n_crashed) == 1).all()


def test_fault_spans_and_counters():
    sess = session("rma_mcs")
    before = spans.counters()["session.fault_lanes"]
    plan = plan_for(sess, "writer")
    crash_t = np.array(plan.crash_t)
    crash_t[1] = float(engine.INF)                   # lane 1 crashes none
    sess.run_batch(SEEDS, faults=FaultPlan(crash_t, plan.revive_t))
    assert spans.counters()["session.fault_lanes"] - before == 3
    moved = [r for r in spans.records() if r.name == "session.faults_to_device"]
    assert moved and moved[-1].parent == "session.run_batch"


@pytest.mark.parametrize("shape", ["lanes", "procs", "flat"])
def test_a_malformed_plan_raises(shape):
    sess = session("d_mcs")
    plan = {"lanes": FaultPlan.lanes(P, [0, 1], [1.0, 2.0]),
            "procs": FaultPlan.lanes(P - 1, [0, 1, 2, 3], [1.0] * 4),
            "flat": FaultPlan.single(P, 0, 1.0)}[shape]
    with pytest.raises(ValueError, match="one plan per lane"):
        sess.run_batch(SEEDS, faults=plan)


def test_fault_plan_lanes_rejects_what_it_cannot_build():
    with pytest.raises(ValueError):
        FaultPlan.lanes(P, [0, 1], [1.0])
    with pytest.raises(ValueError):
        FaultPlan.lanes(P, [P], [1.0])
    plan = FaultPlan.lanes(P, [2, 0], [1.5, 3.0])
    assert plan.crash_t.shape == plan.revive_t.shape == (2, P)
    assert plan.crash_t[0, 2] == np.float32(1.5)
    assert (np.asarray(plan.revive_t) == float(engine.INF)).all()


def test_plans_are_not_sharded_over_devices():
    sess = session("d_mcs")
    with pytest.raises(NotImplementedError, match="shard"):
        sess.run_batch(SEEDS, faults=plan_for(sess, "writer"), devices=1)


def test_a_root_waiter_takes_the_cs_of_a_writer_lost_on_a_local_pass():
    """A writer handed the whole hold by a local pass on its node, and
    lost before it consumed the pass, held the full path: the waiter
    behind its node at the root takes the CS. Waiting for the lost
    writer instead wedged these runs."""
    spec = LockSpec(kind="rma_rw", P=P, fanout=(4,), T_DC=4, T_L=(4, 4),
                    T_R=8, writer_fraction=0.5, role_seed=17)
    sess = Session(spec, target_acq=4, max_events=100_000)
    m = sess.run_batch([2097930220, 1265946037, 1785329355],
                       faults=FaultPlan.lanes(P, [5, 10, 7],
                                              [65.4684829711914,
                                               50.800106048583984,
                                               65.42594909667969]))
    assert np.asarray(m.completed).all()
    assert (np.asarray(m.violations) == 0).all()
    assert (np.asarray(m.n_crashed) == 1).all()
    assert (np.asarray(m.reclaims) >= 1).all()


def test_a_release_over_a_dead_root_successor_unwinds_its_leaf():
    """A writer that finds its root successor dead at ROOT_GETSUCC passes
    over it, then must still release its leaf level, where a successor
    linked after the writer read its NEXT word. Resuming the unwind past
    the leaf stranded that successor, and the run never completed."""
    spec = LockSpec(kind="rma_rw", P=P, fanout=(4,), T_DC=4, T_L=(4, 4),
                    T_R=8, writer_fraction=0.25, role_seed=17)
    sess = Session(spec, target_acq=4, max_events=100_000)
    m = sess.run_batch([1276249595, 1128755548],
                       faults=FaultPlan.lanes(P, [10, 5], [6.0, 15.0]))
    assert np.asarray(m.completed).all()
    assert (np.asarray(m.violations) == 0).all()
    assert (np.asarray(m.reclaims) >= 1).all()
    assert (np.asarray(m.total_acquires) < P * 4).all()
