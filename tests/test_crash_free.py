"""The crash-free step loop.

A run without a `FaultPlan` cannot crash, so `Session` and
`engine.run_sim(fault=None)` compile a loop with no fault branch whose
switch holds only the pcs such a run reaches; the declared dead and
recovery pcs share one trap slot. Any `FaultPlan` takes the full loop.
Both must give the same results, bit for bit.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import LockSpec, Session, engine, spans
from repro.core.engine import FaultPlan
from repro.core.programs import hier
from repro.core.session import metrics_at

P = 16
SEEDS = np.arange(4)
RUN_SEED = 5
SPECS = {
    "rma_rw": LockSpec(kind="rma_rw", P=P, fanout=(4,), T_DC=4,
                       T_L=(4, 4), T_R=8, writer_fraction=0.25),
    "rma_mcs": LockSpec(kind="rma_mcs", P=P, fanout=(4,), T_L=(4, 4)),
    "d_mcs": LockSpec(kind="d_mcs", P=P),
    "fompi_rw": LockSpec(kind="fompi_rw", P=P, writer_fraction=0.25),
    "fompi_spin": LockSpec(kind="fompi_spin", P=P),
}


@functools.lru_cache(maxsize=None)
def _runs(kind):
    """One Session per kind, its crash-free runs and the full loop's,
    and the `program.pruned_pcs` each table derivation counted."""
    sess = Session(SPECS[kind], target_acq=2)
    built = [r for r in spans.records() if r.name == "session.handlers"]
    pruned_session = built[-1].counters.get("program.pruned_pcs")
    none = FaultPlan.none(P)
    before = spans.counters()["program.pruned_pcs"]
    full_run = engine.run_sim(sess.program, sess.env, sess.layout,
                              seed=RUN_SEED, max_events=sess.max_events,
                              fault=none)
    pruned_fault = spans.counters()["program.pruned_pcs"] - before
    full_batch = engine.run_sim_batch(sess.program, sess.env, sess.layout,
                                      seeds=SEEDS,
                                      max_events=sess.max_events,
                                      fault=none)
    fault_scopes = spans.op_scopes()
    run = sess.run(RUN_SEED)
    batch = sess.run_batch(SEEDS)
    return dict(sess=sess, run=run, batch=batch, full_run=full_run,
                full_batch=full_batch, fault_scopes=fault_scopes,
                crash_free_scopes=spans.op_scopes(),
                pruned_session=pruned_session, pruned_fault=pruned_fault)


@pytest.fixture(scope="module", params=sorted(SPECS))
def runs(request):
    return _runs(request.param)


@pytest.fixture(scope="module")
def rw():
    return _runs("rma_rw")


def assert_bitwise(a, b):
    for name, x, y in zip(a._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert x.tobytes() == y.tobytes(), name


@pytest.mark.parametrize("shape", ["run", "batch"])
def test_crash_free_equals_full_loop(runs, shape):
    m = runs[shape]
    assert_bitwise(m, runs["full_" + shape])
    assert bool(np.all(m.completed)) and int(np.sum(m.violations)) == 0


def test_grid_equals_per_point_run_batch():
    """A grid prunes the pcs no point reaches; a padded T_DC point
    shifts the scratch words of the foMPI kinds."""
    spec = SPECS["fompi_rw"]
    sess = _runs("fompi_rw")["sess"]
    g = sess.grid([1, 2], [None], [spec.T_R], seeds=SEEDS)
    assert_bitwise(metrics_at(g, 0, 0, 0), _runs("fompi_rw")["batch"])
    other = Session(spec.replace(T_DC=2), target_acq=2).run_batch(SEEDS)
    assert_bitwise(metrics_at(g, 1, 0, 0), other)


def _pcs(scopes):
    return {s.split("/pc.", 1)[1].split("/", 1)[0]
            for s in scopes.values() if "/pc." in s}


@pytest.mark.parametrize("program", ["crash_free", "fault"])
def test_dispatched_program_scopes(runs, program):
    sess = runs["sess"]
    meta = sess.program.meta(sess.env)
    scopes = runs[program + "_scopes"]
    pcs = _pcs(scopes)
    dead = {meta.pc_names[pc] for pc in meta.dead_pcs}
    recovery = {meta.pc_names[pc] for pc in meta.recovery_pcs}
    has_fault = any("/fault/" in s for s in scopes.values())
    assert any("/handlers/" in s for s in scopes.values())
    assert not pcs & dead
    if program == "crash_free":
        assert not has_fault and not pcs & recovery
    else:
        assert has_fault and recovery <= pcs


@pytest.mark.parametrize("path,expected", [("session", 5), ("fault", 1)])
def test_pruned_pcs_counter(rw, path, expected):
    """Filed under `session.handlers` in a Session; by the first run of
    `run_sim` with a plan, which prunes only TRAP7."""
    assert rw["pruned_" + path] == expected
    sess = rw["sess"]
    assert len(sess.handlers) == hier.N_PCS
    assert len(sess.table.handlers) == hier.N_PCS - 5 + 1


def test_run_sim_without_plan_traces_once(rw):
    sess = rw["sess"]
    max_events = sess.max_events - 1       # a program no test compiled

    def traces():
        before = spans.counters()["jit.traces"]
        m = engine.run_sim(sess.program, sess.env, sess.layout,
                           seed=RUN_SEED, max_events=max_events)
        return spans.counters()["jit.traces"] - before, m

    first, m1 = traces()
    second, m2 = traces()
    assert first > 0 and second == 0
    assert_bitwise(m2, m1)
    assert_bitwise(m1, rw["run"])


def _start_at(sess, pc):
    st = sess.state0
    return st._replace(pc=st.pc.at[0].set(pc))


def test_trap_ends_the_run_unfinished(rw):
    sess = rw["sess"]
    st = engine._run(sess.table, sess.max_events,
                     _start_at(sess, hier.REC_INHERIT), RUN_SEED)
    assert int(st.events) == np.iinfo(np.int32).max
    assert not bool(st.done[0])
    assert not bool(engine.summarize(st).completed)


def test_sanitizer_names_a_trapped_pc():
    sess = Session(SPECS["d_mcs"], target_acq=2)
    sess.state0 = _start_at(sess, hier.REC_INHERIT)
    with engine.runtime_checks(True):
        with pytest.raises(Exception, match=f"pc {hier.REC_INHERIT} was "
                                            "declared unreachable"):
            jax.block_until_ready(sess.run_state(RUN_SEED))
