"""locklint: the static analyzer + small-P model checker.

Three layers: (1) every registered lock kind is clean under the quick
config set and the layout lattice has no findings; (2) the rma_rw P=2
model check actually enumerates a non-trivial interleaving space
(paper §4.4's SPIN claim, but with the states counted); (3) seeded
protocol mutations — a dropped release, a mis-aimed wake word, an
out-of-segment access — are each caught by the pass that owns them.
Plus the REPRO_CHECKS runtime sanitizer (clean and trapping paths) and
the tuner's safety-column regression.
"""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.engine import SimState, cs_exit, finish_instr
from repro.core.session import Session
from repro.core.spec import LockSpec, registered_kinds
from repro.core.window import build_layout
from repro.core.programs.fompi import (FompiSpin, S_CS, S_DONE, S_REL,
                                       S_TRY, _NOOP)
from repro.analysis import locklint
from repro.analysis import ir as ir_mod
from repro.analysis.model import Explorer


# ------------------------------------------------------- clean passes
@pytest.mark.parametrize("kind", sorted(registered_kinds()))
def test_kind_clean_under_quick_configs(kind):
    findings, stats = locklint.check_kind(kind, quick=True)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert all(st.n_states > 0 for st in stats)


def test_rma_rw_enumerates_large_interleaving_space():
    # Acceptance bar: the exhaustive P=2 check of the hierarchical RW
    # lock walks >10k distinct root-to-terminal interleavings with zero
    # safety violations.
    findings, stats = locklint.check_kind("rma_rw", quick=True)
    assert findings == []
    assert any(st.n_interleavings > 10_000 for st in stats)


def test_layout_lattice_clean():
    assert locklint.check_layout_lattice() == []


# ---------------------------------------------------------- mutations
def _check_mutant(program, *, P=2, target_acq=2):
    spec = LockSpec(kind="fompi_spin", P=P)
    s = Session(spec, target_acq=target_acq, cs_kind=0, think=False)
    meta = program.meta(s.env)
    return locklint.check_config(program, s.env, s.layout, meta,
                                 "mutant")[0]


class DroppedExitSpin(FompiSpin):
    """Release clears the word but forgets the cs_exit accounting."""

    def _build(self, env):
        h = list(super()._build(env))
        LW = env.scratch_w[self.lock_slot]

        def s_rel(p, now, key, st: SimState):
            win = st.window.at[LW].set(0)      # no cs_exit(...)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, LW), hot_word=LW,
                                writes=[LW], next_pc=S_DONE,
                                regs_row=st.regs[p], window=win)
        h[S_REL] = s_rel
        return tuple(h)


class StuckReleaseSpin(FompiSpin):
    """Release forgets to clear the lock word: every later acquire
    spins forever — a liveness bug, not a safety one."""

    def _build(self, env):
        h = list(super()._build(env))

        def s_rel(p, now, key, st: SimState):
            st = cs_exit(env, st, p)           # accounting ok, word stuck
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(
                                    p, env.scratch_w[self.lock_slot]),
                                hot_word=-1, writes=[], next_pc=S_DONE,
                                regs_row=st.regs[p])
        h[S_REL] = s_rel
        return tuple(h)


class MisaimedWakeSpin(FompiSpin):
    """The spin watches scratch slot 1, which nothing ever writes."""

    def _build(self, env):
        h = list(super()._build(env))
        LW = env.scratch_w[self.lock_slot]
        WRONG = env.scratch_w[1]

        def s_try(p, now, key, st: SimState):
            cur = st.window[LW]
            got = cur == 0
            win = st.window.at[LW].set(jnp.where(got, 1, cur))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, LW), hot_word=LW,
                                writes=[LW],
                                next_pc=jnp.where(got, S_CS, S_TRY),
                                regs_row=st.regs[p], window=win,
                                block_a=jnp.where(got, _NOOP, WRONG))
        h[S_TRY] = s_try
        return tuple(h)


class OutOfSegmentSpin(FompiSpin):
    """The CS body reads a counter word the program never declared."""

    def _build(self, env):
        h = list(super()._build(env))
        orig = h[S_CS]

        def s_cs(p, now, key, st: SimState):
            _ = st.window[env.arrive_w[0]]     # recorded by the tracer
            return orig(p, now, key, st)
        h[S_CS] = s_cs
        return tuple(h)


def test_dropped_cs_exit_flagged_as_safety_violation():
    findings = _check_mutant(DroppedExitSpin())
    assert any(f.pass_name == "model" and "safety" in f.message
               for f in findings), findings


def test_unreleased_lock_word_flagged_as_stuck():
    findings = _check_mutant(StuckReleaseSpin())
    assert any(f.pass_name == "model"
               and ("stuck" in f.message or "incomplete" in f.message)
               for f in findings), findings


def test_misaimed_wake_word_flagged_by_lost_wakeup_lint():
    findings = _check_mutant(MisaimedWakeSpin())
    assert any(f.pass_name == "wakeup" and "lost wakeup" in f.message
               for f in findings), findings


def test_out_of_segment_access_flagged_by_bounds_lint():
    findings = _check_mutant(OutOfSegmentSpin())
    assert any(f.pass_name == "bounds" for f in findings), findings


# ------------------------------------------------------ IR extraction
def test_ir_recovers_spin_lock_shape():
    spec = LockSpec(kind="fompi_spin", P=2)
    s = Session(spec, target_acq=2, cs_kind=0, think=False)
    meta = s.program.meta(s.env)
    res = Explorer(s.program, s.env, s.layout).explore()
    assert res.ok, res.findings
    pir = ir_mod.extract(s.program, s.env, s.layout, res, meta=meta)
    LW = int(np.asarray(s.layout.scratch_w)[0])
    assert pir.instrs[S_TRY].atomic_words == {LW}
    assert LW in pir.instrs[S_REL].declared_writes
    assert pir.instrs[S_CS].enters_cs and pir.instrs[S_REL].exits_cs
    assert pir.cfg_successors(S_TRY) == {S_TRY, S_CS}


# ------------------------------------------------- runtime sanitizer
def test_runtime_checks_clean_protocol_run():
    spec = LockSpec(kind="rma_rw", P=4, fanout=(2,), T_DC=2, T_L=(1, 2),
                    T_R=2, writer_fraction=0.5)
    s = Session(spec, target_acq=2, cs_kind=0, think=False)
    with engine.runtime_checks(True):
        m = s.run(seed=0)
        mb = s.run_batch(seeds=np.arange(2))
    assert bool(m.completed) and int(m.violations) == 0
    assert int(np.asarray(mb.violations).sum()) == 0


def test_runtime_checks_trap_dead_counter_write():
    spec = LockSpec(kind="fompi_spin", P=2)
    machine = spec.machine()
    lay = build_layout(machine, T_DC=1, pad_counters_to=machine.P + 2)
    env = engine.make_env(machine, lay, is_writer=np.ones(2, bool),
                          target_acq=1)
    dead = int(np.asarray(lay.arrive_w)[-1])   # padded slot

    def bad(p, now, key, st):
        win = st.window.at[dead].add(1)
        return finish_instr(env, st, p, now, key, dur=1.0, hot_word=-1,
                            writes=[dead], next_pc=1,
                            regs_row=st.regs[p], window=win)

    def halt(p, now, key, st):
        st = st._replace(done=st.done.at[p].set(True))
        return finish_instr(env, st, p, now, key, dur=0.0, hot_word=-1,
                            writes=[], next_pc=1, regs_row=st.regs[p])

    table = engine.StepTable((bad, halt), None, True, env)
    st0 = engine.init_state(env, lay, np.zeros(2, np.int32), 1)
    with engine.runtime_checks(True):
        with pytest.raises(Exception, match="dead counter"):
            engine._run(table, 1000, st0, 0)
    # The same run is silent without the sanitizer.
    assert int(engine._run(table, 1000, st0, 0).events) > 0


# --------------------------------------------- tuner safety columns
def test_tuner_rejects_unsafe_top_throughput_point(monkeypatch):
    from repro.core import tuner as tuner_mod

    t_dc, t_r = [1, 2], [16]

    class FakeSession:
        devices = None

        def __init__(self, *a, **kw):
            pass

        def grid(self, t_dc, t_l, t_r, *, seeds):
            shape = (len(t_dc), len(t_l), len(t_r), len(seeds))
            tput = np.ones(shape, np.float32)
            viol = np.zeros(shape, np.int32)
            comp = np.ones(shape, bool)
            # T_DC=1: unsafe but 100x the throughput. T_DC=2: safe.
            tput[0] = 100.0
            viol[0] = 1
            return types.SimpleNamespace(
                violations=viol, completed=comp, throughput=tput,
                mean_latency=np.full(shape, 5.0, np.float32))

    monkeypatch.setattr(tuner_mod, "Session", FakeSession)
    res = tuner_mod.tune(LockSpec(kind="fompi_spin", P=4), t_dc=t_dc,
                         t_r=t_r, seeds=(0, 1), refine_rounds=0)
    assert res.spec.T_DC == 2          # the unsafe winner was rejected
    assert res.violations == 0 and res.completed is True
    assert res.rounds[0]["n_disqualified"] == 1   # the T_DC=1 point
    back = tuner_mod.TuneResult.from_json(res.to_json())
    assert back == res
