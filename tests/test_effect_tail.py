"""One instruction tail per trip.

Handlers return their effect (`engine.Effect`) and the step loop applies
it once, after the switch, under the named scope `tail`
(`engine.apply_effect`). The results must not move: every `Metrics`
leaf of the six programs, crash-free and with per-lane fault plans, is
bitwise equal to the golden file `data/effect_tail_golden.json`,
recorded with the tail still inside every handler.

Re-record (only when results are meant to change) with
`PYTHONPATH=src python tests/test_effect_tail.py --record`.
"""
import functools
import json
import os
import sys

import jax
import jax.extend.core
import numpy as np
import pytest

from repro.core import LockSpec, Session, engine, writer_mask
from repro.core.engine import FaultPlan
from repro.core.programs import hier
from repro.core.programs.dht import FompiADHT

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "effect_tail_golden.json")
P = 16
SEEDS = np.array([0, 7, 2**31 - 5, 123456])
SPECS = {
    "rma_rw": LockSpec(kind="rma_rw", P=P, fanout=(2,), T_DC=4,
                       T_L=(4, 4), T_R=8, writer_fraction=0.25),
    "rma_mcs": LockSpec(kind="rma_mcs", P=P, fanout=(2,), T_L=(4, 4)),
    "d_mcs": LockSpec(kind="d_mcs", P=P),
    "fompi_spin": LockSpec(kind="fompi_spin", P=P),
    "fompi_rw": LockSpec(kind="fompi_rw", P=P, writer_fraction=0.25),
}
DHT_TABLE_WORDS = 8


@functools.lru_cache(maxsize=None)
def session(kind) -> Session:
    return Session(SPECS[kind], target_acq=2)


def plan_for(sess) -> FaultPlan:
    """Lanes 0-1 lose the first writer, lanes 2-3 the first reader (the
    second writer where every process writes), early enough to fire."""
    writers = np.flatnonzero(sess.is_writer)
    readers = np.flatnonzero(~sess.is_writer)
    other = readers[0] if readers.size else writers[1]
    return FaultPlan.lanes(P, [writers[0], writers[0], other, other],
                           [0.5, 3.0, 0.1, 0.3])


def dht_run() -> engine.Metrics:
    """The lock-free foMPI-A table program, crash-free, over SEEDS."""
    spec = LockSpec(kind="fompi_spin", P=P)
    machine = spec.machine()
    layout = spec.layout(machine, extra_words=DHT_TABLE_WORDS + 1)
    W = layout.W
    mask = writer_mask(P, 0.2)
    prog = FompiADHT(np.arange(W - DHT_TABLE_WORDS - 1, W - 1,
                               dtype=np.int32), W - 1, mask)
    env = engine.make_env(machine, layout, is_writer=mask, target_acq=2)
    return engine.run_sim_batch(prog, env, layout, seeds=SEEDS)


def run_case(case: str) -> engine.Metrics:
    if case == "dht":
        return dht_run()
    kind, loop = case.split(".")
    sess = session(kind)
    if loop == "crash_free":
        return sess.run_batch(SEEDS)
    return sess.run_batch(SEEDS, faults=plan_for(sess))


CASES = [f"{k}.{loop}" for k in SPECS for loop in ("crash_free", "faults")]
CASES.append("dht")


def encode(m: engine.Metrics) -> dict:
    return {name: {"dtype": str(np.asarray(x).dtype),
                   "shape": list(np.shape(x)),
                   "hex": np.ascontiguousarray(x).tobytes().hex()}
            for name, x in zip(m._fields, m)}


@functools.lru_cache(maxsize=None)
def golden() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("case", CASES)
def test_metrics_are_bitwise_the_golden_ones(case):
    got = encode(run_case(case))
    want = golden()[case]
    assert set(got) == set(want)
    for name in want:
        assert got[name] == want[name], f"{case}: {name}"


def test_the_golden_runs_complete_safely():
    for case in CASES:
        leaves = golden()[case]
        done = np.frombuffer(bytes.fromhex(leaves["completed"]["hex"]), bool)
        viol = np.frombuffer(bytes.fromhex(leaves["violations"]["hex"]),
                             np.int32)
        assert done.all() and not viol.any(), case


def test_a_pruned_pc_in_a_batch_reaches_the_trap_and_ends_the_run():
    sess = session("rma_rw")
    st = sess.state0
    sess_trapped = st._replace(pc=st.pc.at[0].set(hier.REC_TAILFIX))
    m = engine._run_batch(sess.table, sess.max_events, sess_trapped, SEEDS)
    assert (np.asarray(m.events) == np.iinfo(np.int32).max).all()
    assert not np.asarray(m.completed).any()


# ------------------------------------------------------- structure
def _scope_eqns(jaxpr, scope: str) -> list:
    """Every equation, nested ones included, whose name stack holds
    `scope` as a whole component."""
    out = []
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack).split("/")
        if scope in stack:
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_scope_eqns(sub, scope))
    return out


def _loop_jaxpr(table, st):
    return jax.make_jaxpr(
        lambda s: engine.step_loop(table, 100, s, 0))(st).jaxpr


def _dht_table():
    spec = LockSpec(kind="fompi_spin", P=P)
    machine = spec.machine()
    layout = spec.layout(machine, extra_words=DHT_TABLE_WORDS + 1)
    W = layout.W
    mask = writer_mask(P, 0.2)
    prog = FompiADHT(np.arange(W - DHT_TABLE_WORDS - 1, W - 1,
                               dtype=np.int32), W - 1, mask)
    env = engine.make_env(machine, layout, is_writer=mask, target_acq=2)
    st = engine.init_state(env, layout, prog.init_pc(env), prog.n_regs,
                           prog.init_regs(env))
    return engine.run_table(prog, env, faults=False), st


def test_the_loop_holds_one_tail_whatever_the_table_size():
    dht_table, dht_st = _dht_table()
    sess = session("rma_rw")
    assert len(dht_table.handlers) == 4
    assert len(sess.table.handlers) == 30
    small = _scope_eqns(_loop_jaxpr(dht_table, dht_st), "tail")
    large = _scope_eqns(_loop_jaxpr(sess.table, sess.state0), "tail")
    assert small and len(small) == len(large)


SCATTERED_BY_THE_TAIL = ("t_ready", "blocked_a", "blocked_b", "backoff",
                         "pc")


def _scattered(jaxpr, alias: dict) -> set:
    """The state fields that scatters in `jaxpr` update. `alias` maps a
    variable to the fields it is a version of: a scatter's result, or a
    select between versions, is a version of its operand's fields."""
    alias = dict(alias)
    hit = set()
    for eqn in jaxpr.eqns:
        ins = [frozenset() if isinstance(v, jax.extend.core.Literal)
               else alias.get(v, frozenset()) for v in eqn.invars]
        name = eqn.primitive.name
        out = frozenset()
        if name.startswith("scatter"):
            hit |= ins[0]
            out = ins[0]
        elif name == "select_n":
            out = frozenset().union(*ins[1:])
        elif name == "cond":
            for br in eqn.params["branches"]:
                hit |= _scattered(br.jaxpr, dict(zip(br.jaxpr.invars,
                                                     ins[1:])))
        elif "jaxpr" in eqn.params:
            sub = eqn.params["jaxpr"]
            sub = getattr(sub, "jaxpr", sub)
            hit |= _scattered(sub, dict(zip(sub.invars, ins)))
        for v in eqn.outvars:
            alias[v] = out
    return hit


def _switches(jaxpr, n_branches: int) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and \
                len(eqn.params["branches"]) == n_branches:
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_switches(sub, n_branches))
    return out


@pytest.mark.parametrize("faults", [False, True])
def test_no_handler_scatters_into_what_the_tail_writes(faults):
    sess = session("rma_rw")
    table = engine.run_table(sess.program, sess.env, faults=faults)
    [switch] = _switches(_loop_jaxpr(table, sess.state0),
                         len(table.handlers))
    fields = engine.SimState._fields
    written = set()
    for br in switch.params["branches"]:
        st_in = br.jaxpr.invars[-len(fields):]
        alias = {v: frozenset({f}) for v, f in zip(st_in, fields)}
        got = _scattered(br.jaxpr, alias)
        assert not got & set(SCATTERED_BY_THE_TAIL), got
        written |= got
    assert "window" in written and "acq_count" in written


if __name__ == "__main__" and sys.argv[1:] == ["--record"]:
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        json.dump({case: encode(run_case(case)) for case in CASES}, f,
                  indent=1, sort_keys=True)
        f.write("\n")
