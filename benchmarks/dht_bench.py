"""DHT case study -- Fig. 6 of the paper.

P-1 processes hammer one victim volume with F_W inserts / (1-F_W)
reads under three synchronization schemes: foMPI-A (lock-free
CAS/FAO), foMPI-RW (centralized RW lock), RMA-RW (ours). Metric:
total simulated execution time for a fixed op budget.
"""
from __future__ import annotations

import numpy as np

from repro.core import LockSpec, engine, writer_mask
from repro.core.programs.dht import FompiADHT
from benchmarks.locks import make_session

N_TABLE_WORDS = 64


MAX_EVENTS = 1_500_000


def _normalized_us(m, P, target_acq):
    """Total-time estimate: us/op x total ops. Exact when the run
    completed; a steady-state estimator when it hit the event cap
    (centralized locks at P>=256 converge extremely slowly -- the
    paper's 'does not scale' behaviour)."""
    done = int(m.total_acquires)
    if done == 0:                 # saturated: no op finished in budget
        return float("inf")
    return float(m.makespan) / done * (P * target_acq)


def _run_fompi_a(P, fw, target_acq, seed=0):
    # Reuse the lock-free spec's machine/window plumbing; table words
    # live in the extra scratch area (owned round-robin), so rebuild the
    # layout with enough scratch for table + heap pointer.
    spec = LockSpec(kind="fompi_spin", P=P)
    machine = spec.machine()
    layout = spec.layout(machine, extra_words=N_TABLE_WORDS + 1)
    W = layout.W
    table_words = np.arange(W - N_TABLE_WORDS - 1, W - 1, dtype=np.int32)
    heap_word = W - 1
    mask = writer_mask(P, fw)
    prog = FompiADHT(table_words, heap_word, mask)
    env = engine.make_env(machine, layout, is_writer=mask,
                          target_acq=target_acq)
    m = engine.run_sim(prog, env, layout, seed=seed,
                       max_events=MAX_EVENTS)
    return _normalized_us(m, P, target_acq)


def _run_locked(kind, P, fw, target_acq, seed=0):
    sess = make_session(kind, P, bench="sob", target_acq=target_acq,
                        writer_fraction=fw, max_events=MAX_EVENTS)
    m = sess.run(seed)
    assert int(m.violations) == 0
    return _normalized_us(m, P, target_acq)


def bench_dht(ps=(16, 64), fws=(0.0, 0.02, 0.05, 0.20), target_acq=4):
    out = []
    for P in ps:
        for fw in fws:
            rec = {"bench": "dht", "P": P, "F_W": fw,
                   "fompi_a_us": _run_fompi_a(P, fw, target_acq),
                   "fompi_rw_us": _run_locked("fompi_rw", P, fw,
                                              target_acq),
                   "rma_rw_us": _run_locked("rma_rw", P, fw, target_acq)}
            out.append(rec)
    return out
