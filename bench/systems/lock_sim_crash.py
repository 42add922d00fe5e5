"""Cells of the lock simulator under process loss: a closed loop of
`Session.run_batch` calls, each lane of which loses one process.

The loop, its window and `sim_runs_per_s` are those of `lock_sim`. Each
call carries one crash plan per lane, made by the mix's generator, so
every call runs the full step loop: the fault test, the crash branch
and the recovery handlers.

The check compares runs of the window with the plain reference of the
crash semantics (`bench/reference/lock_sim_crash.py`), the crash and
recovery times among the compared fields and whether the plan fired
(`n_crashed`) among the exact ones, and holds every run of the window
to the guarantees the configuration states.
"""
from __future__ import annotations

import numpy as np

from bench.harness import Check
from bench.reference import lock_sim_crash as reference
from bench.systems import lock_sim

TIMES = lock_sim.TIMES + ("t_crash", "t_recover")
F32_INF = np.float32(lock_sim.F32_INF)


class CrashCell(lock_sim.SimCell):
    """One run of a simulator cell with one crash per lane."""

    def setup(self):
        super().setup()
        lease = float(self.cfg["failure"]["lease_us"])
        if self.session.env.lease != lease:
            raise ValueError(f"the configuration states a lease of {lease} "
                             f"us, the program runs {self.session.env.lease}")
        self.is_writer = np.asarray(self.session.is_writer)

    def check(self) -> tuple:
        """(checks, failed runs) against the reference and guarantees."""
        target = int(self.cfg["workload"]["target_acq"])
        lease = np.float32(self.cfg["failure"]["lease_us"])
        gate_failed = 0
        for call, m, _, _ in self.calls:
            bad = gate(m, self.traffic.victims(call, self.is_writer),
                       call.crash_t, target, lease)
            gate_failed += int(bad.sum())
        sample = self.sample()
        runs = []
        for c, s in sample:
            call = self.calls[c][0]
            runs.append((self.traffic.lane_seeds(call)[s],
                         self.traffic.victims(call, self.is_writer)[s],
                         call.crash_t[s]))
        seeds, victims, times = zip(*runs)
        differing, gap = 0, 0.0
        for (c, s), ref in zip(sample, reference.run(self.cfg, seeds,
                                                     victims, times)):
            d, g = compare_run(lock_sim.run_fields(self.calls[c][1], s), ref)
            differing += int(d)
            gap = max(gap, g)
        checks = [
            Check("runs_differing", differing, 0),
            Check("time_gap", gap, float(self.mix["check"]["time_gap"])),
            Check("gate_failures", gate_failed, 0),
        ]
        print(f"checked {len(sample)} of {self.attempted()} runs against "
              f"the crash reference", flush=True)
        return checks, gate_failed + differing


def gate(m, victims, planned, target: int, lease) -> np.ndarray:
    """[S] bool: the runs of one call that break a guarantee. Each must
    have no violation and at most one crash, no earlier than planned; a
    run without one (its victim finished before its crash time) must
    have no crash time and no reclaim. Every process still running at
    the end, the victim too where it never crashed, must be at its
    target, and a first reclaim, where there is one, no earlier than
    the crash plus the lease. Whether each victim was still running at
    its crash time is decided by the reference, on the sampled runs."""
    acq = np.asarray(m.per_proc_acq)
    n_crashed = np.asarray(m.n_crashed)
    crashed = n_crashed == 1
    running = np.ones(acq.shape, bool)
    running[np.arange(acq.shape[0]), victims] = ~crashed
    t_crash = np.asarray(m.t_crash, np.float32)
    t_recover = np.asarray(m.t_recover, np.float32)
    reclaimed = np.asarray(m.reclaims) > 0
    return ((np.asarray(m.violations) != 0)
            | ~np.asarray(m.completed)
            | ((n_crashed != 0) & ~crashed)
            | (crashed != (t_crash < F32_INF))
            | (crashed & (t_crash < np.asarray(planned, np.float32)))
            | ((acq != target) & running).any(axis=1)
            | (reclaimed != (t_recover < F32_INF))
            | (reclaimed & (t_recover < t_crash + lease)))


def compare_run(got: dict, ref: dict) -> tuple:
    """(any exact field differs, widest relative gap of the times)."""
    differ = any(not np.array_equal(np.asarray(got[f]), np.asarray(ref[f]))
                 for f in lock_sim.EXACT)
    gap = 0.0
    for f in TIMES:
        g, r = float(got[f]), float(ref[f])
        gap = max(gap, abs(g - r) / max(abs(r), 1e-30))
    return differ, gap


def make(cell, tracer):
    return CrashCell(cell, tracer)
