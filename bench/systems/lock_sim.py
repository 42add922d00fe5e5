"""Cells of the lock simulator: a closed loop of `Session` calls.

Set-up builds the configuration's `LockSpec` and `Session` and makes one
warm-up call, which compiles (or loads from the cache) the one program
the window uses. The window then runs whole calls back to back until
`--seconds` have passed, each made by the mix's generator
(`bench/generators/<name>.py`), and closes when the last call's results
are ready. `sim_runs_per_s` is every run completed over that whole span.

The check compares runs of the window with the plain reference
(`bench/reference/lock_sim.py`), and holds every run of the window to
the invariants the configuration guarantees.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import harness
from bench.harness import Check
from bench.reference import lock_sim as reference

# Metrics fields compared exactly; the simulated times are compared by
# their relative gap.
EXACT = ("completed", "violations", "total_acquires", "events",
         "per_proc_acq", "n_crashed", "reclaims", "recovery_retries")
TIMES = ("makespan", "mean_latency", "throughput", "locality")
F32_INF = float(np.float32(3.4e38))


def lock_spec(cfg: dict):
    from repro.core import LockSpec
    from repro.core.cost import CostModel

    lock, cost = cfg["lock"], cfg["cost"]
    return LockSpec(
        kind=lock["kind"], P=int(lock["P"]), fanout=tuple(lock["fanout"]),
        T_DC=int(lock["T_DC"]), T_L=tuple(lock["T_L"]), T_R=int(lock["T_R"]),
        writer_fraction=float(lock["writer_fraction"]),
        role_seed=int(lock["role_seed"]),
        cost=CostModel(**{**cost, "lat": tuple(cost["lat"])}))


class SimCell:
    """One run of a simulator cell: set-up, window, check."""

    def __init__(self, cell, tracer):
        self.cell, self.tracer = cell, tracer
        self.cfg, self.mix = cell.cfg, cell.mix
        self.traffic = harness.traffic_generator(self.mix, cell.bench_dir)
        self.calls = []           # (call, Metrics on the host, t0, t1)

    # -------------------------------------------------------- set-up
    def setup(self):
        from repro.core import Session

        wl = self.cfg["workload"]
        self.session = Session(
            lock_spec(self.cfg), target_acq=int(wl["target_acq"]),
            cs_kind=int(wl["cs_kind"]), think=bool(wl["think"]),
            max_events=int(wl["max_events"]))
        self.inputs = self.traffic.calls(self.mix, self.cell.seed)
        warm = self.traffic.calls(self.mix, self.cell.seed + 1)
        jax.block_until_ready(self.call(next(warm)))

    def call(self, inputs):
        return self.traffic.submit(self.session, inputs)

    # -------------------------------------------------------- window
    def window(self, seconds: float):
        """Whole calls until `seconds` have passed; returns the span.

        A traced run records call `trace.call` from its start until
        `trace.slice_s` seconds after its dispatch: a trip runs hundreds
        of small ops, so a whole call would be millions of events."""
        plan = self.mix["trace"]
        t_start = time.perf_counter()
        while True:
            inputs = next(self.inputs)
            sliced = self.cell.trace and len(self.calls) == plan["call"]
            t0 = time.perf_counter()
            with self.tracer.window(sliced):
                with self.tracer.span("sim.dispatch"):
                    m = self.call(inputs)
                if sliced:
                    with self.tracer.span("sim.wait"):
                        time.sleep(float(plan["slice_s"]))
            m = jax.block_until_ready(m)
            t1 = time.perf_counter()
            self.calls.append((inputs, m, t0, t1))
            if t1 - t_start >= seconds:
                break
        self.span_s = self.calls[-1][3] - t_start
        self.calls = [(c, jax.tree.map(np.asarray, m), t0, t1)
                      for c, m, t0, t1 in self.calls]
        return self.span_s

    def end_to_end(self) -> dict:
        return {"sim_runs_per_s": self.attempted() / self.span_s}

    def attempted(self) -> int:
        return sum(np.asarray(m.events).size for _, m, _, _ in self.calls)

    def lanes(self) -> list:
        """Per call, the `events` of each lane (run) of its batch."""
        return [np.asarray(m.events).reshape(-1) for _, m, _, _ in self.calls]

    def free(self):
        del self.session

    # --------------------------------------------------------- check
    def check(self) -> tuple:
        """(checks, failed runs) against the reference and invariants."""
        wl, lock = self.cfg["workload"], self.cfg["lock"]
        want_acq = int(lock["P"]) * int(wl["target_acq"])
        gate_failed = 0
        for _, m, _, _ in self.calls:
            bad = ((np.asarray(m.violations) != 0)
                   | ~np.asarray(m.completed)
                   | (np.asarray(m.total_acquires) != want_acq))
            gate_failed += int(bad.sum())
        sample = self.sample()
        seeds = [self.traffic.lane_seeds(self.calls[c][0])[s]
                 for c, s in sample]
        differing, gap = 0, 0.0
        for (c, s), ref in zip(sample, reference.run(self.cfg, seeds)):
            d, g = compare_run(run_fields(self.calls[c][1], s), ref)
            differing += int(d)
            gap = max(gap, g)
        checks = [
            Check("runs_differing", differing, 0),
            Check("time_gap", gap, float(self.mix["check"]["time_gap"])),
            Check("gate_failures", gate_failed, 0),
        ]
        print(f"checked {len(sample)} of {self.attempted()} runs against "
              f"the reference", flush=True)
        return checks, gate_failed + differing

    def sample(self) -> list:
        """(call, lane) of the runs compared with the reference: one run
        at each lane position of the batch, from a call drawn from the
        seed, and at the longest run's lane the longest run. A fault in
        any lanes of the batch's program is then in the sample."""
        events = np.stack(self.lanes())            # [calls, lanes]
        r = harness.rng(self.cell.seed, 4)
        picks = [(int(r.integers(0, events.shape[0])), s)
                 for s in range(events.shape[1])]
        c, s = np.unravel_index(int(np.argmax(events)), events.shape)
        picks[int(s)] = (int(c), int(s))
        return picks


def run_fields(m, s: int) -> dict:
    """Lane `s`'s fields from a call's Metrics (host arrays)."""
    return {name: np.asarray(getattr(m, name))[s] for name in m._fields}


def compare_run(got: dict, ref: dict) -> tuple:
    """(any exact field differs, widest relative gap of the times)."""
    differ = any(not np.array_equal(np.asarray(got[f]), np.asarray(ref[f]))
                 for f in EXACT)
    differ |= float(got["t_recover"]) != F32_INF
    differ |= float(got["t_crash"]) != F32_INF
    gap = 0.0
    for f in TIMES:
        g, r = float(got[f]), float(ref[f])
        gap = max(gap, abs(g - r) / max(abs(r), 1e-30))
    return differ, gap


def make(cell, tracer):
    return SimCell(cell, tracer)
