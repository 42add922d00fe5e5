"""Crash-fault injection benchmark: lease-based lock recovery.

For each recoverable lock kind, crash one process at several simulated
times (before, during, and after its first critical section) and let the
survivors detect the expired lease and repair the abandoned queue/lock
state. Reported per kind:

  recovery_us_p50/p90/p99  time from the crash to the first successful
                           reclaim (lease expiry + detection + repair),
                           over the runs where a survivor had to recover
  n_recovered              runs in which at least one reclaim happened
                           (a crash that never blocks anyone needs none)
  total_reclaims           abandoned words reclaimed across all runs
  recovery_retries         recovery steps that had to re-block (lease
                           raced with an in-flight handoff)
  violations               mutual-exclusion violations -- must be 0
  all_completed            every survivor reached its acquire target

Victims are chosen to stress the hardest paths: the writer for RW kinds
(readers must drain and un-bar; a successor must inherit or pass over
the dead writer) and process 0 otherwise. Revive is exercised only for
the fompi kinds -- the hierarchical queue locks do not support revive
(a revived process would re-initialise its queue node and orphan linked
successors); see tests/test_faults.py for the revive path.

Results are simulated microseconds under the calibrated Aries-class
cost model; the lease is the `make_env` default (2.0 us). Each kind's
runs (crash times x seeds) are one `Session.run_batch` call with one
`FaultPlan` per lane.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.core import LockSpec, Session, engine
from repro.core.engine import FaultPlan
from repro.launch.compile_cache import use_compile_cache

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results", "bench")

CRASH_TIMES_US = (0.5, 1.5, 3.0)

#    kind          P  victim  spec kwargs
FAULT_POINTS = (
    ("d_mcs",      2, 0, {}),
    ("rma_mcs",    2, 0, {"fanout": (2,), "T_L": (2, 1)}),
    ("rma_rw",     2, 0, {"fanout": (2,), "T_DC": 1, "T_L": (1, 1),
                          "T_R": 1, "writer_fraction": 0.5}),
    ("fompi_spin", 4, 0, {}),
    ("fompi_rw",   4, 0, {"writer_fraction": 0.5}),
)


def _session(kind, P, spec_kwargs, *, target_acq=6):
    spec = LockSpec(kind=kind, P=P, **spec_kwargs)
    return Session(spec, target_acq=target_acq, cs_kind=0, think=False)


def bench_faults(*, quick: bool = False, target_acq: int = 6):
    """Run the crash matrix; returns the BENCH_faults payload dict."""
    crash_times = CRASH_TIMES_US[:2] if quick else CRASH_TIMES_US
    rows = []
    for kind, P, victim, spec_kwargs in FAULT_POINTS:
        sess = _session(kind, P, spec_kwargs, target_acq=target_acq)
        seeds = np.tile(np.arange(1 if quick else P), len(crash_times))
        times = np.repeat(crash_times, len(seeds) // len(crash_times))
        m = sess.run_batch(seeds, faults=FaultPlan.lanes(
            P, np.full(len(seeds), victim), times))
        n_runs = len(seeds)
        violations = int(np.sum(m.violations))
        reclaims = int(np.sum(m.reclaims))
        retries = int(np.sum(m.recovery_retries))
        all_completed = bool(np.all(m.completed))
        t_rec, t_crash = np.asarray(m.t_recover), np.asarray(m.t_crash)
        recovered = t_rec < float(engine.INF)
        n_recovered = int(recovered.sum())
        recovery_us = [float(r) - float(c) for r, c in
                       zip(t_rec[recovered], t_crash[recovered])]
        pcts = (np.percentile(recovery_us, (50, 90, 99))
                if recovery_us else np.zeros(3))
        rows.append({
            "kind": kind, "P": P,
            "n_runs": n_runs,
            "n_recovered": n_recovered,
            "recovery_us_p50": float(pcts[0]),
            "recovery_us_p90": float(pcts[1]),
            "recovery_us_p99": float(pcts[2]),
            "total_reclaims": reclaims,
            "recovery_retries": retries,
            "violations": violations,
            "all_completed": all_completed,
        })
    return {"crash_times_us": list(crash_times), "rows": rows}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="2 crash times x 1 seed; checks only, no JSON")
    ap.add_argument("--out", default=os.path.join(RESULTS,
                                                  "BENCH_faults.json"))
    args = ap.parse_args(argv)
    use_compile_cache()

    payload = bench_faults(quick=args.quick)
    for row in payload["rows"]:
        print(f"{row['kind']:<12} P={row['P']} runs={row['n_runs']:>2} "
              f"recovered={row['n_recovered']:>2} "
              f"reclaims={row['total_reclaims']:>2} "
              f"p50={row['recovery_us_p50']:.3f}us "
              f"p99={row['recovery_us_p99']:.3f}us "
              f"violations={row['violations']} "
              f"completed={row['all_completed']}")
        assert row["violations"] == 0, f"ME violated: {row}"
        assert row["all_completed"], f"survivors stalled: {row}"
    if not args.quick:
        os.makedirs(RESULTS, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
