"""The control of a cell: its plain reference with one guarantee broken,
put in the program's place and checked as a run is.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For a simulator cell the control is the reference with reader-writer
exclusion broken (`exclusive=False`), run on the first call of the
window that `--seed` would drive. Each seed prints the numbers the
check compares, beside their limits. The benchmark's own runs never run
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def sim_control(cell) -> list:
    from bench.reference import lock_sim
    from bench.systems.lock_sim import compare_run

    traffic = harness.traffic_generator(cell.mix, cell.bench_dir)
    seeds = traffic.lane_seeds(next(traffic.calls(cell.mix, cell.seed)))
    want = lock_sim.run(cell.cfg, seeds)
    got = lock_sim.run(cell.cfg, seeds, exclusive=False)
    differing, gap = 0, 0.0
    for g, w in zip(got, want):
        d, t = compare_run(dict(g, t_recover=3.4e38, t_crash=3.4e38), w)
        differing += int(d)
        gap = max(gap, t)
    return [harness.Check("runs_differing", differing, 0),
            harness.Check("time_gap", gap,
                          float(cell.mix["check"]["time_gap"]))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell.from_benchmark(args.workload, bench, seed=seed)
        checks = sim_control(cell)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": all(c.ok for c in checks),
                          "checks": {c.name: {"value": c.value,
                                              "limit": c.limit}
                                     for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
