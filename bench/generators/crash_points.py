"""crash_points: calls of `Session.run_batch`, each over `seeds_per_call`
fresh schedule seeds (drawn from `--seed` as `fresh_seeds` draws them)
with one crash plan per lane.

Lane s of every call plans the loss of one process, at the same point
in each call, and never revives it. The first `writer_lanes` lanes
plan to lose a writer, the others a reader: the `pick[s]`-th share of
the processes of its role, at `crash_us[s]` simulated microseconds
(the mix's `points`). A plan fires only if its victim is still running
at that time: a victim that has finished its acquires before it never
crashes, and whether it has depends on the schedule seed.

Besides the three functions every generator of the lock simulator
gives (`calls`, `submit`, `lane_seeds`), `victims(call, is_writer)`
names the process each lane's plan crashes, once the roles are known.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from bench import harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Call:
    """One call: a seed, a role, a pick and a crash time per lane."""

    seeds: np.ndarray      # int32 [S] schedule seeds
    writer: np.ndarray     # bool [S] the lane's victim is a writer
    pick: np.ndarray       # float64 [S] in [0, 1): which one of its role
    crash_t: np.ndarray    # float32 [S] simulated us


def calls(mix: dict, seed: int):
    """Yields, call after call, fresh seeds and the mix's crash points."""
    seeds = harness.traffic_generator({"generator": "fresh_seeds"},
                                      BENCH_DIR).calls(mix, seed)
    per_call = int(mix["seeds_per_call"])
    writer = np.arange(per_call) < int(mix["writer_lanes"])
    pick = np.asarray(mix["points"]["pick"], np.float64)
    t = np.asarray(mix["points"]["crash_us"], np.float32)
    if pick.shape != (per_call,) or t.shape != (per_call,):
        raise ValueError(f"the mix needs {per_call} crash points")
    while True:
        yield Call(next(seeds), writer, pick, t)


def victims(call: Call, is_writer) -> np.ndarray:
    """The process each lane's plan crashes: the pick-th of its role."""
    is_writer = np.asarray(is_writer, bool)
    out = []
    for writer, pick in zip(call.writer, call.pick):
        ranks = np.flatnonzero(is_writer == writer)
        out.append(ranks[int(pick * ranks.size)])
    return np.asarray(out, np.int64)


def submit(session, call: Call):
    from repro.core.engine import FaultPlan

    plan = FaultPlan.lanes(len(session.is_writer),
                           victims(call, session.is_writer), call.crash_t)
    return session.run_batch(call.seeds, faults=plan)


def lane_seeds(call: Call) -> list:
    return [int(s) for s in call.seeds]
