"""fresh_seeds: calls of `Session.run_batch`, each over `seeds_per_call`
schedule seeds that no earlier call of the run has used.

A generator of the lock simulator gives its driver three functions:
`calls(mix, seed)` yields the run's calls one after the other, without
end; `submit(session, call)` hands one call to the program and returns
its Metrics without waiting for them; `lane_seeds(call)` is the schedule
seed of each run of the call, in the order of its Metrics.
"""
from __future__ import annotations

import numpy as np

from bench.harness import rng

SEED_MAX = 2**31 - 1


def calls(mix: dict, seed: int):
    """Yields, call after call, `seeds_per_call` distinct seeds."""
    per_call = int(mix["seeds_per_call"])
    r = rng(seed, 1)
    used = set()
    while True:
        out = []
        while len(out) < per_call:
            s = int(r.integers(0, SEED_MAX))
            if s not in used:
                used.add(s)
                out.append(s)
        yield np.asarray(out, np.int32)


def submit(session, call):
    return session.run_batch(call)


def lane_seeds(call) -> list:
    return [int(s) for s in call]
