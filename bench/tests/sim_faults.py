"""Faults of the simulator's timed path, planted in what a call returns."""
from __future__ import annotations

import numpy as np


def seed_axis(name: str, leaf) -> int:
    return leaf.ndim - (2 if name == "per_proc_acq" else 1)


def state_unchanged(drv, m):
    """Every run returns its initial state: a step that changed nothing."""
    from repro.core import engine

    m0 = engine.summarize(drv.session.state0)
    return type(m)(*(np.broadcast_to(np.asarray(x0), np.shape(x))
                     for x0, x in zip(m0, m)))


def half_batch(drv, m):
    """The second half of the seeds carries the first half's runs."""
    out = []
    for name, x in zip(m._fields, m):
        x = np.asarray(x)
        S = x.shape[seed_axis(name, x)]
        idx = np.arange(S) % max(S // 2, 1)
        out.append(np.take(x, idx, axis=seed_axis(name, x)))
    return type(m)(*out)


def answer_altered(drv, m):
    """One run's makespan is off by 1%."""
    mk = np.array(m.makespan)
    mk.reshape(-1)[-1] *= 1.01
    return m._replace(makespan=mk)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}


def plant(drv, fault):
    """Route the driver's calls through `fault`."""
    call = drv.call

    def broken(inputs):
        return fault(drv, call(inputs))

    drv.call = broken
    return call
