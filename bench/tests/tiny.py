"""Cells cut to a size the CPU runs in seconds, from a configuration
and a mix of `bench/`, and a fresh window and check on a cell that is
set up already."""
from __future__ import annotations

from bench import harness
from bench.trace import Tracer


def tiny_cell(config: str, traffic: str,
              seed: int = 2**31 + 11) -> harness.Cell:
    cfg = harness.load_json(harness.BENCH_DIR, "configs", config)
    mix = harness.load_json(harness.BENCH_DIR, "traffic", traffic)
    cell = harness.Cell(name=f"{config}.{traffic}", config_name=config,
                        traffic_name=traffic, chips=int(cfg["chips"]),
                        cfg=cfg, mix=mix, seed=seed, seconds=0.5)
    cell.cfg["lock"].update(P=16, fanout=[1])
    return cell


def driver_for(cell: harness.Cell):
    return harness.system_driver(cell.cfg["system"]).make(cell, Tracer())


def sim_window(drv, seconds: float = 0.5):
    """(correct, checks) of one more window of a set-up simulator cell."""
    drv.calls = []
    drv.window(seconds)
    checks, _ = drv.check()
    return all(c.ok for c in checks), {c.name: c.value for c in checks}
