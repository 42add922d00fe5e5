"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json`. Set-up builds
the system of the cell's configuration, makes the inputs from `--seed`
and warms up every program the window runs. The window then runs the
cell's traffic mix for `--seconds`. After it the run is checked against
the plain reference, and the last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics`, `device` and, last,
`checks` (each number compared, with its limit). With `--trace 1` the
window is profiled and the metrics are the cell's per-layer metrics,
with a `breakdown` of device ops and idle gaps.

It needs the chips the cell asks for: on any other platform, or with
fewer devices, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_info(devices, chips: int) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices[:chips]),
            "memory_peak_bytes": peak}


def run_cell(cell: harness.Cell, bench: dict, t_process: float) -> dict:
    """Set up, run and check one cell; returns the parts of its line.
    `t_process` is the process's start on `time.perf_counter`."""
    import jax

    from bench.timing import CompileCounter
    from bench.trace import Tracer

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise NoAccelerator(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
    counter = CompileCounter()
    tracer = Tracer()
    driver = harness.system_driver(cell.cfg["system"]).make(cell, tracer)
    driver.setup()
    setup_s = time.perf_counter() - t_process
    compiles_before = counter.compiles, counter.compile_s
    # As `timeit` does, the window runs with Python's cyclic collector
    # off, so that the client's own collections stay out of the timing.
    gc.collect()
    gc.disable()
    try:
        driver.window(cell.seconds)
    finally:
        gc.enable()
    compiles = (counter.compiles - compiles_before[0],
                counter.compile_s - compiles_before[1])
    device = device_info(devices, cell.chips)
    driver.free()
    checks, failed = driver.check()
    e2e = dict(driver.end_to_end(), setup_s=setup_s)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    names = [m["name"] for m in harness.metrics_of(bench, cell.name,
                                                   "end_to_end")]
    out = {"correct": all(c.ok for c in checks),
           "attempted": driver.attempted(), "failed": failed,
           "checks": checks, "device": device,
           "compiles_in_window": compiles[0],
           "compile_s_in_window": compiles[1],
           "setup_compile_s": compiles_before[1],
           "cache_hits": counter.cache_hits,
           "end_to_end": {n: {"value": e2e[n], "unit": units[n]}
                          for n in names}}
    if cell.trace:
        summary = tracer.read()
        if summary is not None:
            device["busy_s"] = summary.busy_s()
            device["window_s"] = summary.window_s
            out["breakdown"] = summary.breakdown()
        ctx = {"cell": cell, "driver": driver, "trace": summary,
               "device_kind": device["kind"]}
        out["per_layer"] = harness.read_layer_metrics(bench, cell.name, ctx)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.Cell.from_benchmark(
        args.workload, bench, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace))

    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    # Every program of a cell, small ones too, is kept, so that only a
    # cell's first run in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = run_cell(cell, bench, T_PROCESS)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"cell {cell.name}: seed {cell.seed}, compile cache {cache}, "
          f"compile {out['setup_compile_s']:.3f} s in set-up "
          f"({out['cache_hits']} persistent-cache hits), "
          f"{out['compiles_in_window']} compiles "
          f"({out['compile_s_in_window']:.3f} s) in the window", flush=True)
    metrics = out["per_layer"] if cell.trace else out["end_to_end"]
    line = harness.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, device=out["device"],
        checks=out["checks"], breakdown=out.get("breakdown"))
    harness.print_checks(out["checks"])
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
