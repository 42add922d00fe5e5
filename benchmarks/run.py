"""Benchmark driver: one section per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--full]
    PYTHONPATH=src python -m benchmarks.run --tune [--quick]

Writes results/bench/*.csv and prints a summary. Simulated latencies /
throughputs come from the calibrated cost model (DESIGN.md §4); the
roofline section reads the dry-run artifacts if present.

`--tune` runs the coarse-to-fine (T_DC, T_L, T_R) grid auto-tuner
(repro.core.tuner) for the paper's benchmark workload and writes the
winning LockSpec + evidence to results/bench/tuned_spec.json; the
embedded spec round-trips through `LockSpec.from_dict` unchanged.
"""
from __future__ import annotations

import argparse
import csv
import os

from repro.launch.compile_cache import use_compile_cache

RESULTS = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "results", "bench"))


def coerce_scalars(rows):
    """Convert numpy scalars to plain Python values.

    `isinstance(x, float)` is False for np.float32/np.float64 scalars,
    so without this they fall into show()'s string branch and print as
    `np.float32(...)` noise (and write_csv emits the same repr).
    """
    import numpy as np

    return [{k: (v.item() if isinstance(v, np.generic) else v)
             for k, v in r.items()} for r in rows]


def write_csv(name, rows):
    if not rows:
        return
    rows = coerce_scalars(rows)
    keys = sorted({k for r in rows for k in r})
    with open(os.path.join(RESULTS, name + ".csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)


def show(title, rows, cols):
    rows = coerce_scalars(rows)
    print(f"\n== {title} ==")
    hdr = " ".join(f"{c:>16s}" for c in cols)
    print(hdr)
    for r in rows:
        print(" ".join(
            f"{r.get(c, ''):>16.4g}" if isinstance(r.get(c), float)
            else f"{str(r.get(c, '')):>16s}" for c in cols))


def run_tuner(args) -> str:
    """`--tune`: grid-search the 3D space for the benchmark workload and
    emit the winning LockSpec as JSON."""
    import json

    from repro.core import LockSpec
    from repro.core.tuner import tune

    P = 16 if args.quick else (256 if args.full else 64)
    spec = LockSpec.paper_default("rma_rw", P, writer_fraction=0.05)
    res = tune(spec,
               seeds=(0, 1) if args.quick else tuple(range(4)),
               refine_rounds=0 if args.quick else (2 if args.full else 1),
               target_acq=2 if args.quick else 4,
               max_events=400_000 if args.quick else 2_000_000,
               devices=args.devices)
    # The emitted spec must survive serialization exactly — it is the
    # deployment artifact.
    assert LockSpec.from_dict(res.to_dict()["spec"]) == res.spec
    path = os.path.join(RESULTS, "tuned_spec.json")
    with open(path, "w") as f:
        json.dump(res.to_dict(), f, indent=2, sort_keys=True)
    print(f"\n== TUNE: best (T_DC, T_L, T_R) point for rma_rw P={P} ==")
    print(f"  winner: T_DC={res.spec.T_DC} T_L={res.spec.T_L} "
          f"T_R={res.spec.T_R}")
    print(f"  {res.objective}: {res.score:.4g} "
          f"({res.n_points} lattice points, {len(res.rounds)} rounds, "
          f"{res.n_devices} device(s))")
    print(f"  report: {path}")
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small P values only (CI-speed)")
    ap.add_argument("--full", action="store_true",
                    help="larger P sweep (P up to 1024; slow)")
    ap.add_argument("--only", default=None,
                    help="comma list: lb,ecsb,sob,wcsb,warb,rw,tdc,tl,tr,"
                         "dht,roofline,faults")
    ap.add_argument("--tune", action="store_true",
                    help="run the 3D grid auto-tuner and write "
                         "results/bench/tuned_spec.json")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="shard --tune and the threshold-sweep sections "
                         "over the first N local devices (force host "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    use_compile_cache()
    os.makedirs(RESULTS, exist_ok=True)

    if args.tune:
        if args.only:
            print("note: --tune runs alone; ignoring --only "
                  f"{args.only!r} (run the sections without --tune)")
        run_tuner(args)
        return

    from benchmarks import dht_bench, faults, locks, roofline, thresholds

    ps = (16, 64) if args.quick else (16, 64, 256)
    if args.full:
        ps = (16, 64, 256, 1024)
    only = set(args.only.split(",")) if args.only else None

    def want(x):
        return only is None or x in only

    if want("lb"):
        rows = locks.bench_latency(ps=ps)
        write_csv("lb", rows)
        show("LB: acquire+release latency (us, simulated)", rows,
             ["bench", "kind", "P", "latency_us"])
    for b in ("ecsb", "sob", "wcsb", "warb"):
        if want(b):
            rows = locks.bench_throughput(b, ps=ps)
            write_csv(b, rows)
            show(f"{b.upper()}: throughput (acquires/s, simulated)", rows,
                 ["bench", "kind", "P", "throughput_per_s", "locality"])
    if want("rw"):
        rows = locks.bench_rw_vs_sota(ps=ps)
        write_csv("rw_vs_sota", rows)
        show("RW vs SOTA (Fig. 5)", rows,
             ["kind", "F_W", "P", "throughput_per_s"])
    if want("tdc"):
        rows = thresholds.sweep_tdc(ps=ps[:2] if args.quick else ps,
                                    devices=args.devices)
        write_csv("tdc", rows)
        show("T_DC sweep (Fig. 4a)", rows,
             ["T_DC", "P", "throughput_per_s", "latency_us"])
    if want("tl"):
        rows = thresholds.sweep_tl_product(devices=args.devices)
        rows += thresholds.sweep_tl_split(devices=args.devices)
        write_csv("tl", rows)
        show("T_L sweeps (Fig. 4b-d)", rows,
             ["bench", "T_L", "throughput_per_s", "latency_us",
              "locality"])
    if want("tr"):
        rows = thresholds.sweep_tr(devices=args.devices)
        write_csv("tr", rows)
        show("T_R sweep (Fig. 4e-f)", rows,
             ["T_R", "F_W", "throughput_per_s"])
    if want("dht"):
        rows = dht_bench.bench_dht(ps=(16,) if args.quick else (16, 64))
        write_csv("dht", rows)
        show("DHT case study (Fig. 6; total us, lower=better)", rows,
             ["P", "F_W", "fompi_a_us", "fompi_rw_us", "rma_rw_us"])
    if want("faults"):
        payload = faults.bench_faults(quick=args.quick)
        rows = payload["rows"]
        for r in rows:
            assert r["violations"] == 0, f"ME violated: {r}"
            assert r["all_completed"], f"survivors stalled: {r}"
        show("FAULTS: crash injection + lease recovery (us, simulated)",
             rows, ["kind", "P", "n_runs", "n_recovered",
                    "recovery_us_p50", "recovery_us_p99",
                    "total_reclaims", "violations"])
        if not args.quick:
            import json

            path = os.path.join(RESULTS, "BENCH_faults.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.write("\n")
    if want("roofline"):
        recs = roofline.load_records()
        if recs:
            print("\n== Roofline (from dry-run artifacts) ==")
            print(roofline.markdown_table(recs, mesh="pod16x16"))
        else:
            print("\n(no dry-run artifacts; run python -m "
                  "repro.launch.dryrun first)")
    print(f"\nbenchmarks complete; csv in {RESULTS}")


if __name__ == "__main__":
    main()
