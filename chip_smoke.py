"""Smoke run of the lock simulator and the DHT kernel on a TPU.

    python3 chip_smoke.py               # phases a-e on one chip
    python3 chip_smoke.py --four-chips  # the sharded grid on four chips

One process drives the main path through the entry points users call:
`LockSpec` -> `Session.run_batch` / `Session.grid` for the simulator,
`Session.run_batch` with one `FaultPlan` per lane for crash recovery,
and `BatchedDHT` over the compiled `dht_probe` kernel.

  a. paper scale: rma_rw and rma_mcs at P=1024 (64 nodes x 16), 8 seeds;
  b. the rma_rw P=64 batch on the chip against the same batch on the
     host CPU, and the paper's ordering rma_rw > fompi_rw in throughput;
  c. a tuner-sized `Session.grid` at P=256 (12 lattice points x 4 seeds),
     one trace, sampled points bitwise-equal to a per-point run_batch;
  d. one writer crashes at P=1024; survivors recover after its lease;
  e. `BatchedDHT`: 2^18 seeded keys into 2^20 slots, every one read back
     against a plain dict, and 4096 absent keys not found.

`--four-chips` runs only phase c's grid, sharded over four chips and
unsharded, plus a batch that is not a multiple of four, and requires
bitwise-equal points and one trace each.

Every gate raises on failure; nothing is caught. The times printed are
smoke timings of one run: compile seconds from JAX's compile events and
wall seconds on the host clock until the results are ready. They are
not benchmark metrics. The last line of standard output is one JSON
object naming the device. Without a TPU the script exits non-zero
before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import LockSpec, Session, engine, metrics_at, spans  # noqa: E402
from repro.dht import BatchedDHT  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# The program's counters of JAX's compile events (`repro.core.spans`).
COMPILE_COUNTERS = ("jit.trace_s", "jit.lower_s", "jit.compile_s")

# Phase c's lattice: the paper's counter spacings, leaf thresholds and
# reader batches around its benchmark point (T_DC=16, T_L=64, T_R=1024).
GRID = dict(t_dc=(1, 4, 16), t_l=((1 << 20, 1), (1 << 20, 64)),
            t_r=(64, 1024))
GRID_SAMPLES = ((0, 0, 0), (2, 1, 1))

# Metrics fields that hold counts; the rest are simulated times.
INT_FIELDS = ("completed", "violations", "total_acquires", "events",
              "per_proc_acq", "n_crashed", "reclaims", "recovery_retries")


class SmokeFailure(RuntimeError):
    """A gate of the smoke did not hold."""


def check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


class Timer:
    """Times calls: compile seconds from the program's counters of JAX's
    compile events, wall seconds on the host clock until the results are
    ready. Counts from the Timer's creation."""

    def __init__(self):
        self._start = spans.counters()

    def _since_start(self, name):
        return spans.counters()[name] - self._start[name]

    @property
    def compile_s(self) -> float:
        return sum(self._since_start(n) for n in COMPILE_COUNTERS)

    @property
    def cache_hits(self) -> int:
        return self._since_start("jit.cache_hits")

    def once(self, label, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        out = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        print(f"  {label}: compile {self.compile_s - c0:.3f} s, "
              f"wall {wall:.3f} s", flush=True)
        return out

    def cold_warm(self, label, fn):
        """Call fn twice: the first call compiles, the second is steady.
        Both must give the same bits."""
        c0, t0 = self.compile_s, time.perf_counter()
        out = jax.block_until_ready(fn())
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = jax.block_until_ready(fn())
        warm = time.perf_counter() - t0
        print(f"  {label}: compile {self.compile_s - c0:.3f} s, first call "
              f"{cold:.3f} s, steady {warm:.3f} s", flush=True)
        check(not differing_fields(out, again),
              f"{label}: a second run gave other results")
        return out


def count_builds(fn):
    """Run fn() counting builds of a point program's handlers: one per
    trace of a grid."""
    before = spans.counters()["program.builds"]
    out = fn()
    return out, spans.counters()["program.builds"] - before


def differing_fields(got, want) -> list:
    return [name for name, g, w in zip(got._fields, got, want)
            if not np.array_equal(np.asarray(g), np.asarray(w))]


def gate_runs(label, m, acquires):
    """Every run: no violation, every survivor done, all acquires made."""
    v, c = np.asarray(m.violations), np.asarray(m.completed)
    a, ev = np.asarray(m.total_acquires), np.asarray(m.events)
    print(f"  {label}: runs {v.size}, violations {int(v.sum())}, "
          f"completed {int(c.sum())}/{c.size}, acquires "
          f"{int(a.min())}..{int(a.max())} (want {acquires}), events per "
          f"run min {int(ev.min())} median {int(np.median(ev))} "
          f"max {int(ev.max())}", flush=True)
    check((v == 0).all(), f"{label}: mutual exclusion violated")
    check(c.all(), f"{label}: a run did not complete")
    check((a == acquires).all(), f"{label}: acquires {a} != {acquires}")


def paper_rw(P: int) -> LockSpec:
    return LockSpec.paper_default("rma_rw", P, writer_fraction=0.02)


# ------------------------------------------------------------- phases
def phase_paper_scale(timer, *, P=1024, seeds=8, target_acq=4) -> Session:
    """a. rma_rw and rma_mcs at the paper's largest P. Returns the rma_rw
    session, which phase d reuses (same program, no new compile)."""
    print(f"a. paper scale: P={P}, target_acq={target_acq}, {seeds} seeds",
          flush=True)
    rw = None
    for spec in (paper_rw(P), LockSpec.paper_default("rma_mcs", P)):
        sess = Session(spec, target_acq=target_acq)
        label = f"{spec.kind} P={P}"
        m = timer.cold_warm(f"{label} run_batch",
                            lambda: sess.run_batch(np.arange(seeds)))
        gate_runs(label, m, P * target_acq)
        if spec.kind == "rma_rw":
            rw = sess
    return rw


def phase_cpu_crosscheck(timer, *, P=64, seeds=8, target_acq=4):
    """b. The same rma_rw batch on the default device and on the host
    CPU; then the paper's ordering against fompi_rw."""
    print(f"b. cross-check against the CPU: rma_rw P={P}, {seeds} seeds",
          flush=True)
    spec = paper_rw(P)
    sess = Session(spec, target_acq=target_acq)
    dev = timer.cold_warm(f"rma_rw P={P} on {jax.devices()[0].platform}",
                          lambda: sess.run_batch(np.arange(seeds)))
    gate_runs(f"rma_rw P={P}", dev, P * target_acq)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        host_sess = Session(spec, target_acq=target_acq)
        host = timer.once(f"rma_rw P={P} on cpu",
                          lambda: host_sess.run_batch(np.arange(seeds)))
    check(host.makespan.devices() == {cpu}, "the CPU run left the CPU")
    differ = differing_fields(dev, host)
    if not differ:
        print("  chip vs cpu: bitwise equal in every Metrics field")
    else:
        for name in differ:
            g = np.asarray(getattr(dev, name), np.float64)
            w = np.asarray(getattr(host, name), np.float64)
            rel = np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-30))
            print(f"  chip vs cpu: {name} differs, max relative "
                  f"difference {rel:.3e}")
        bad = [f for f in differ if f in INT_FIELDS]
        check(not bad, f"chip and cpu disagree on counts: {bad}")
        mk_d, mk_h = np.asarray(dev.makespan), np.asarray(host.makespan)
        check(np.allclose(mk_d, mk_h, rtol=1e-6, atol=0),
              "chip and cpu makespans differ by more than 1e-6")

    base = Session(LockSpec.paper_default("fompi_rw", P,
                                          writer_fraction=0.02),
                   target_acq=target_acq)
    fm = timer.cold_warm(f"fompi_rw P={P} run_batch",
                         lambda: base.run_batch(np.arange(seeds)))
    gate_runs(f"fompi_rw P={P}", fm, P * target_acq)
    t_rma = float(np.mean(np.asarray(dev.throughput)))
    t_fompi = float(np.mean(np.asarray(fm.throughput)))
    print(f"  throughput (simulated acquires/s, mean of seeds): rma_rw "
          f"{t_rma!r}, fompi_rw {t_fompi!r}", flush=True)
    check(t_rma > t_fompi, "paper ordering: rma_rw must beat fompi_rw")


def phase_grid(timer, *, P=256, grid=GRID, seeds=4, target_acq=4,
               samples=GRID_SAMPLES):
    """c. One `Session.grid` dispatch over the lattice x seeds."""
    t_dc, t_l, t_r = grid["t_dc"], grid["t_l"], grid["t_r"]
    print(f"c. grid: rma_rw P={P}, {len(t_dc)}x{len(t_l)}x{len(t_r)} "
          f"points x {seeds} seeds", flush=True)
    spec = paper_rw(P)
    sess = Session(spec, target_acq=target_acq)
    seeds = np.arange(seeds)
    m, builds = count_builds(lambda: timer.cold_warm(
        "grid", lambda: sess.grid(t_dc, t_l, t_r, seeds=seeds)))
    print(f"  grid traces: {builds}")
    check(builds == 1, f"grid traced the point program {builds} times")
    gate_runs("grid", m, P * target_acq)
    for d, l, r in samples:
        point = spec.replace(T_DC=t_dc[d], T_L=t_l[l], T_R=t_r[r])
        label = f"point T_DC={point.T_DC} T_L={point.T_L} T_R={point.T_R}"
        one = Session(point, target_acq=target_acq)
        want = timer.once(f"{label} run_batch",
                          lambda: one.run_batch(seeds))
        differ = differing_fields(metrics_at(m, d, l, r), want)
        print(f"  {label}: grid vs run_batch "
              f"{'bitwise equal' if not differ else differ}")
        check(not differ, f"{label}: grid differs from run_batch")


def phase_crash(timer, sess: Session, *, seeds=8, t_crash=2.0):
    """d. The first writer crashes at t_crash; survivors must recover
    through the lease and finish."""
    P = sess.spec.P
    victim = int(np.flatnonzero(sess.is_writer)[0])
    lease = sess.env.lease
    print(f"d. crash recovery: rma_rw P={P}, writer {victim} crashes at "
          f"{t_crash} us, lease {lease} us, {seeds} seeds", flush=True)
    plan = engine.FaultPlan.lanes(P, [victim] * seeds, [t_crash] * seeds)
    m = timer.cold_warm("run_batch with FaultPlans", lambda: (
        sess.run_batch(np.arange(seeds), faults=plan)))
    v, c = np.asarray(m.violations), np.asarray(m.completed)
    crashed = np.asarray(m.n_crashed)
    t_c, t_r = np.asarray(m.t_crash), np.asarray(m.t_recover)
    recovered = t_r < float(engine.INF)
    print(f"  violations {int(v.sum())}, survivors completed "
          f"{int(c.sum())}/{c.size}, crashed per run {crashed.tolist()}, "
          f"recovered in {int(recovered.sum())} runs, reclaims "
          f"{np.asarray(m.reclaims).tolist()}")
    print(f"  t_recover - t_crash (us): "
          f"{(t_r - t_c)[recovered].tolist()}", flush=True)
    check((v == 0).all(), "crash: mutual exclusion violated")
    check(c.all(), "crash: a survivor did not complete")
    check((crashed == 1).all(), "crash: the victim did not crash")
    check((t_r[recovered] >= t_c[recovered] + lease).all(),
          "crash: a survivor reclaimed before the lease ran out")


def distinct_keys(rng, n: int) -> np.ndarray:
    """n distinct positive int32 keys in random order."""
    keys = np.unique(rng.integers(1, 2**31 - 1, size=2 * n, dtype=np.int64))
    check(keys.size >= n, "too few distinct keys drawn")
    return rng.permutation(keys)[:n].astype(np.int32)


def phase_dht(timer, *, nb=4096, TB=256, heap=1 << 16, n_keys=1 << 18,
              n_absent=4096, batch=4096, seed=0, interpret=False):
    """e. Insert seeded distinct keys, read every one back, and miss
    every absent key; a plain dict is the reference."""
    print(f"e. DHT: {nb * TB} slots (nb={nb}, TB={TB}), heap {heap}, "
          f"{n_keys} keys in batches of {batch}, {n_absent} absent keys, "
          f"interpret={interpret}", flush=True)
    rng = np.random.default_rng(seed)
    keys = distinct_keys(rng, n_keys + n_absent)
    ins, absent = keys[:n_keys], keys[n_keys:]
    vals = rng.integers(0, 2**31 - 1, size=n_keys, dtype=np.int64)
    vals = vals.astype(np.int32)
    dht = BatchedDHT(nb=nb, TB=TB, heap=heap, interpret=interpret)
    st = dht.init()

    def insert(st, i):
        return dht.insert(st, jnp.asarray(ins[i:i + batch]),
                          jnp.asarray(vals[i:i + batch]))

    st, s0 = timer.once("first insert batch", lambda: insert(st, 0))
    status = [s0]

    def insert_rest():
        nonlocal st
        for i in range(batch, n_keys, batch):
            st, s = insert(st, i)
            status.append(s)
        return st

    timer.once(f"{n_keys // batch - 1} more insert batches", insert_rest)
    status = np.concatenate([np.asarray(s) for s in status])
    heap_ptr = int(st.heap_ptr)
    n_table, n_heap = int((status == 0).sum()), int((status == 2).sum())
    print(f"  inserted: {n_table} in the table, {n_heap} to the heap, "
          f"heap pointer {heap_ptr}/{heap}")
    check(n_table + n_heap == n_keys, "an insert was neither placed nor "
          "sent to the heap")
    check(heap_ptr == n_heap and heap_ptr <= heap,
          "the overflow heap is full: acknowledged inserts were dropped")

    queries = np.concatenate([ins, absent])
    outs, founds = [], []

    def lookup_all():
        for i in range(0, queries.size, batch):
            out, found = dht.lookup(st, jnp.asarray(queries[i:i + batch]))
            outs.append(out)
            founds.append(found)
        return outs[-1]

    timer.once(f"{-(-queries.size // batch)} lookup batches (first "
               f"compiles)", lookup_all)
    out = np.concatenate([np.asarray(o) for o in outs])
    found = np.concatenate([np.asarray(f) for f in founds])
    ref = dict(zip(ins.tolist(), vals.tolist()))
    want = np.array([ref.get(k, -1) for k in queries.tolist()], np.int64)
    want_found = np.array([k in ref for k in queries.tolist()])
    n_missed = int((~found & want_found).sum())
    n_wrong = int((found & want_found & (out != want)).sum())
    n_phantom = int((found & ~want_found).sum())
    print(f"  lookups: {queries.size}; inserted keys not found {n_missed}, "
          f"wrong values {n_wrong}, absent keys found {n_phantom}",
          flush=True)
    check(n_missed == 0 and n_wrong == 0 and n_phantom == 0,
          "the table disagrees with the dict reference")


def phase_four_chips(timer, *, n=4, P=256, grid=GRID, seeds=4,
                     target_acq=4, pad_grid=None):
    """Phase c's grid sharded over n devices against the unsharded
    dispatch, plus a batch that is not a multiple of n."""
    check(len(jax.devices()) >= n,
          f"--four-chips needs {n} devices, JAX has {len(jax.devices())}")
    spec = paper_rw(P)
    sess = Session(spec, target_acq=target_acq)
    pad_grid = pad_grid or dict(t_dc=(16,), t_l=((1 << 20, 64),),
                                t_r=(64, 1024))
    cases = (("grid", grid, seeds), ("padded grid", pad_grid, 3))
    for name, g, s in cases:
        points = len(g["t_dc"]) * len(g["t_l"]) * len(g["t_r"])
        print(f"sharded {name}: rma_rw P={P}, {points} points x {s} seeds "
              f"= {points * s} entries over {n} devices", flush=True)
        runs = {}
        for devices in (None, n):
            runs[devices], builds = count_builds(lambda: timer.cold_warm(
                f"devices={devices}",
                lambda: sess.grid(g["t_dc"], g["t_l"], g["t_r"],
                                  seeds=np.arange(s), devices=devices)))
            print(f"  devices={devices}: traces {builds}")
            check(builds == 1, f"{name} devices={devices} traced "
                  f"{builds} times")
            gate_runs(f"{name} devices={devices}", runs[devices],
                      P * target_acq)
        differ = differing_fields(runs[n], runs[None])
        print(f"  sharded vs unsharded: "
              f"{'bitwise equal' if not differ else differ}", flush=True)
        check(not differ, f"{name}: sharded differs from unsharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the grid sharded over four chips")
    args = ap.parse_args(argv)
    cache = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    timer = Timer()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(timer)
    else:
        rw = phase_paper_scale(timer)
        phase_cpu_crosscheck(timer)
        phase_grid(timer)
        phase_crash(timer, rw)
        phase_dht(timer)
    print(f"total: wall {time.perf_counter() - t0:.3f} s, compile "
          f"{timer.compile_s:.3f} s, persistent cache hits "
          f"{timer.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
