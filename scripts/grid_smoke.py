"""CI smoke for the one-dispatch 3D grid path and the grid tuner.

Runs a small-machine 2x2x2 (T_DC, T_L, T_R) lattice under 2 seeds and
asserts the single-trace property via a compile count: the point
program must be built exactly ONCE for the whole grid (vmap traces the
point body once), so the shape-stable T_DC path can never silently
regress to per-point compiles. Then dry-runs the tuner and checks its
emitted LockSpec survives JSON round-tripping.

With `--devices N` the same lattice additionally runs device-sharded
(flattened points x seeds padded to a device multiple) and must be
bitwise-equal per point to the single-device dispatch, again with ONE
trace. Force host devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python scripts/grid_smoke.py --devices 8

    PYTHONPATH=src python scripts/grid_smoke.py
"""
import argparse

import numpy as np

from repro.core import LockSpec, Session, TuneResult, spans, tune


def count_builds(fn):
    """Run fn() counting builds of the point program's handlers (one
    per trace)."""
    before = spans.counters()["program.builds"]
    out = fn()
    return out, spans.counters()["program.builds"] - before


def assert_bitwise(got, want, ctx):
    for name, g, w in zip(got._fields, got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (ctx, name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="also run the lattice sharded over N local "
                         "devices and assert bitwise equivalence")
    args = ap.parse_args()

    spec = LockSpec(kind="rma_rw", P=8, fanout=(2,), T_DC=2, T_L=(2, 2),
                    T_R=8, writer_fraction=0.25)
    sess = Session(spec, target_acq=2, max_events=200_000)
    lattice = dict(t_dc=[1, 2], t_l=[(2, 2), (2, 4)], t_r=[4, 16])

    m, n = count_builds(
        lambda: sess.grid(seeds=[0, 1], **lattice))
    assert m.violations.shape == (2, 2, 2, 2), m.violations.shape
    assert int(np.asarray(m.violations).sum()) == 0, "mutual exclusion"
    assert bool(np.asarray(m.completed).all()), "liveness"
    assert n == 1, (
        f"grid built the point program {n} times — the "
        f"single-dispatch T_DC path regressed to per-point compiles")
    print("grid smoke ok: 2x2x2 lattice x 2 seeds, ONE trace, "
          "0 violations")

    if args.devices:
        import jax
        assert jax.local_device_count() >= args.devices, (
            f"{jax.local_device_count()} local devices < {args.devices}; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={args.devices} before running")
        # 2 seeds x 8 lattice points = 16 entries; 3 seeds = 24. Run a
        # 3-seed sharded grid so N=8 devices also exercises chunking,
        # and a 1x1x1 x 2-seed one so B < N exercises the padding path.
        ms, n = count_builds(lambda: sess.grid(
            seeds=[0, 1, 2], devices=args.devices, **lattice))
        assert n == 1, f"sharded grid traced {n} times, want 1"
        ref = sess.grid(seeds=[0, 1, 2], **lattice)
        assert_bitwise(ms, ref, "sharded grid")
        pad = sess.grid([2], [(2, 2)], [8], seeds=[0, 1],
                        devices=args.devices)
        pad_ref = sess.grid([2], [(2, 2)], [8], seeds=[0, 1])
        assert_bitwise(pad, pad_ref, "sharded grid (padded)")
        print(f"sharded grid smoke ok: {args.devices} devices, ONE "
              f"trace, bitwise == single-device (padding path incl.)")

    res = tune(spec, t_dc=[1, 2], t_l=[(2, 2), (2, 4)], t_r=[4, 16],
               seeds=(0, 1), refine_rounds=0, target_acq=2,
               max_events=200_000, devices=args.devices)
    assert LockSpec.from_dict(res.to_dict()["spec"]) == res.spec
    assert TuneResult.from_json(res.to_json()).spec == res.spec
    assert res.n_devices == (args.devices or 1)
    print(f"tuner dry-run ok: winner T_DC={res.spec.T_DC} "
          f"T_L={res.spec.T_L} T_R={res.spec.T_R} "
          f"({res.n_points} points, throughput {res.throughput:.4g}/s, "
          f"{res.n_devices} device(s))")


if __name__ == "__main__":
    main()
