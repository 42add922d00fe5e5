"""Device-sharded grid exploration + makespan accounting + tuner
input hardening.

The sharded contract: `Session.grid/sweep/run_batch` with `devices=`
flatten the (lattice points × seeds) batch, pad it to a device
multiple with dead entries, shard it over a 1D mesh, and unpad the
Metrics — per-point results BITWISE-equal to the single-device
dispatch, still one trace. In-process tests cover the 1-device
degenerate mesh (this host has one CPU device); the true multi-device
+ padding path runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count forced BEFORE jax
import (jax pins the device count at first init).

The makespan contract: `Metrics.makespan` is the *finish* time of the
last instruction (`SimState.t_finish`), not the start time of the last
event (`SimState.clock`).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import LockSpec, Session, TuneResult, engine, tune

MAX_EVENTS = 400_000

SMALL_RW = LockSpec(kind="rma_rw", P=8, fanout=(2,), T_DC=2, T_L=(2, 2),
                    T_R=8, writer_fraction=0.25)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def assert_metrics_equal(got, want, ctx):
    for name, g, w in zip(got._fields, got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (ctx, name)


# ------------------------------------------ sharded == unsharded (1 dev)
def test_sharded_grid_one_device_degenerate_bitwise():
    """devices=[single cpu] exercises the full pad/shard/unpad path on
    a 1-device mesh; results must be bitwise the unsharded dispatch."""
    sess = Session(SMALL_RW, target_acq=2, max_events=MAX_EVENTS)
    t_dc, t_l, t_r, seeds = [1, 2], [(2, 2), (2, 4)], [4, 16], [0, 1, 2]
    ref = sess.grid(t_dc, t_l, t_r, seeds=seeds)
    got = sess.grid(t_dc, t_l, t_r, seeds=seeds,
                    devices=jax.local_devices()[:1])
    assert got.violations.shape == (2, 2, 2, 3)
    assert_metrics_equal(got, ref, "grid devices=[cpu:0]")


def test_sharded_sweep_and_run_batch_one_device_bitwise():
    sess = Session(SMALL_RW, target_acq=2, max_events=MAX_EVENTS)
    seeds = [0, 1, 2]
    assert_metrics_equal(
        sess.sweep("T_DC", [1, 2, 8], seeds=seeds, devices=1),
        sess.sweep("T_DC", [1, 2, 8], seeds=seeds), "sweep devices=1")
    assert_metrics_equal(
        sess.run_batch(seeds, devices=1),
        sess.run_batch(seeds), "run_batch devices=1")


def test_session_level_devices_default_and_override():
    """Constructor devices= is the default; per-call devices=None forces
    the classic single-device path on the same session."""
    sess = Session(SMALL_RW, target_acq=2, max_events=MAX_EVENTS,
                   devices=1)
    ref = Session(SMALL_RW, target_acq=2,
                  max_events=MAX_EVENTS).run_batch([0, 1])
    assert_metrics_equal(sess.run_batch([0, 1]), ref, "session default")
    assert_metrics_equal(sess.run_batch([0, 1], devices=None), ref,
                         "explicit None override")


def test_devices_argument_validation():
    sess = Session(SMALL_RW, target_acq=2, max_events=MAX_EVENTS)
    with pytest.raises(ValueError, match="local device"):
        sess.run_batch([0], devices=0)
    with pytest.raises(ValueError, match="local device"):
        sess.run_batch([0], devices=10_000)
    with pytest.raises(ValueError, match="non-empty"):
        sess.run_batch([0], devices=[])


# --------------------------------- true multi-device path (subprocess)
def test_sharded_grid_eight_forced_devices():
    """The real thing: 8 forced host devices, bitwise equivalence incl.
    the non-multiple-of-device-count padding path, single-trace assert.
    Subprocess because jax pins the device count at first init."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "grid_smoke.py"),
         "--devices", "8"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharded grid smoke ok" in proc.stdout, proc.stdout


# -------------------------------------------------- makespan accounting
def test_makespan_is_last_event_finish_not_start():
    """2-process spec with known latencies: makespan must be the max
    instruction *finish* time, strictly after the start time of the
    last event (the old buggy value — `summarize` used `st.clock`)."""
    spec = LockSpec(kind="fompi_spin", P=2)
    sess = Session(spec, target_acq=3, max_events=100_000)
    for seed in range(4):
        st = sess.run_state(seed)
        m = engine.summarize(st)
        assert bool(np.asarray(m.completed))
        mk = float(np.asarray(m.makespan))
        clock = float(np.asarray(st.clock))
        assert mk == float(np.asarray(st.t_finish))
        # finish = start + dur + jitter of some instruction that starts
        # no earlier than every other finishes: strictly past `clock`.
        assert mk > clock, (seed, mk, clock)
        # ... and by no more than one maximal instruction round-trip
        # (longest latency, atomic premium, occupancy, CS + think ~0
        # for this spec) — the fix removes a one-op bias, not more.
        worst = (max(spec.cost.lat) * spec.cost.atomic_factor
                 + spec.cost.occupancy + spec.cost.jitter)
        assert mk <= clock + worst, (seed, mk, clock)


def test_makespan_monotone_in_events():
    """t_finish is a running max: longer runs never report a smaller
    makespan (guards against clock-style regressions)."""
    sess2 = Session(SMALL_RW, target_acq=2, max_events=MAX_EVENTS)
    sess4 = Session(SMALL_RW, target_acq=4, max_events=MAX_EVENTS)
    m2 = float(np.asarray(sess2.run(0).makespan))
    m4 = float(np.asarray(sess4.run(0).makespan))
    assert m4 > m2


@pytest.mark.parametrize("n", [1, 48, 1024])
def test_pairwise_sum_is_one_fixed_order(n):
    """mean_latency's sum over processes follows one pairwise order, the
    same bits alone and inside batches of any size, so grid, run_batch
    and sharded dispatches agree per point on every backend."""
    rng = np.random.default_rng(n)
    x = rng.uniform(0, 1e4, size=(8, n)).astype(np.float32)
    ref = []
    for row in x:
        r = np.concatenate([row, np.zeros(-n % (1 << (n - 1).bit_length()),
                                          np.float32)])
        while r.size > 1:
            r = r[:r.size // 2] + r[r.size // 2:]
        ref.append(r[0])
    ref = np.asarray(ref, np.float32)
    for b in (1, 3, 8):
        got = np.asarray(jax.jit(jax.vmap(engine.pairwise_sum))(x[:b]))
        assert got.tobytes() == ref[:b].tobytes()
    np.testing.assert_allclose(ref, x.astype(np.float64).sum(axis=1),
                               rtol=1e-5)


# ------------------------------------------------ tuner input hardening
def test_spec_rejects_tdc_above_p():
    """T_DC > P silently degraded to one counter in counter_ranks;
    LockSpec now bounds it, covering grid/sweep/serving — not just the
    tuner's up-front lattice validation."""
    with pytest.raises(ValueError, match="T_DC"):
        LockSpec(kind="rma_rw", P=8, fanout=(2,), T_DC=16, T_L=(2, 2))
    sess = Session(SMALL_RW, target_acq=2, max_events=MAX_EVENTS)
    with pytest.raises(ValueError, match="T_DC"):
        sess.grid([16], [(2, 2)], [8])
    with pytest.raises(ValueError, match="T_DC"):
        sess.sweep("T_DC", [16])


def test_tune_rejects_out_of_range_axes():
    with pytest.raises(ValueError, match="t_dc"):
        tune(SMALL_RW, t_dc=[0], t_l=[(2, 2)], t_r=[4], seeds=(0,),
             refine_rounds=0)
    with pytest.raises(ValueError, match="t_dc"):
        tune(SMALL_RW, t_dc=[16], t_l=[(2, 2)], t_r=[4], seeds=(0,),
             refine_rounds=0)       # > P=8
    with pytest.raises(ValueError, match="t_r"):
        tune(SMALL_RW, t_dc=[2], t_l=[(2, 2)], t_r=[0], seeds=(0,),
             refine_rounds=0)
    with pytest.raises(ValueError, match="t_l"):
        tune(SMALL_RW, t_dc=[2], t_l=[(2, 0)], t_r=[4], seeds=(0,),
             refine_rounds=0)
    with pytest.raises(ValueError, match="t_l"):
        tune(SMALL_RW, t_dc=[2], t_l=[()], t_r=[4], seeds=(0,),
             refine_rounds=0)


def test_tune_reports_device_count_and_json_backcompat():
    res = tune(SMALL_RW, t_dc=[2], t_l=[(2, 2)], t_r=[8], seeds=(0,),
               refine_rounds=0, target_acq=2, max_events=MAX_EVENTS,
               devices=1)
    assert res.n_devices == 1
    assert TuneResult.from_json(res.to_json()).n_devices == 1
    # Reports written before the field existed still load (default 1).
    d = res.to_dict()
    del d["n_devices"]
    assert TuneResult.from_json(json.dumps(d)).n_devices == 1


# -------------------------------------- benchmark formatting hardening
def test_show_and_write_csv_coerce_numpy_scalars(tmp_path, monkeypatch,
                                                 capsys):
    from benchmarks import run as bench_run
    rows = [{"P": np.int32(8), "throughput_per_s": np.float32(123.456789),
             "kind": "rma_rw"}]
    bench_run.show("t", rows, ["kind", "P", "throughput_per_s"])
    out = capsys.readouterr().out
    assert "np.float32" not in out and "np.int32" not in out
    # np.float32 must take the float branch (%.4g), not the str branch.
    assert "123.5" in out and "123.45679" not in out
    monkeypatch.setattr(bench_run, "RESULTS", str(tmp_path))
    bench_run.write_csv("coerce", rows)
    text = (tmp_path / "coerce.csv").read_text()
    assert "np.float32" not in text and "123.45" in text
