"""chip_smoke.py's phases at tiny sizes on the CPU, with the DHT kernel
in interpret mode; the script's refusal to run without a TPU; and where
the entry points keep JAX's compilation cache."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke as smoke
from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_GRID = dict(t_dc=(1, 16), t_l=((1 << 20, 1), (1 << 20, 64)),
                 t_r=(64,))


@pytest.fixture(scope="module")
def timer():
    return smoke.Timer()


def test_paper_scale_and_crash_phases(timer):
    rw = smoke.phase_paper_scale(timer, P=32, seeds=2, target_acq=2)
    smoke.phase_crash(timer, rw, seeds=2)


def test_cpu_crosscheck_phase(timer):
    smoke.phase_cpu_crosscheck(timer, P=64, seeds=2, target_acq=2)


def test_grid_phase(timer):
    smoke.phase_grid(timer, P=32, grid=TINY_GRID, seeds=2, target_acq=2,
                     samples=((1, 1, 0),))


def test_dht_phase(timer, capsys):
    smoke.phase_dht(timer, nb=8, TB=128, heap=1024, n_keys=1024,
                    n_absent=64, batch=256, interpret=True)
    out = capsys.readouterr().out
    assert "inserted keys not found 0, wrong values 0, absent keys " \
           "found 0" in out


def test_four_chips_phase_on_local_devices(timer, capsys):
    smoke.phase_four_chips(timer, n=len(jax.devices()), P=32,
                           grid=dict(TINY_GRID, t_l=((1 << 20, 64),)),
                           seeds=2, target_acq=2)
    assert capsys.readouterr().out.count("sharded vs unsharded: bitwise "
                                         "equal") == 2


def test_gate_failure_raises():
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


def _run(args, **env):
    """Run python with args on the CPU; env entries set to None are
    removed from the environment."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"), **env)
    env = {k: v for k, v in env.items() if v is not None}
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_smoke_without_tpu_exits_nonzero_and_prints_no_result():
    proc = _run([os.path.join(REPO, "chip_smoke.py")])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_compile_cache_uses_the_environment_directory(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n")
    proc = _run(["-c", code], JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_compile_cache_defaults_to_the_repo_directory():
    """Without the variable the cache is the fixed <repo>/.jax_cache."""
    code = ("import jax\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = _run(["-c", code], JAX_COMPILATION_CACHE_DIR=None)
    assert proc.returncode == 0, proc.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want, want]
    assert compile_cache.REPO_CACHE == want
