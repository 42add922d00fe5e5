"""Elastic checkpoint restore: a checkpoint written on 1 device restores
onto an 8-device mesh (and trains on), proven in a subprocess because
the host device count is locked at first jax init. Plus the restart
policy: `run_with_recovery`'s budget and exponential backoff."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.checkpoint import save_checkpoint
from tests.test_system import TINY


def test_elastic_restore_other_mesh(tmp_path):
    # Save on this process (1 CPU device).
    from repro.train.step import init_state
    state = init_state(TINY, jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 5, state)

    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {repr(os.path.join(os.path.dirname(__file__), "..", "src"))})
        sys.path.insert(0, {repr(os.path.join(os.path.dirname(__file__), ".."))})
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tests.test_system import TINY
        from repro.checkpoint import load_checkpoint
        from repro.parallel import sharding as shd
        from repro.train.step import build_train_step, init_state
        from repro.data import batch_for

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=2, model=4)
        state = jax.eval_shape(lambda k: init_state(TINY, k),
                               jax.random.PRNGKey(0))
        pspecs = shd.param_spec_tree(state.params, mesh)
        sspecs = type(state)(params=pspecs,
                             opt=type(state.opt)(step=P(), m=pspecs,
                                                 v=pspecs),
                             step=P())
        shard_tree = jax.tree.map(
            lambda s: NamedSharding(mesh, s), sspecs,
            is_leaf=lambda x: isinstance(x, P))
        restored, manifest = load_checkpoint(
            {repr(str(tmp_path))}, 5, state, sharding_tree=shard_tree)
        assert manifest["step"] == 5
        # Train one step on the new mesh to prove the state is usable.
        step_fn = jax.jit(build_train_step(TINY, remat="none"))
        batch = jax.tree.map(jnp.asarray, batch_for(TINY, 4, 32, 0))
        with mesh:
            new_state, metrics = step_fn(restored, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state.step) == 1
        print("ELASTIC_OK", float(metrics["loss"]))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stdout + out.stderr


# ------------------------------------------------- restart budget/backoff
def test_run_with_recovery_exhausts_budget(tmp_path, monkeypatch):
    """A persistently failing run is retried exactly `max_restarts`
    times with exponentially growing delays, then re-raises."""
    from repro.runtime import Trainer, TrainerConfig

    tr = Trainer(TINY, str(tmp_path), TrainerConfig(batch=2, seq=16))
    calls = []
    def boom(num_steps):
        calls.append(num_steps)
        raise RuntimeError("persistent failure")
    monkeypatch.setattr(tr, "run", boom)
    delays = []
    with pytest.raises(RuntimeError, match="max restarts") as ei:
        tr.run_with_recovery(10, max_restarts=3, backoff_s=0.25,
                             sleep=delays.append)
    assert calls == [10, 10, 10, 10]          # 1 try + 3 restarts
    assert delays == [0.25, 0.5, 1.0]
    assert "persistent failure" in str(ei.value.__cause__)


def test_run_with_recovery_transient_failure(tmp_path, monkeypatch):
    """Two transient failures, then success: backoff is applied per
    restart and capped at max_backoff_s."""
    from repro.runtime import Trainer, TrainerConfig

    tr = Trainer(TINY, str(tmp_path), TrainerConfig(batch=2, seq=16))
    attempts = []
    def flaky(num_steps):
        attempts.append(num_steps)
        if len(attempts) < 3:
            raise RuntimeError("flaky")
        return "final-state"
    monkeypatch.setattr(tr, "run", flaky)
    delays = []
    out = tr.run_with_recovery(10, max_restarts=5, backoff_s=0.1,
                               backoff_factor=3.0, max_backoff_s=0.2,
                               sleep=delays.append)
    assert out == "final-state"
    assert delays == [0.1, 0.2]               # 0.3 capped at 0.2
