"""Compiles of the main path for a described TPU v5e, with no chip.

The TPU compiler refuses what the CPU and the Pallas interpreter accept:
block shapes off the (8, 128) tiling, kernels over the fast-memory
limit, programs that do not fit the device. These tests compile the
`dht_probe` kernels at the chip smoke's sizes and the batched simulator
at the paper's largest P for one v5e chip, without and with one fault
plan per lane. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so every pytest worker must
collect these tests and only the one that runs them loads it.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import LockSpec, Session, engine
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("op", ["insert", "lookup"])
def test_dht_probe_compiles_for_v5e(one_chip, op):
    nb, TB, K = 4096, 256, 4096          # 2^20 slots, KB = 512 per block
    table = _spec((nb, TB), jnp.int32, one_chip)
    keys = _spec((K,), jnp.int32, one_chip)
    if op == "insert":
        lowered = jax.jit(lambda tk, tv, k, v: ops.dht_insert(
            tk, tv, k, v, interpret=False)).lower(table, table, keys, keys)
    else:
        lowered = jax.jit(lambda tk, tv, k: ops.dht_lookup(
            tk, tv, k, interpret=False)).lower(table, table, keys)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_simulator_compiles_for_v5e(one_chip):
    sess = Session(LockSpec.paper_default("rma_rw", 1024,
                                          writer_fraction=0.02),
                   target_acq=4)
    st = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                      sess.state0)
    seeds = _spec((8,), jnp.int32, one_chip)
    compiled = engine._run_batch_jit.lower(
        sess.table, sess.max_events, st, seeds).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30


def test_batched_fault_loop_compiles_for_v5e(one_chip):
    """The full loop with one fault plan per lane, as
    `Session.run_batch(faults=)` dispatches it at paper scale."""
    sess = Session(LockSpec.paper_default("rma_rw", 1024,
                                          writer_fraction=0.02),
                   target_acq=4)
    st = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                      sess.state0)
    seeds = _spec((8,), jnp.int32, one_chip)
    plan = _spec((8, 1024), jnp.float32, one_chip)
    table = engine.run_table(sess.program, sess.env, faults=True)
    compiled = engine._run_lanes_jit.lower(
        table, sess.max_events, st, seeds, plan, plan).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
