"""Quickstart: the paper's RMA-RW lock + the DHT it accelerates.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LockSpec, Session
from repro.dht import BatchedDHT

# --- 1. A topology-aware distributed Reader-Writer lock (paper §3) ----
# 64 processes on 4 nodes; one physical counter per node (T_DC=16);
# up to 8 consecutive local writer passes (T_L leaf), 1024 reader batch.
# A LockSpec is one point in the paper's (T_DC, T_L, T_R) space -- it
# validates on construction and round-trips through JSON.
spec = LockSpec(kind="rma_rw", P=64, fanout=(4,), T_DC=16,
                T_L=(1 << 20, 8), T_R=1024, writer_fraction=0.02)
assert LockSpec.from_json(spec.to_json()) == spec

sess = Session(spec, target_acq=8, cs_kind=1)
m = sess.run(seed=0)
print(f"RMA-RW:  {int(m.total_acquires)} acquires, "
      f"violations={int(m.violations)}, "
      f"throughput={float(m.throughput):.3g}/s (simulated), "
      f"locality={float(m.locality):.2f}")

# One jitted dispatch, 32 seeds = 32 distinct schedule interleavings
# (the executable analogue of the paper's SPIN checking, §4.4).
mb = sess.run_batch(np.arange(32))
print(f"         32-seed batch: violations={int(mb.violations.sum())}, "
      f"throughput={float(mb.throughput.mean()):.3g}"
      f"+-{float(mb.throughput.std()):.2g}/s")

# The same workload on the centralized foMPI-RW baseline:
base = Session(LockSpec(kind="fompi_rw", P=64, writer_fraction=0.02),
               target_acq=8, cs_kind=1)
mbase = base.run(seed=0)
print(f"foMPI-RW: throughput={float(mbase.throughput):.3g}/s "
      f"({float(m.throughput) / float(mbase.throughput):.1f}x slower than "
      f"RMA-RW)")

# --- 2. The distributed hashtable case study (paper §5.3), TPU-style --
# The probe kernel compiles for the TPU; anywhere else it runs in the
# Pallas interpreter.
interpret = jax.default_backend() != "tpu"
print(f"DHT kernel: interpret={interpret} "
      f"(backend {jax.default_backend()})")
dht = BatchedDHT(nb=8, TB=128, heap=1024, interpret=interpret)
st = dht.init()
keys = jnp.asarray(np.random.RandomState(0).permutation(10_000)[:200] + 1,
                   jnp.int32)
vals = jnp.arange(200, dtype=jnp.int32)
st, status = dht.insert(st, keys, vals)
out, found = dht.lookup(st, keys)
print(f"DHT:     inserted={int((status == 0).sum())}, "
      f"overflow={int((status == 2).sum())}, "
      f"all found={bool(jnp.all(found))}, "
      f"values ok={bool(jnp.all(out == vals))}")
