"""Batched TPU hashtable = the paper's "local volume" (fixed-size table
+ overflow heap), vectorized: the table hot path runs through the
dht_probe Pallas kernel, the overflow heap is a jnp append buffer (the
exact structure of §5.3: "the losing thread places the element in the
overflow list by atomically incrementing the next free pointer").

All state is a pytree -> a volume can live sharded on a mesh and the
insert/lookup ops jit/pjit like any other step function.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.kernels import ops

EMPTY = jnp.int32(-1)


class DHTState(NamedTuple):
    table_keys: jnp.ndarray     # [nb, TB] int32
    table_vals: jnp.ndarray     # [nb, TB] int32
    heap_keys: jnp.ndarray      # [H] int32
    heap_vals: jnp.ndarray      # [H] int32
    heap_ptr: jnp.ndarray       # int32 [] next free heap slot


class BatchedDHT:
    def __init__(self, nb: int = 16, TB: int = 256, heap: int = 4096,
                 interpret: bool = False):
        self.nb, self.TB, self.heap = nb, TB, heap
        self.interpret = interpret

    def init(self) -> DHTState:
        return DHTState(
            table_keys=jnp.full((self.nb, self.TB), EMPTY, jnp.int32),
            table_vals=jnp.full((self.nb, self.TB), EMPTY, jnp.int32),
            heap_keys=jnp.full((self.heap,), EMPTY, jnp.int32),
            heap_vals=jnp.full((self.heap,), EMPTY, jnp.int32),
            heap_ptr=jnp.zeros((), jnp.int32))

    def insert(self, st: DHTState, keys, vals
               ) -> Tuple[DHTState, jnp.ndarray]:
        """Insert a batch of distinct keys (>0). Returns (state, status):
        0 inserted, 1 updated, 2 went to the overflow heap."""
        tk, tv, status = ops.dht_insert(st.table_keys, st.table_vals,
                                        keys, vals,
                                        interpret=self.interpret)
        # Overflow path: FAO on the heap pointer -> contiguous slots.
        over = status == 2
        pos = jnp.cumsum(over.astype(jnp.int32)) - 1
        slot = jnp.where(over, st.heap_ptr + pos, self.heap)
        hk = jnp.concatenate([st.heap_keys, jnp.zeros((1,), jnp.int32)])
        hv = jnp.concatenate([st.heap_vals, jnp.zeros((1,), jnp.int32)])
        hk = hk.at[slot].set(keys)[: self.heap]
        hv = hv.at[slot].set(vals)[: self.heap]
        new_ptr = st.heap_ptr + jnp.sum(over.astype(jnp.int32))
        return DHTState(tk, tv, hk, hv, new_ptr), status

    def lookup(self, st: DHTState, keys) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (vals, found). Table hit via the kernel; misses scan
        the heap with one dense equality contraction."""
        vals, hit = ops.dht_lookup(st.table_keys, st.table_vals, keys,
                                   interpret=self.interpret)
        eq = st.heap_keys[None, :] == keys[:, None]        # [K, H]
        heap_hit = jnp.any(eq, axis=1)
        heap_val = jnp.max(jnp.where(eq, st.heap_vals[None, :], EMPTY),
                           axis=1)
        found = hit | heap_hit
        out = jnp.where(hit, vals, jnp.where(heap_hit, heap_val, EMPTY))
        return out, found
