"""DHT access programs for the lock simulator (paper §5.3).

Models the paper's benchmark: P-1 processes fire inserts/reads at one
selected process's local volume. Three synchronization variants:

  * foMPI-A  -- no lock: per the paper it "only synchronizes accesses
    with CAS/FAO", so EVERY access (read or insert) is a remote atomic
    on the victim volume. RDMA atomics serialize in the target NIC's
    atomic unit; we model that with a single designated occupancy word
    (nic proxy) that all of the volume's atomics pass through. Inserts
    additionally take the overflow path (FAO heap pointer + Put +
    second CAS for the last-element pointer, §5.3) on a collision.
  * foMPI-RW / RMA-RW -- the whole volume is protected by the lock;
    the CS performs the single table access (cs_kind=1 semantics:
    plain Gets/Puts stream at line rate, no atomic-unit serialization).

This module provides the foMPI-A program; the lock-protected variants
reuse the standard lock programs with cs_kind=1 (benchmarks/dht_bench).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import (Env, SimState, finish_instr, handler_table,
                               memoized_build, think_duration)
from repro.core.programs.meta import SEG_SCRATCH, ProgramMeta

A_OP, A_OVERFLOW, A_DONE, A_CHAIN = 0, 1, 2, 3
PC_NAMES = ("A_OP", "A_OVERFLOW", "A_DONE", "A_CHAIN")

# The paper's benchmark operates the table at a high load factor (random
# keys into a fixed-size table), so roughly half of the accesses touch
# an overflow chain: inserts take the heap path, reads walk one chain
# link (an extra remote atomic read under CAS/FAO-only consistency).
COLLISION_RATE = 0.5        # inserts hitting an occupied slot
READ_CHAIN_RATE = 0.5       # reads that traverse one overflow link


class FompiADHT:
    """Lock-free CAS/FAO DHT access (the paper's foMPI-A variant).

    `table_words`: window word indices of the victim volume's table;
    `heap_word`: the overflow heap's next-free pointer.
    """

    n_regs = 2

    def __init__(self, table_words, heap_word: int, writer_mask):
        self.table_words = jnp.asarray(table_words, jnp.int32)
        self.heap_word = int(heap_word)
        self.writer_mask = writer_mask
        self._cache = {}

    def init_pc(self, env: Env):
        import numpy as np
        return np.zeros(env.P, np.int32)

    def init_regs(self, env: Env):
        import numpy as np
        return np.zeros((env.P, self.n_regs), np.int32)

    def meta(self, env: Env) -> ProgramMeta:
        """Declared program shape for `repro.analysis` (locklint).

        The table/heap words live in the window's scratch region (see
        benchmarks/dht_bench.py), so SEG_SCRATCH is the allowed segment.
        There is no critical section: foMPI-A is the lock-free variant.
        """
        import numpy as np
        writers = np.asarray(self.writer_mask)
        dead = set()
        if not writers.any():
            dead.add(A_OVERFLOW)
        if writers.all():
            dead.add(A_CHAIN)
        return ProgramMeta(
            name="fompi_a_dht", n_pcs=4, n_regs=self.n_regs,
            pc_names=PC_NAMES,
            dead_pcs=frozenset(dead),
            cs_enter_pcs=frozenset(),
            cs_exit_pcs=frozenset(),
            done_pcs=frozenset({A_DONE}),
            blocking_pcs=frozenset(),
            segments=(SEG_SCRATCH,))

    def build(self, env: Env):
        return memoized_build(self._cache, env, self._build)

    def _build(self, env: Env):
        table = self.table_words
        HW = self.heap_word
        n_slots = table.shape[0]
        is_writer = jnp.asarray(self.writer_mask)

        nic = table[0]          # occupancy proxy: the victim NIC's atomic unit

        def a_op(p, now, key, st: SimState):
            k1, k2 = jax.random.split(key)
            slot = table[jax.random.randint(k1, (), 0, n_slots)]
            w = is_writer[p]
            # Both reads and inserts are remote atomics (CAS/FAO-only
            # synchronization); they serialize at the target's atomic unit.
            r = jax.random.uniform(k2, ())
            chain_read = (~w) & (r < READ_CHAIN_RATE)
            dur = env.lat_atomic(p, slot)
            collide = w & (r < COLLISION_RATE)
            nxt = jnp.where(collide, A_OVERFLOW,
                            jnp.where(chain_read, A_CHAIN, A_DONE))
            return finish_instr(
                env, st, p, now, key, dur=dur, hot_word=nic,
                writes=[jnp.where(w, slot, -1)], next_pc=nxt,
                regs_row=st.regs[p])

        def a_chain(p, now, key, st: SimState):
            # Second atomic read for the overflow-chain link: its own
            # serialized slot in the target NIC's atomic unit.
            dur = env.lat_atomic(p, nic)
            return finish_instr(env, st, p, now, key, dur=dur, hot_word=nic,
                                writes=[], next_pc=A_DONE,
                                regs_row=st.regs[p])

        def a_overflow(p, now, key, st: SimState):
            # FAO on the heap pointer + Put of the element + second CAS
            # updating the last-element pointer (paper §5.3).
            dur = (2.0 * env.lat_atomic(p, HW) + env.lat_plain(p, HW))
            return finish_instr(env, st, p, now, key, dur=dur, hot_word=nic,
                                writes=[HW], next_pc=A_DONE,
                                regs_row=st.regs[p])

        def a_done(p, now, key, st: SimState):
            cnt = st.acq_count[p] + 1
            st = st._replace(acq_count=st.acq_count.at[p].set(cnt),
                             done=st.done.at[p].set(cnt >= env.target_acq))

            return finish_instr(env, st, p, now, key,
                                dur=think_duration(env, key), hot_word=-1,
                                writes=[], next_pc=A_OP,
                                regs_row=st.regs[p], stamp_attempt=True)

        return handler_table((a_op, a_overflow, a_done, a_chain), PC_NAMES,
                             env)
