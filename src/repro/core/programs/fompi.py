"""State-of-the-art comparison targets from foMPI (Gerstenberger et al.,
SC'13), the paper's §5 baselines.

  * foMPI-Spin — a simple CAS spin lock over one global word (mutual
    exclusion only). Topology-oblivious, centralized: contention at the
    lock word is what limits it at scale (paper §5.1).
  * foMPI-RW   — a centralized reader-writer lock: a shared reader
    counter plus a writer flag, both on one rank. Readers FAO the
    counter then verify the flag; writers CAS the flag then wait for the
    counter to drain.

Both use the same simulator/cost model as the proposed locks, so the
comparison isolates protocol design (as in the paper). The baselines
live entirely in the window's scratch region and are addressed through
`env.scratch_w` SLOTS, never absolute word indices: absolute positions
shift with counter padding (shape-stable T_DC layouts), so routing them
through the env is what lets the baselines join one-dispatch
`Session.grid` / `sweep("T_DC", ...)` scans bitwise-identically to
fresh per-point sessions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import (Env, SimState, cs_duration, cs_enter,
                               cs_exit, finish_instr, handler_table,
                               leases_expired, memoized_build, think_duration)
from repro.core.programs.meta import SEG_SCRATCH, ProgramMeta

_NOOP = jnp.int32(-1)

# foMPI-Spin PCs.
S_TRY, S_CS, S_REL, S_DONE, S_REC = 0, 1, 2, 3, 4
SPIN_PC_NAMES = ("S_TRY", "S_CS", "S_REL", "S_DONE", "S_REC")
# foMPI-RW PCs.
W_TRY, W_WAITR, W_CS, W_REL, W_DONE = 0, 1, 2, 3, 4
R_INC, R_CHECK, R_UNDO, R_CS, R_REL, R_DONE = 5, 6, 7, 8, 9, 10
W_REC, R_REC, W_DRAIN = 11, 12, 13
RW_PC_NAMES = ("W_TRY", "W_WAITR", "W_CS", "W_REL", "W_DONE",
               "R_INC", "R_CHECK", "R_UNDO", "R_CS", "R_REL",
               "R_DONE", "W_REC", "R_REC", "W_DRAIN")

# Crash recovery (lease-based): lock words store the OWNER'S id (p+1)
# instead of a bare 1, so a waiter that finds the word held can ask the
# lease oracle (`engine.leases_expired`) whether that exact owner is
# dead — no guessing, no false reclaims of a live holder's lock. The
# reclaim itself is a separate recovery instruction that re-validates
# the guard atomically (instructions are atomic in the engine), so a
# release or a competing reclaim racing the detection just sends the
# recoverer back to its spin loop (bounded retry, counted in
# Metrics.recovery_retries).


def _owner_dead(env: Env, st: SimState, now, word_val):
    """True iff `word_val` encodes an owner id whose lease expired."""
    expired = leases_expired(env, st, now)
    owner = jnp.clip(word_val - 1, 0, env.P - 1)
    return (word_val > 0) & expired[owner]


class FompiSpin:
    """CAS spin lock on scratch slot `lock_slot`."""

    n_regs = 2

    def __init__(self, lock_slot: int = 0):
        self.lock_slot = int(lock_slot)
        self._cache = {}

    def init_pc(self, env: Env):
        import numpy as np
        return np.zeros(env.P, np.int32)

    def init_regs(self, env: Env):
        import numpy as np
        return np.zeros((env.P, self.n_regs), np.int32)

    def meta(self, env: Env) -> ProgramMeta:
        """Declared program shape for `repro.analysis` (locklint)."""
        return ProgramMeta(
            name="fompi_spin", n_pcs=5, n_regs=self.n_regs,
            pc_names=SPIN_PC_NAMES,
            dead_pcs=frozenset(),
            cs_enter_pcs=frozenset({S_CS}),
            cs_exit_pcs=frozenset({S_REL}),
            done_pcs=frozenset({S_DONE}),
            blocking_pcs=frozenset({S_TRY}),
            segments=(SEG_SCRATCH,),
            scratch_slots=(self.lock_slot,),
            recovery_pcs=frozenset({S_REC}))

    def build(self, env: Env):
        return memoized_build(self._cache, env, self._build)

    def _build(self, env: Env):
        LW = env.scratch_w[self.lock_slot]

        def s_try(p, now, key, st: SimState):
            cur = st.window[LW]
            # cur == p+1 happens only after this process crashed holding
            # the lock and revived before anyone reclaimed it: safe to
            # re-take (no other process ever writes p's id).
            got = (cur == 0) | (cur == p + 1)
            dead = _owner_dead(env, st, now, cur) & ~got
            win = st.window.at[LW].set(jnp.where(got, p + 1, cur))
            nxt = jnp.where(got, S_CS, jnp.where(dead, S_REC, S_TRY))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, LW), hot_word=LW,
                                writes=[LW], next_pc=nxt,
                                regs_row=st.regs[p], window=win,
                                block_a=jnp.where(got | dead, _NOOP, LW))

        def s_cs(p, now, key, st: SimState):
            k1, k2 = jax.random.split(key)
            st = cs_enter(env, st, p, now)
            return finish_instr(env, st, p, now, k1,
                                reset_backoff=True,
                                dur=cs_duration(env, k2, p), hot_word=-1,
                                writes=[], next_pc=S_REL, regs_row=st.regs[p])

        def s_rel(p, now, key, st: SimState):
            st = cs_exit(env, st, p)
            win = st.window.at[LW].set(0)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, LW), hot_word=LW,
                                writes=[LW], next_pc=S_DONE,
                                regs_row=st.regs[p], window=win)

        def s_done(p, now, key, st: SimState):
            cnt = st.acq_count[p] + 1
            st = st._replace(acq_count=st.acq_count.at[p].set(cnt),
                             done=st.done.at[p].set(cnt >= env.target_acq))

            return finish_instr(env, st, p, now, key,
                                dur=think_duration(env, key), hot_word=-1,
                                writes=[], next_pc=S_TRY,
                                regs_row=st.regs[p], stamp_attempt=True)

        def s_rec(p, now, key, st: SimState):
            # Atomic re-validate + reclaim: CAS(dead-owner-id -> p+1).
            # The guard can evaporate between detection and this
            # instruction (release raced the lease expiry, or another
            # recoverer won); then fall back to the spin loop.
            cur = st.window[LW]
            dead = _owner_dead(env, st, now, cur)
            win = st.window.at[LW].set(jnp.where(dead, p + 1, cur))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, LW), hot_word=LW,
                                writes=[LW],
                                next_pc=jnp.where(dead, S_CS, S_TRY),
                                regs_row=st.regs[p], window=win,
                                reclaimed=dead, retried=~dead)

        return handler_table((s_try, s_cs, s_rel, s_done, s_rec),
                             SPIN_PC_NAMES, env)


class FompiRW:
    """Centralized reader-writer lock: RCNT + WFLAG scratch slots."""

    n_regs = 2

    def __init__(self, rcnt_slot: int = 0, wflag_slot: int = 1):
        self.rcnt_slot = int(rcnt_slot)
        self.wflag_slot = int(wflag_slot)
        self._cache = {}

    def init_pc(self, env: Env):
        import numpy as np
        pc = np.full(env.P, R_INC, np.int32)
        pc[np.asarray(env.is_writer)] = W_TRY
        return pc

    def init_regs(self, env: Env):
        import numpy as np
        return np.zeros((env.P, self.n_regs), np.int32)

    def meta(self, env: Env) -> ProgramMeta:
        """Declared program shape for `repro.analysis` (locklint)."""
        import numpy as np
        writers = np.asarray(env.is_writer)
        dead = set()
        if not writers.any():
            dead |= {W_TRY, W_WAITR, W_CS, W_REL, W_DONE, W_REC, W_DRAIN}
        if writers.all():
            dead |= {R_INC, R_CHECK, R_UNDO, R_CS, R_REL, R_DONE, R_REC}
        return ProgramMeta(
            name="fompi_rw", n_pcs=14, n_regs=self.n_regs,
            pc_names=RW_PC_NAMES,
            dead_pcs=frozenset(dead),
            cs_enter_pcs=frozenset({W_CS, R_CS}),
            cs_exit_pcs=frozenset({W_REL, R_REL}),
            done_pcs=frozenset({W_DONE, R_DONE}),
            blocking_pcs=frozenset({W_TRY, W_WAITR, R_UNDO}),
            segments=(SEG_SCRATCH,),
            scratch_slots=(self.rcnt_slot, self.wflag_slot),
            recovery_pcs=frozenset({W_REC, R_REC, W_DRAIN} - dead))

    def build(self, env: Env):
        return memoized_build(self._cache, env, self._build)

    def _build(self, env: Env):
        RC = env.scratch_w[self.rcnt_slot]
        WF = env.scratch_w[self.wflag_slot]

        def _readers_quiescent(st: SimState, now):
            # True iff no live reader can hold an arrival on RC: every
            # reader is done, lease-expired, or at a pc with no
            # outstanding arrival (R_INC is pre-FAO, R_DONE is past the
            # release). Residual RC then belongs to dead readers only.
            expired = leases_expired(env, st, now)
            free = (st.pc == R_INC) | (st.pc == R_DONE)
            return jnp.all(env.is_writer | st.done | expired | free)

        def w_try(p, now, key, st: SimState):
            cur = st.window[WF]
            # cur == p+1: this writer crashed holding the flag and
            # revived before any reclaim — safe to re-take.
            got = (cur == 0) | (cur == p + 1)
            dead = _owner_dead(env, st, now, cur) & ~got
            win = st.window.at[WF].set(jnp.where(got, p + 1, cur))
            nxt = jnp.where(got, W_WAITR, jnp.where(dead, W_REC, W_TRY))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, WF), hot_word=WF,
                                writes=[WF], next_pc=nxt,
                                regs_row=st.regs[p], window=win,
                                block_a=jnp.where(got | dead, _NOOP, WF))

        def w_waitr(p, now, key, st: SimState):
            r = st.window[RC]
            drained = r == 0
            stale = ~drained & _readers_quiescent(st, now)
            nxt = jnp.where(drained, W_CS,
                            jnp.where(stale, W_DRAIN, W_WAITR))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, RC), hot_word=-1,
                                writes=[],
                                next_pc=nxt,
                                regs_row=st.regs[p],
                                block_a=jnp.where(drained | stale,
                                                  _NOOP, RC))

        def w_cs(p, now, key, st: SimState):
            k1, k2 = jax.random.split(key)
            st = cs_enter(env, st, p, now)
            return finish_instr(env, st, p, now, k1,
                                reset_backoff=True,
                                dur=cs_duration(env, k2, p), hot_word=-1,
                                writes=[], next_pc=W_REL, regs_row=st.regs[p])

        def w_rel(p, now, key, st: SimState):
            st = cs_exit(env, st, p)
            win = st.window.at[WF].set(0)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, WF), hot_word=WF,
                                writes=[WF], next_pc=W_DONE,
                                regs_row=st.regs[p], window=win)

        def w_done(p, now, key, st: SimState):
            cnt = st.acq_count[p] + 1
            st = st._replace(acq_count=st.acq_count.at[p].set(cnt),
                             done=st.done.at[p].set(cnt >= env.target_acq))

            return finish_instr(env, st, p, now, key,
                                dur=think_duration(env, key), hot_word=-1,
                                writes=[], next_pc=W_TRY,
                                regs_row=st.regs[p], stamp_attempt=True)

        def r_inc(p, now, key, st: SimState):
            win = st.window.at[RC].add(1)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, RC), hot_word=RC,
                                writes=[RC], next_pc=R_CHECK,
                                regs_row=st.regs[p], window=win)

        def r_check(p, now, key, st: SimState):
            f = st.window[WF]
            dead = _owner_dead(env, st, now, f)
            nxt = jnp.where(f == 0, R_CS, jnp.where(dead, R_REC, R_UNDO))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, WF), hot_word=-1,
                                writes=[], next_pc=nxt,
                                regs_row=st.regs[p])

        def r_undo(p, now, key, st: SimState):
            win = st.window.at[RC].add(-1)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, RC), hot_word=RC,
                                writes=[RC], next_pc=R_INC,
                                regs_row=st.regs[p], window=win,
                                block_a=WF)

        def r_cs(p, now, key, st: SimState):
            k1, k2 = jax.random.split(key)
            st = cs_enter(env, st, p, now)
            return finish_instr(env, st, p, now, k1,
                                reset_backoff=True,
                                dur=cs_duration(env, k2, p), hot_word=-1,
                                writes=[], next_pc=R_REL, regs_row=st.regs[p])

        def r_rel(p, now, key, st: SimState):
            st = cs_exit(env, st, p)
            win = st.window.at[RC].add(-1)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, RC), hot_word=RC,
                                writes=[RC], next_pc=R_DONE,
                                regs_row=st.regs[p], window=win)

        def r_done(p, now, key, st: SimState):
            cnt = st.acq_count[p] + 1
            st = st._replace(acq_count=st.acq_count.at[p].set(cnt),
                             done=st.done.at[p].set(cnt >= env.target_acq))

            return finish_instr(env, st, p, now, key,
                                dur=think_duration(env, key), hot_word=-1,
                                writes=[], next_pc=R_INC,
                                regs_row=st.regs[p], stamp_attempt=True)

        def w_rec(p, now, key, st: SimState):
            # Atomic re-validate + steal the dead writer's flag.
            cur = st.window[WF]
            dead = _owner_dead(env, st, now, cur)
            win = st.window.at[WF].set(jnp.where(dead, p + 1, cur))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, WF), hot_word=WF,
                                writes=[WF],
                                next_pc=jnp.where(dead, W_WAITR, W_TRY),
                                regs_row=st.regs[p], window=win,
                                reclaimed=dead, retried=~dead)

        def r_rec(p, now, key, st: SimState):
            # Reader clears a dead writer's flag; its own arrival is
            # still counted in RC, so on re-check it proceeds to CS
            # (unless a live writer snatches the freed flag first).
            cur = st.window[WF]
            dead = _owner_dead(env, st, now, cur)
            win = st.window.at[WF].set(jnp.where(dead, 0, cur))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, WF), hot_word=WF,
                                writes=[WF], next_pc=R_CHECK,
                                regs_row=st.regs[p], window=win,
                                reclaimed=dead, retried=~dead)

        def w_drain(p, now, key, st: SimState):
            # Atomic re-validate + zero the reader counter: every
            # residual arrival provably belongs to a lease-expired
            # reader (the quiescence guard), i.e. a reader that died
            # between its FAO(+1) and its departing FAO(-1).
            r = st.window[RC]
            stale = (r != 0) & _readers_quiescent(st, now)
            drained = r == 0
            win = st.window.at[RC].set(jnp.where(stale, 0, r))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, RC), hot_word=RC,
                                writes=[RC],
                                next_pc=jnp.where(stale | drained,
                                                  W_CS, W_WAITR),
                                regs_row=st.regs[p], window=win,
                                reclaimed=stale,
                                retried=~(stale | drained))

        return handler_table((w_try, w_waitr, w_cs, w_rel, w_done,
                              r_inc, r_check, r_undo, r_cs, r_rel, r_done,
                              w_rec, r_rec, w_drain), RW_PC_NAMES, env)
