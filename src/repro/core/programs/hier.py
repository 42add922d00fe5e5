"""The paper's lock protocols as simulator instruction programs.

One unified program implements the whole family (§3 of the paper):

  * RMA-RW   — has_readers=True, N >= 1 levels (DQ + DT + DC).
  * RMA-MCS  — has_readers=False, N >= 2 (DQ + DT, no DC; §3.5).
  * D-MCS    — has_readers=False, N == 1 (single root queue; §2.4).

Program counters follow the paper's listings (4, 5, 7, 8, 9, 10 and the
counter helpers of Listing 6); comments cite them. Levels are 0-based
here with 0 = root (paper's level 1) and N-1 = leaf (paper's level N).

Queue entities at level i < N-1 are per-element nodes (HMCS-style
completion of the abbreviated listings — DESIGN.md §2): `ent_of_p[i, p]`
is the entity that p acts as at level i, and exclusivity of element-node
use follows from p only acting at level i-1 while holding level i.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.engine import Env, SimState, cs_duration, cs_enter, cs_exit, finish_instr, think_duration
from repro.core.programs.meta import (SEG_COUNTERS, SEG_QUEUES,
                                      ProgramMeta)
from repro.core.window import (ACQUIRE_PARENT, ACQUIRE_START, MODE_CHANGE,
                               NULL, WAIT, WRITE_FLAG)

# Registers.
L = 0          # current level during acquire/release descent
PRED = 1
STATUS = 2
NEXT_STAT = 3
CRESET = 4     # counters_reset flag (Listing 8)
K = 5          # counter-loop index (Listing 6 loops)
UL = 6         # unwind level during release
SUCC0 = 7      # SUCC0+lvl: successor observed at level lvl (max 4 levels)
BARRIER = 11   # reader barrier flag (Listing 9)
RET = 12       # reader FAO result
TMP = 13       # return-pc for the shared reset-counters loop
N_REGS = 16

# Writer PCs.
WA_PREP, WA_ENQ, WA_LINK, WA_SPIN, WA_START_PARENT = 0, 1, 2, 3, 4
W_SCTW_FLAG, W_SCTW_VERIFY = 5, 6
# (7 merged into WA_START_PARENT)
CS, WR_READ, WR_DECIDE = 8, 9, 10
ROOT_DECIDE, ROOT_RESET, ROOT_CAS, ROOT_WAITSUCC, ROOT_PASS = 11, 12, 13, 14, 15
UNW_CHECK, UNW_WAIT, UNW_PUT = 16, 17, 18
ROOT_GETSUCC = 19
DONE_ONE = 20
# Reader PCs (Listing 9/10).
R_BARRIER, R_FAO, R_CHECK_TAIL, R_BACKOFF, R_CS, R_RELEASE, R_RESET, R_DONE = (
    21, 22, 23, 24, 25, 26, 27, 28)
# Barred-reader recovery (see r_recover): reset the counter when the
# last writer departed after the reader passed R_CHECK_TAIL. Found by
# the repro.analysis model checker (a barred reader could starve).
R_RECOVER = 29
# Crash recovery (lease-based; engine.FaultPlan). All four re-validate
# their guard atomically before repairing, so a release or a competing
# recoverer racing the detection costs a bounded retry, never a wrong
# repair.
REC_INHERIT = 30   # waiter whose predecessor's node is abandoned
REC_TAILFIX = 31   # releaser whose successor died between FAO and link
REC_DRAIN = 32     # writer draining arrivals of dead readers
R_UNBAR = 33       # barred reader clearing dead writers' flags
N_PCS = 34

PC_NAMES = (
    "WA_PREP", "WA_ENQ", "WA_LINK", "WA_SPIN", "WA_START_PARENT",
    "W_SCTW_FLAG", "W_SCTW_VERIFY", "TRAP7", "CS", "WR_READ",
    "WR_DECIDE", "ROOT_DECIDE", "ROOT_RESET", "ROOT_CAS",
    "ROOT_WAITSUCC", "ROOT_PASS", "UNW_CHECK", "UNW_WAIT", "UNW_PUT",
    "ROOT_GETSUCC", "DONE_ONE", "R_BARRIER", "R_FAO", "R_CHECK_TAIL",
    "R_BACKOFF", "R_CS", "R_RELEASE", "R_RESET", "R_DONE", "R_RECOVER",
    "REC_INHERIT", "REC_TAILFIX", "REC_DRAIN", "R_UNBAR")

_NOOP = jnp.int32(-1)


class HierProgram:
    """RMA-RW / RMA-MCS / D-MCS instruction program."""

    n_regs = N_REGS

    def __init__(self, has_readers: bool):
        self.has_readers = has_readers
        self._cache = {}

    def init_pc(self, env: Env):
        import numpy as np
        pc = np.zeros(env.P, np.int32)
        if self.has_readers:
            pc[~np.asarray(env.is_writer)] = R_BARRIER
        return pc

    def init_regs(self, env: Env):
        import numpy as np
        regs = np.zeros((env.P, N_REGS), np.int32)
        regs[:, L] = env.N - 1
        return regs

    def meta(self, env: Env) -> ProgramMeta:
        """Declared program shape for `repro.analysis` (locklint)."""
        Nlv = int(env.N)
        dead = {7}                      # merged into WA_START_PARENT
        if self.has_readers:
            segments = (SEG_QUEUES, SEG_COUNTERS)
        else:
            segments = (SEG_QUEUES,)
            # Reader and hand-to-readers instructions exist in the
            # handler table but are never routed to.
            dead |= {W_SCTW_FLAG, W_SCTW_VERIFY, ROOT_RESET,
                     R_BARRIER, R_FAO, R_CHECK_TAIL, R_BACKOFF, R_CS,
                     R_RELEASE, R_RESET, R_DONE, R_RECOVER}
        if not self.has_readers:
            # Counter recovery only exists for the RW variant.
            dead |= {REC_DRAIN, R_UNBAR}
        if Nlv == 1:
            # Single root queue: no per-level descent, and the unwind
            # above the release floor is empty (UNW_CHECK finishes
            # immediately), so the late-successor pcs cannot run.
            dead |= {WR_READ, WR_DECIDE, UNW_WAIT, UNW_PUT}
        return ProgramMeta(
            name="rma_rw" if self.has_readers else
                 ("d_mcs" if Nlv == 1 else "rma_mcs"),
            n_pcs=N_PCS, n_regs=N_REGS, pc_names=PC_NAMES,
            dead_pcs=frozenset(dead),
            cs_enter_pcs=frozenset({CS, R_CS}),
            cs_exit_pcs=frozenset(
                {ROOT_DECIDE if Nlv == 1 else WR_READ, R_RELEASE}),
            done_pcs=frozenset({DONE_ONE, R_DONE}),
            blocking_pcs=frozenset({WA_SPIN, W_SCTW_VERIFY,
                                    ROOT_WAITSUCC, UNW_WAIT, R_BARRIER,
                                    REC_INHERIT, REC_TAILFIX}),
            segments=segments,
            recovery_pcs=frozenset(
                {REC_INHERIT, REC_TAILFIX, REC_DRAIN, R_UNBAR} - dead))

    # -- helpers -------------------------------------------------------
    def build(self, env: Env):
        return engine.memoized_build(self._cache, env, self._build)

    def _build(self, env: Env):
        RW = self.has_readers
        Nlv = env.N

        def ent(r, lvl, p):
            return env.ent_of_p[lvl, p]

        def nw(lvl, e):       # NEXT word of entity e at level lvl
            return env.next_t[lvl, e]

        def sw(lvl, e):       # STATUS word
            return env.status_t[lvl, e]

        def tw(lvl, p):       # TAIL word of p's element at level lvl
            return env.tail_t[lvl, env.elem_of_p[lvl, p]]

        # ---- crash-recovery guards (lease registry reads) -----------
        # These read other processes' frozen pc/regs as well as window
        # words: the lease service that detects a crash is assumed to
        # also publish each process's last announced protocol phase
        # (one extra word per process on its home node), so survivors
        # can reason about exactly where the dead stopped. All guards
        # are monotone in "more processes expired", and every repair
        # re-validates its guard in the same atomic step, so a race
        # with a live release costs a bounded retry, never a bad write.
        def expired(st, now):
            return engine.leases_expired(env, st, now)

        def entity_dead(st, now, lvl, e, p):
            """Every process that can act as entity `e` at `lvl` (other
            than p itself) is lease-expired or done, and at least one is
            expired — so the node is abandoned (nobody left to release
            it) rather than merely idle or crash-free. Only writers ever
            enqueue or release an element's queue node, so in the RW
            variant a live reader of the entity does not keep it alive."""
            qs = jnp.arange(env.P)
            exp = expired(st, now)
            sel = (env.ent_of_p[lvl] == e) & (qs != p)
            if RW:
                sel = sel & env.is_writer
            return jnp.any(sel & exp) & jnp.all(~sel | exp | st.done)

        def no_live_enqueuer(st, now, lvl, p):
            """No live process sits between its tail-FAO and its link at
            this level. Repairs that reroute the queue must wait for
            such a process: it holds a queue position that is not yet
            visible in any NEXT word."""
            qs = jnp.arange(env.P)
            mid = ((st.pc == WA_LINK) & (st.regs[:, L] == lvl)
                   & ~st.crashed & ~st.done & (qs != p))
            return ~jnp.any(mid)

        def ghost_succ(st, now, lvl, e, p):
            """The expired process that crashed between its tail-FAO
            and its link at `lvl` with PRED == e — p's invisible
            immediate successor. Returns (found, its entity, that
            entity's NEXT word value)."""
            qs = jnp.arange(env.P)
            cand = (expired(st, now) & (st.pc == WA_LINK)
                    & (st.regs[:, L] == lvl)
                    & (st.regs[:, PRED] == e) & (qs != p))
            d = jnp.argmax(cand)
            e_d = env.ent_of_p[lvl, d]
            return jnp.any(cand), e_d, st.window[nw(lvl, e_d)]

        def ctr_quiescent(st, now, c):
            """No live reader assigned to counter `c` holds an
            unmatched arrival: every one is done, lease-expired, or
            frozen at a pc whose arrive/depart contributions balance."""
            free = ((st.pc == R_BARRIER) | (st.pc == R_FAO)
                    | (st.pc == R_DONE) | (st.pc == R_RECOVER)
                    | (st.pc == R_UNBAR))
            rdr = (~env.is_writer) & (env.ctr_of_p == c)
            return jnp.all(~rdr | st.done | expired(st, now) | free)

        # ---- writer instructions ------------------------------------
        def wa_prep(p, now, key, st: SimState):
            """Listing 4/7 lines 2-3: reset own NEXT, STATUS at level L."""
            r = st.regs[p]
            lvl = r[L]
            e = ent(r, lvl, p)
            win = st.window.at[nw(lvl, e)].set(NULL).at[sw(lvl, e)].set(WAIT)
            dur = 2.0 * env.lat_plain(p, sw(lvl, e))
            return finish_instr(env, st, p, now, key, dur=dur, hot_word=-1,
                                writes=[], next_pc=WA_ENQ, regs_row=r,
                                window=win)

        def wa_enq(p, now, key, st: SimState):
            """Listing 4/7: FAO(p, tail, REPLACE) — enqueue; branch on pred."""
            r = st.regs[p]
            lvl = r[L]
            e = ent(r, lvl, p)
            t = tw(lvl, p)
            pred = st.window[t]
            win = st.window.at[t].set(e)
            r = r.at[PRED].set(pred).at[K].set(0)
            # pred == own entity means the tail still held our own node
            # from an abandoned earlier enqueue (a crashed same-subtree
            # process, reclaimed under its lease): level-(lvl+1)
            # exclusivity guarantees no live process can be queued as
            # this entity, so treat it as an empty queue.
            no_pred = (pred == NULL) | (pred == e)
            if RW:
                pc_no_pred = jnp.where(lvl == 0, W_SCTW_FLAG, WA_START_PARENT)
            else:
                pc_no_pred = jnp.where(lvl == 0, WA_START_PARENT, WA_START_PARENT)
            nxt = jnp.where(no_pred, pc_no_pred, WA_LINK)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, t), hot_word=t,
                                writes=[t], next_pc=nxt, regs_row=r, window=win)

        def wa_link(p, now, key, st: SimState):
            """Listing 4 line 8: Put(p, pred, NEXT)."""
            r = st.regs[p]
            lvl = r[L]
            w = nw(lvl, r[PRED])
            win = st.window.at[w].set(ent(r, lvl, p))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[w], next_pc=WA_SPIN, regs_row=r,
                                window=win)

        def wa_spin(p, now, key, st: SimState):
            """Listing 4 lines 10-12 / Listing 7 lines 10-17: local spin."""
            r = st.regs[p]
            lvl = r[L]
            w = sw(lvl, ent(r, lvl, p))
            s = st.window[w]
            r = r.at[STATUS].set(s)
            waiting = s == WAIT
            # Dead-predecessor detection: if every process that can act
            # as our predecessor's node is lease-expired, the grant may
            # never flow here on its own — take over in REC_INHERIT.
            pred_gone = waiting & entity_dead(st, now, lvl, r[PRED], p)
            if RW:
                nxt = jnp.where(
                    waiting, WA_SPIN,
                    jnp.where(s == ACQUIRE_PARENT, WA_START_PARENT,
                              jnp.where((lvl == 0) & (s == MODE_CHANGE),
                                        W_SCTW_FLAG, CS)))
            else:
                nxt = jnp.where(waiting, WA_SPIN,
                                jnp.where(s == ACQUIRE_PARENT,
                                          WA_START_PARENT, CS))
            nxt = jnp.where(pred_gone, REC_INHERIT, nxt)
            block = jnp.where(waiting & ~pred_gone, w, _NOOP)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r,
                                block_a=block)

        def wa_start_parent(p, now, key, st: SimState):
            """Listing 4 line 22 (+ Listing 7 lines 17/22): STATUS :=
            ACQUIRE_START, then climb (or enter CS when at the root)."""
            r = st.regs[p]
            lvl = r[L]
            w = sw(lvl, ent(r, lvl, p))
            win = st.window.at[w].set(ACQUIRE_START)
            at_root = lvl == 0
            r = r.at[L].set(jnp.where(at_root, lvl, lvl - 1))
            nxt = jnp.where(at_root, CS, WA_PREP)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[w], next_pc=nxt, regs_row=r,
                                window=win)

        # Counter loops (Listing 6) are register-K state machines bounded
        # by env.n_ctr — a traced VALUE derived from the counter mask, not
        # a static shape. K only ever indexes live slots (k < n_ctr), so
        # padded counter words stay untouched and one compiled program
        # serves every T_DC point of the machine (shape-stable layouts).
        def w_sctw_flag(p, now, key, st: SimState):
            """Listing 6 set_counters_to_WRITE phase 1: flag counter K."""
            r = st.regs[p]
            k = r[K]
            w = env.arrive_w[k]
            arr = st.window[w]
            # Set-if-unset rather than a blind add: crash recovery may
            # re-run the flagging loop over counters the dead writer
            # already flagged (REC_INHERIT adopting a mid-SCTW
            # continuation), and a double flag can never be drained.
            win = st.window.at[w].set(
                jnp.where(arr >= WRITE_FLAG, arr, arr + WRITE_FLAG))
            last = k + 1 >= env.n_ctr
            r = r.at[K].set(jnp.where(last, 0, k + 1))
            nxt = jnp.where(last, W_SCTW_VERIFY, W_SCTW_FLAG)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, w), hot_word=w,
                                writes=[w], next_pc=nxt, regs_row=r,
                                window=win)

        def w_sctw_verify(p, now, key, st: SimState):
            """§4.1: after flagging all counters, wait until no reader is
            active on counter K (arrived - WRITE_FLAG == departed)."""
            r = st.regs[p]
            k = r[K]
            wa, wd = env.arrive_w[k], env.depart_w[k]
            arr, dep = st.window[wa], st.window[wd]
            clear = (arr - WRITE_FLAG) == dep
            # Dead readers can leave unmatched arrivals that make
            # `clear` unreachable; once every live reader of counter K
            # is provably quiescent, drain them in REC_DRAIN.
            stale = (~clear) & ctr_quiescent(st, now, k)
            last = k + 1 >= env.n_ctr
            r = r.at[K].set(jnp.where(clear & ~last, k + 1,
                                      jnp.where(clear & last, 0, k)))
            nxt = jnp.where(~clear,
                            jnp.where(stale, REC_DRAIN, W_SCTW_VERIFY),
                            jnp.where(last, WA_START_PARENT, W_SCTW_VERIFY))
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, wa), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r,
                                block_a=jnp.where(clear | stale, _NOOP, wa),
                                block_b=jnp.where(clear | stale, _NOOP, wd))

        def cs_instr(p, now, key, st: SimState):
            """Critical section (workload depends on the benchmark)."""
            k1, k2 = jax.random.split(key)
            r = st.regs[p]
            st = cs_enter(env, st, p, now)
            r = r.at[L].set(Nlv - 1).at[UL].set(Nlv)  # reset for release
            nxt = ROOT_DECIDE if Nlv == 1 else WR_READ
            return finish_instr(env, st, p, now, k1,
                                reset_backoff=True,
                                dur=cs_duration(env, k2, p), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r)

        def wr_read(p, now, key, st: SimState):
            """Listing 5 lines 3-4: read succ + status at level L."""
            r = st.regs[p]
            lvl = r[L]
            if Nlv > 1:
                st = jax.lax.cond(lvl == Nlv - 1,
                                  lambda s: cs_exit(env, s, p), lambda s: s, st)
            e = ent(r, lvl, p)
            succ = st.window[nw(lvl, e)]
            stat = st.window[sw(lvl, e)]
            r = r.at[SUCC0 + lvl].set(succ).at[STATUS].set(stat)
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, sw(lvl, e)),
                                hot_word=-1, writes=[], next_pc=WR_DECIDE,
                                regs_row=r)

        def wr_decide(p, now, key, st: SimState):
            """Listing 5 lines 5-12: pass locally within the element, or
            release toward the root."""
            r = st.regs[p]
            lvl = r[L]
            succ = r[SUCC0 + lvl]
            # Never pass into an abandoned node: the grant would be
            # stranded (its owner will never consume it). Descend
            # instead — the unwind passes over the dead node.
            succ_dead = ((succ != NULL)
                         & entity_dead(st, now, lvl,
                                       jnp.maximum(succ, 0), p))
            can_pass = ((succ != NULL) & ~succ_dead
                        & (r[STATUS] < env.T_L[lvl]) & (lvl > 0))
            # Local pass: Put(status+1, succ, STATUS) (Listing 5 line 8).
            w = sw(lvl, succ * jnp.where(succ == NULL, 0, 1))
            win = jnp.where(can_pass,
                            st.window.at[w].set(r[STATUS] + 1), st.window)
            # Else descend: L -= 1; root handled by ROOT_DECIDE.
            r2 = r.at[L].set(jnp.where(can_pass, lvl, lvl - 1))
            r2 = r2.at[UL].set(jnp.where(can_pass, lvl + 1, r[UL]))
            nxt = jnp.where(can_pass, UNW_CHECK,
                            jnp.where(lvl - 1 >= 1, WR_READ, ROOT_DECIDE))
            dur = jnp.where(can_pass, env.lat_plain(p, w), 0.02)
            return finish_instr(env, st, p, now, key, dur=dur, hot_word=-1,
                                writes=[w], next_pc=nxt, regs_row=r2,
                                window=win)

        def root_decide(p, now, key, st: SimState):
            """Listing 8 lines 3-8 (RW) / root release (MCS): read own
            root STATUS; maybe hand the lock to the readers."""
            r = st.regs[p]
            if Nlv == 1:
                st = cs_exit(env, st, p)
            e = ent(r, 0, p)
            stat = st.window[sw(0, e)]
            ns = stat + 1
            r = r.at[STATUS].set(stat).at[NEXT_STAT].set(ns).at[CRESET].set(0)
            if RW:
                hand_readers = ns >= env.T_W
                r = r.at[K].set(0).at[TMP].set(ROOT_GETSUCC)
                nxt = jnp.where(hand_readers, ROOT_RESET, ROOT_GETSUCC)
            else:
                nxt = jnp.asarray(ROOT_GETSUCC, jnp.int32)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, sw(0, e)), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r)

        def root_reset(p, now, key, st: SimState):
            """Listing 6 reset_counters: reset counter K, looping over all
            counters; then NEXT_STAT := MODE_CHANGE (Listing 8 line 7)."""
            r = st.regs[p]
            k = r[K]
            wa, wd = env.arrive_w[k], env.depart_w[k]
            arr, dep = st.window[wa], st.window[wd]
            sub_arr = -dep - jnp.where(arr >= WRITE_FLAG, WRITE_FLAG, 0)
            win = st.window.at[wa].add(sub_arr).at[wd].add(-dep)
            last = k + 1 >= env.n_ctr
            r = r.at[K].set(jnp.where(last, 0, k + 1))
            r = jnp.where(last,
                          r.at[NEXT_STAT].set(MODE_CHANGE).at[CRESET].set(1),
                          r)
            nxt = jnp.where(last, r[TMP], ROOT_RESET)
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, wa)
                                + 2.0 * env.lat_atomic(p, wa),
                                hot_word=wa, writes=[wa, wd], next_pc=nxt,
                                regs_row=r, window=win)

        def root_getsucc(p, now, key, st: SimState):
            """Listing 8 line 9: succ = Get(p, NEXT)."""
            r = st.regs[p]
            e = ent(r, 0, p)
            succ = st.window[nw(0, e)]
            r = r.at[SUCC0 + 0].set(succ)
            # A linked successor whose node is abandoned is passed over
            # in REC_TAILFIX (TMP carries the level), never granted.
            sdead = ((succ != NULL)
                     & entity_dead(st, now, 0, jnp.maximum(succ, 0), p))
            if RW:
                # No successor: hand to readers first if not done yet
                # (Listing 8 lines 10-13).
                need_reset = (succ == NULL) & (r[CRESET] == 0)
                r = r.at[K].set(0).at[TMP].set(
                    jnp.where(sdead, 0, ROOT_CAS))
                nxt = jnp.where(sdead, REC_TAILFIX,
                                jnp.where(succ != NULL, ROOT_PASS,
                                          jnp.where(need_reset,
                                                    ROOT_RESET, ROOT_CAS)))
            else:
                r = r.at[TMP].set(jnp.where(sdead, 0, r[TMP]))
                nxt = jnp.where(sdead, REC_TAILFIX,
                                jnp.where(succ != NULL, ROOT_PASS,
                                          ROOT_CAS))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, nw(0, e)), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r)

        def root_cas(p, now, key, st: SimState):
            """Listing 8 line 15 / Listing 3 line 5: CAS(∅, p, TAIL)."""
            r = st.regs[p]
            e = ent(r, 0, p)
            t = tw(0, p)
            cur = st.window[t]
            ok = cur == e
            win = st.window.at[t].set(jnp.where(ok, NULL, cur))
            r = r.at[UL].set(1)
            nxt = jnp.where(ok, UNW_CHECK, ROOT_WAITSUCC)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, t), hot_word=t,
                                writes=[t], next_pc=nxt, regs_row=r,
                                window=win)

        def root_waitsucc(p, now, key, st: SimState):
            """Listing 8 lines 18-20: wait for the successor to appear."""
            r = st.regs[p]
            e = ent(r, 0, p)
            w = nw(0, e)
            succ = st.window[w]
            r = r.at[SUCC0 + 0].set(succ)
            # Our successor may have died between its tail-FAO and its
            # link: it will never appear in our NEXT word. Identify it
            # through the lease registry and repair in REC_TAILFIX. A
            # successor that DID link but whose node is abandoned is
            # rerouted the same way (pass over it, never grant it).
            found, e_d, dn = ghost_succ(st, now, 0, e, p)
            sdead = ((succ != NULL)
                     & entity_dead(st, now, 0, jnp.maximum(succ, 0), p))
            rec = (sdead
                   | ((succ == NULL) & found
                      & no_live_enqueuer(st, now, 0, p)
                      & ((dn != NULL) | (st.window[tw(0, p)] == e_d))))
            r = r.at[TMP].set(jnp.where(rec, 0, r[TMP]))
            nxt = jnp.where(rec, REC_TAILFIX,
                            jnp.where(succ != NULL, ROOT_PASS,
                                      ROOT_WAITSUCC))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r,
                                block_a=jnp.where((succ == NULL) & ~rec,
                                                  w, _NOOP))

        def root_pass(p, now, key, st: SimState):
            """Listing 8 line 23: Put(next_stat, succ, STATUS)."""
            r = st.regs[p]
            succ = r[SUCC0 + 0]
            w = sw(0, succ)
            win = st.window.at[w].set(r[NEXT_STAT])
            r = r.at[UL].set(1)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[w], next_pc=UNW_CHECK, regs_row=r,
                                window=win)

        def unw_check(p, now, key, st: SimState):
            """Listing 5 lines 13-17 at each level from the release floor
            back to the leaf: clear the tail or find the late successor."""
            r = st.regs[p]
            ul = r[UL]
            fin = ul > Nlv - 1
            ulc = jnp.minimum(ul, Nlv - 1)
            e = ent(r, ulc, p)
            succ = r[SUCC0 + ulc]
            t = tw(ulc, p)
            cur = st.window[t]
            do_cas = (~fin) & (succ == NULL)
            cas_ok = do_cas & (cur == e)
            win = st.window.at[t].set(jnp.where(cas_ok, NULL, cur))
            r = r.at[UL].set(jnp.where(fin | cas_ok, ul + jnp.where(fin, 0, 1), ul))
            # A linked successor whose node is abandoned must be passed
            # over (REC_TAILFIX), never granted: the grant would strand.
            sdead = ((~fin) & (succ != NULL)
                     & entity_dead(st, now, ulc,
                                   jnp.maximum(succ, 0), p))
            r = r.at[TMP].set(jnp.where(sdead, ulc, r[TMP]))
            nxt = jnp.where(fin, DONE_ONE,
                            jnp.where(succ != NULL,
                                      jnp.where(sdead, REC_TAILFIX,
                                                UNW_PUT),
                                      jnp.where(cas_ok, UNW_CHECK,
                                                UNW_WAIT)))
            dur = jnp.where(do_cas, env.lat_atomic(p, t), 0.02)
            return finish_instr(env, st, p, now, key, dur=dur,
                                hot_word=jnp.where(do_cas, t, _NOOP),
                                writes=[t], next_pc=nxt, regs_row=r,
                                window=win)

        def unw_wait(p, now, key, st: SimState):
            """Listing 5 lines 18-20: wait for the late successor."""
            r = st.regs[p]
            ul = jnp.minimum(r[UL], Nlv - 1)
            e = ent(r, ul, p)
            w = nw(ul, e)
            succ = st.window[w]
            r = r.at[SUCC0 + ul].set(succ)
            # Same ghost-successor repair as ROOT_WAITSUCC, one level
            # up the unwind; TMP carries the level to REC_TAILFIX. A
            # successor that DID link but whose node is abandoned is
            # also rerouted there (pass over it, never grant it).
            found, e_d, dn = ghost_succ(st, now, ul, e, p)
            sdead = ((succ != NULL)
                     & entity_dead(st, now, ul,
                                   jnp.maximum(succ, 0), p))
            rec = (sdead
                   | ((succ == NULL) & found
                      & no_live_enqueuer(st, now, ul, p)
                      & ((dn != NULL) | (st.window[tw(ul, p)] == e_d))))
            r = r.at[TMP].set(jnp.where(rec, ul, r[TMP]))
            nxt = jnp.where(rec, REC_TAILFIX,
                            jnp.where(succ == NULL, UNW_WAIT, UNW_PUT))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r,
                                block_a=jnp.where((succ == NULL) & ~rec,
                                                  w, _NOOP))

        def unw_put(p, now, key, st: SimState):
            """Listing 5 line 23: Put(ACQUIRE_PARENT, succ, STATUS)."""
            r = st.regs[p]
            ul = jnp.minimum(r[UL], Nlv - 1)
            succ = r[SUCC0 + ul]
            w = sw(ul, succ)
            win = st.window.at[w].set(ACQUIRE_PARENT)
            r = r.at[UL].set(ul + 1)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, w), hot_word=-1,
                                writes=[w], next_pc=UNW_CHECK, regs_row=r,
                                window=win)

        def done_one(p, now, key, st: SimState):
            r = st.regs[p]
            cnt = st.acq_count[p] + 1
            finished = cnt >= env.target_acq
            r = r.at[L].set(Nlv - 1).at[CRESET].set(0).at[K].set(0)
            st = st._replace(acq_count=st.acq_count.at[p].set(cnt),
                             done=st.done.at[p].set(finished))
            nxt = WA_PREP

            def extra(s, finish):
                return s._replace(t_attempt=s.t_attempt.at[p].set(finish))

            return finish_instr(env, st, p, now, key,
                                dur=think_duration(env, key), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r,
                                extra=extra)

        # ---- reader instructions (Listings 9 / 10) -------------------
        def r_barrier(p, now, key, st: SimState):
            r = st.regs[p]
            wa = env.arrive_w[env.ctr_of_p[p]]
            t = tw(0, p)
            s = st.window[wa]
            over = (r[BARRIER] == 1) & (s >= env.T_R)
            # Starvation recovery (found by the repro.analysis model
            # checker): a barred reader saw a writer in the root tail at
            # R_CHECK_TAIL, so it skipped the self-reset — but if that
            # writer departs for good, nobody resets the counter and the
            # reader waits forever. Re-check the tail while barred and
            # reset the counter ourselves once it drains; watch the tail
            # word too so the departing writer's CAS wakes us.
            cur_tail = st.window[t]
            # Crash recovery takes priority: if every writer is done or
            # lease-expired and no live reader of this counter holds an
            # arrival, the flag/residual can never drain on its own —
            # clear the counter in R_UNBAR.
            exp = expired(st, now)
            unbar = (over & jnp.any(exp)
                     & jnp.all(~env.is_writer | st.done | exp)
                     & ctr_quiescent(st, now, env.ctr_of_p[p]))
            recover = over & ~unbar & (cur_tail == NULL)
            barred = over & ~unbar & ~recover
            nxt = jnp.where(unbar, R_UNBAR,
                            jnp.where(recover, R_RECOVER,
                                      jnp.where(barred, R_BARRIER, R_FAO)))
            dur = jnp.where(r[BARRIER] == 1,
                            env.lat_plain(p, wa) + env.lat_plain(p, t),
                            jnp.float32(0.02))
            return finish_instr(env, st, p, now, key, dur=dur, hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r,
                                block_a=jnp.where(barred, wa, _NOOP),
                                block_b=jnp.where(barred, t, _NOOP))

        def r_fao(p, now, key, st: SimState):
            """Listing 9 line 12: FAO(1, c(p), ARRIVE, SUM)."""
            r = st.regs[p]
            wa = env.arrive_w[env.ctr_of_p[p]]
            ret = st.window[wa]
            win = st.window.at[wa].add(1)
            r = r.at[RET].set(ret)
            got = ret < env.T_R
            first = ret == env.T_R
            r = r.at[BARRIER].set(jnp.where(got, r[BARRIER], 1))
            nxt = jnp.where(got, R_CS, jnp.where(first, R_CHECK_TAIL,
                                                 R_BACKOFF))
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, wa), hot_word=wa,
                                writes=[wa], next_pc=nxt, regs_row=r,
                                window=win)

        def r_check_tail(p, now, key, st: SimState):
            """Listing 9 lines 15-21: first to reach T_R checks for
            waiting writers at the root tail."""
            r = st.regs[p]
            t = tw(0, p)
            cur = st.window[t]
            nxt = jnp.where(cur == NULL, R_RESET, R_BACKOFF)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_plain(p, t), hot_word=-1,
                                writes=[], next_pc=nxt, regs_row=r)

        def r_backoff(p, now, key, st: SimState):
            """Listing 9 line 24: Accumulate(-1, c(p), ARRIVE)."""
            r = st.regs[p]
            wa = env.arrive_w[env.ctr_of_p[p]]
            win = st.window.at[wa].add(-1)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, wa), hot_word=wa,
                                writes=[wa], next_pc=R_BARRIER, regs_row=r,
                                window=win)

        def r_cs(p, now, key, st: SimState):
            k1, k2 = jax.random.split(key)
            r = st.regs[p]
            st = cs_enter(env, st, p, now)
            return finish_instr(env, st, p, now, k1,
                                reset_backoff=True,
                                dur=cs_duration(env, k2, p), hot_word=-1,
                                writes=[], next_pc=R_RELEASE, regs_row=r)

        def r_release(p, now, key, st: SimState):
            """Listing 10: Accumulate(1, c(p), DEPART)."""
            r = st.regs[p]
            wd = env.depart_w[env.ctr_of_p[p]]
            win = st.window.at[wd].add(1)
            st = cs_exit(env, st, p)
            return finish_instr(env, st, p, now, key,
                                dur=env.lat_atomic(p, wd), hot_word=wd,
                                writes=[wd], next_pc=R_DONE, regs_row=r,
                                window=win)

        def r_reset(p, now, key, st: SimState):
            """Listing 9 line 20: reset own counter; clear barrier.

            Only the departed readers are subtracted — the writer's
            WRITE_FLAG (if one raced in after our R_CHECK_TAIL) must
            survive, or W_SCTW_VERIFY's `(arrive - FLAG) == depart`
            can never hold again and the writer starves (race found by
            the repro.analysis model checker)."""
            r = st.regs[p]
            c = env.ctr_of_p[p]
            wa, wd = env.arrive_w[c], env.depart_w[c]
            dep = st.window[wd]
            win = st.window.at[wa].add(-dep).at[wd].add(-dep)
            r = r.at[BARRIER].set(0)
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, wa)
                                + 2.0 * env.lat_atomic(p, wa),
                                hot_word=wa, writes=[wa, wd],
                                next_pc=R_BACKOFF, regs_row=r, window=win)

        def r_recover(p, now, key, st: SimState):
            """Barred-reader self-reset (starvation recovery; see
            r_barrier). Unlike R_RESET this is reached after R_BACKOFF
            already removed our own arrival, so it returns to R_BARRIER
            directly instead of passing through R_BACKOFF again."""
            r = st.regs[p]
            c = env.ctr_of_p[p]
            wa, wd = env.arrive_w[c], env.depart_w[c]
            dep = st.window[wd]
            win = st.window.at[wa].add(-dep).at[wd].add(-dep)
            r = r.at[BARRIER].set(0)
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, wa)
                                + 2.0 * env.lat_atomic(p, wa),
                                hot_word=wa, writes=[wa, wd],
                                next_pc=R_BARRIER, regs_row=r, window=win)

        def r_done(p, now, key, st: SimState):
            r = st.regs[p]
            cnt = st.acq_count[p] + 1
            finished = cnt >= env.target_acq
            r = r.at[BARRIER].set(0)
            st = st._replace(acq_count=st.acq_count.at[p].set(cnt),
                             done=st.done.at[p].set(finished))

            def extra(s, finish):
                return s._replace(t_attempt=s.t_attempt.at[p].set(finish))

            return finish_instr(env, st, p, now, key,
                                dur=think_duration(env, key), hot_word=-1,
                                writes=[], next_pc=R_BARRIER, regs_row=r,
                                extra=extra)

        # ---- crash-recovery instructions ----------------------------
        def rec_inherit(p, now, key, st: SimState):
            """Take over an abandoned predecessor node (detected in
            WA_SPIN). Case ladder, re-validated atomically:
              1. own status granted — the grant raced in (possibly from
                 a REC_TAILFIX passing over the dead node): consume it.
              2. pred's status holds an UNCONSUMED grant — inherit it.
                 A grant can only arrive at a node after its owner
                 linked, i.e. reached WA_SPIN; and the owner consumes
                 it by routing out of WA_SPIN / WA_START_PARENT. So the
                 value is provably unconsumed exactly when the dead
                 froze at one of those two pcs at this level — anywhere
                 else the value is a relic of a grant the dead already
                 acted on, and inheriting it would duplicate the lock.
              3. the dead froze on the acquire side strictly closer to
                 the root (or on this level's fast path / the root
                 counter loop) — it held this level, so adopt its
                 continuation (pc + descent registers). Sound because
                 successor and predecessor share every ancestor element
                 node, so the adopted continuation's addresses resolve
                 identically.
              4. the dead froze inside the CS with the full path held,
                 or in WA_SPIN below this level on a local pass it had
                 not consumed (the pass hands over the whole hold, so
                 it would have entered the CS next) — take the CS.
              5. the dead froze mid-release BEFORE its root grant (the
                 release walks leaf->root reading successors, grants
                 the root first, then unwinds back down) — it still
                 holds every level and has granted nobody, so take the
                 whole hold: straight to the CS (a writer re-runs the
                 counter-flagging loop first, since the dead may have
                 part-cleared the DC flags in ROOT_RESET).
              6. the dead froze mid-unwind at level dUL <= our level —
                 levels above dUL are already released (their grants
                 landed elsewhere: the CS may be live), but the shared
                 ancestor nodes dUL..lvl-1 are still occupied by the
                 dead's incarnation and must NOT be re-enqueued. Take
                 over levels dUL..lvl and resume the climb from dUL.
              7. sole live contender — steal the level and climb.
              Otherwise wait, watching both status words."""
            r = st.regs[p]
            lvl = r[L]
            e = ent(r, lvl, p)
            pred = r[PRED]
            my_w = sw(lvl, e)
            pw = sw(lvl, jnp.maximum(pred, 0))
            pnw = nw(lvl, jnp.maximum(pred, 0))
            own_s = st.window[my_w]
            sp = st.window[pw]
            qs = jnp.arange(env.P)
            exp = expired(st, now)
            pred_dead = entity_dead(st, now, lvl, pred, p)
            # The (single) expired process behind the dead node — in
            # the RW variant, the dead WRITER (a dead reader of the
            # same entity never held the queue node).
            dcand = exp & (env.ent_of_p[lvl] == pred) & (qs != p)
            if RW:
                dcand = dcand & env.is_writer
            d = jnp.argmax(dcand)
            dpc = st.pc[d]
            dL = st.regs[d, L]

            own_granted = own_s != WAIT
            grant = ((sp == ACQUIRE_PARENT) | (sp == MODE_CHANGE)
                     | (sp >= 1))
            unconsumed = (((dpc == WA_SPIN) | (dpc == WA_START_PARENT))
                          & (dL == lvl))
            inherit = ~own_granted & pred_dead & grant & unconsumed
            operating = (((dpc == WA_PREP) | (dpc == WA_ENQ)
                          | (dpc == WA_LINK) | (dpc == WA_SPIN)
                          | (dpc == WA_START_PARENT)) & (dL < lvl)
                         | ((dpc == WA_START_PARENT) & (dL == lvl))
                         | (dpc == W_SCTW_FLAG) | (dpc == W_SCTW_VERIFY))
            adopt = ~own_granted & pred_dead & ~inherit & operating
            d_lvl = jnp.clip(dL, 0, Nlv - 1)
            passed_below = ((dpc == WA_SPIN) & (dL > lvl)
                            & (st.window[sw(d_lvl, ent(st.regs[d], d_lvl,
                                                       d))] >= 1))
            take_cs = (~own_granted & pred_dead & ~inherit & ~adopt
                       & ((dpc == CS) | passed_below))
            # Release side. The release grants nothing until ROOT_PASS /
            # the root tail CAS executes: before that (WR_* descent and
            # every ROOT_* pc) the dead still holds ALL levels. Once it
            # is unwinding (UNW_*), register UL names the next level to
            # release: levels < UL are gone (their grants may be live
            # in another branch), levels UL..leaf are still held.
            rel_pre = ((dpc == WR_READ) | (dpc == WR_DECIDE)
                       | (dpc == ROOT_DECIDE) | (dpc == ROOT_RESET)
                       | (dpc == ROOT_GETSUCC) | (dpc == ROOT_CAS)
                       | (dpc == ROOT_WAITSUCC) | (dpc == ROOT_PASS))
            rel_unw = ((dpc == UNW_CHECK) | (dpc == UNW_WAIT)
                       | (dpc == UNW_PUT))
            dUL = st.regs[d, UL]
            rel_take = (~own_granted & pred_dead & rel_pre
                        & ~(inherit | adopt | take_cs))
            # dUL > lvl would mean our level was already released, i.e.
            # our grant is in flight or landed — own_granted handles it.
            rel_climb = (~own_granted & pred_dead & rel_unw
                         & (dUL <= lvl)
                         & ~(inherit | adopt | take_cs | rel_take))
            sole = jnp.all(st.done | exp | (qs == p))
            claim = (~own_granted & pred_dead & sole
                     & ~(inherit | adopt | take_cs | rel_take
                         | rel_climb))

            s_eff = jnp.where(own_granted, own_s, sp)
            if RW:
                vroute = jnp.where(
                    s_eff == ACQUIRE_PARENT, WA_START_PARENT,
                    jnp.where((lvl == 0) & (s_eff == MODE_CHANGE),
                              W_SCTW_FLAG, CS))
                claim_pc = jnp.where(lvl == 0, W_SCTW_FLAG,
                                     WA_START_PARENT)
                # Full-hold take-over must re-assert the DC write flags
                # (idempotent set-if-unset) before entering the CS: the
                # dead may have part-cleared them in ROOT_RESET.
                take_pc = jnp.asarray(W_SCTW_FLAG, jnp.int32)
            else:
                vroute = jnp.where(s_eff == ACQUIRE_PARENT,
                                   WA_START_PARENT, CS)
                claim_pc = jnp.asarray(WA_START_PARENT, jnp.int32)
                take_pc = jnp.asarray(CS, jnp.int32)
            nxt = jnp.where(
                own_granted | inherit, vroute,
                jnp.where(adopt, dpc,
                          jnp.where(take_cs, CS,
                                    jnp.where(rel_take, take_pc,
                                              jnp.where(rel_climb,
                                                        WA_START_PARENT,
                                                        jnp.where(claim,
                                                                  claim_pc,
                                                                  REC_INHERIT))))))

            stat_new = jnp.where(
                own_granted | inherit, s_eff,
                jnp.where(adopt | take_cs | rel_take | rel_climb | claim,
                          jnp.asarray(ACQUIRE_START, jnp.int32),
                          r[STATUS]))
            r2 = r.at[STATUS].set(stat_new)
            # rel_take owns everything down from the root (L:=0 so the
            # RW flag/verify loop re-enters the CS via the root node);
            # rel_climb owns dUL..lvl and resumes the climb at dUL.
            r2 = r2.at[L].set(jnp.where(
                adopt, dL,
                jnp.where(rel_climb, dUL,
                          jnp.where(rel_take, 0, r2[L]))))
            r2 = r2.at[PRED].set(jnp.where(adopt, st.regs[d, PRED], pred))
            r2 = r2.at[K].set(jnp.where(adopt, st.regs[d, K],
                                        jnp.where(rel_take, 0, r2[K])))

            win = st.window
            consume = inherit | adopt | take_cs | rel_take | rel_climb
            win = jnp.where(
                consume,
                win.at[my_w].set(jnp.where(
                    inherit, s_eff,
                    jnp.asarray(ACQUIRE_START, jnp.int32))),
                win)
            reclaim = ((inherit | adopt | take_cs | rel_take | rel_climb
                        | claim) & (pred != e))
            win = jnp.where(reclaim,
                            win.at[pw].set(WAIT).at[pnw].set(NULL), win)

            resolved = (own_granted | inherit | adopt | take_cs
                        | rel_take | rel_climb | claim)
            return finish_instr(
                env, st, p, now, key,
                dur=2.0 * env.lat_plain(p, my_w) + env.lat_atomic(p, pw),
                hot_word=jnp.where(resolved, pw, _NOOP),
                writes=[my_w, pw, pnw], next_pc=nxt, regs_row=r2,
                window=win,
                block_a=jnp.where(resolved, _NOOP, pw),
                block_b=jnp.where(resolved, _NOOP, my_w),
                extra=engine.recovery_extra(resolved & ~own_granted,
                                            ~resolved))

        def rec_tailfix(p, now, key, st: SimState):
            """Pass over (or unhook) a dead successor during release
            (TMP holds the level). Two shapes, re-validated atomically:

              * ghost — the successor died between its tail-FAO and its
                link, so it never appears in our NEXT word (detected in
                ROOT_WAITSUCC / UNW_WAIT via the lease registry);
              * linked — the successor appears in our NEXT word but its
                node is abandoned (detected wherever a grant was about
                to be written: ROOT_GETSUCC/ROOT_WAITSUCC, UNW_CHECK/
                UNW_WAIT).

            Either way the dead node's own successor (if any) receives
            the grant the dead would have gotten; if the dead was the
            tail, the tail is cleared and this level's release is done.
            If the dead's NEXT is still empty while the tail is beyond
            it, a live enqueuer is mid-link behind the dead node — wait
            for its link to land, watching the dead node's NEXT word."""
            r = st.regs[p]
            lvl = jnp.minimum(r[TMP], Nlv - 1)
            at_root = lvl == 0
            e = ent(r, lvl, p)
            my_next = st.window[nw(lvl, e)]
            t = tw(lvl, p)
            cur = st.window[t]
            found, e_g, gn = ghost_succ(st, now, lvl, e, p)
            linked = ((my_next != NULL)
                      & entity_dead(st, now, lvl,
                                    jnp.maximum(my_next, 0), p))
            ghost = ((my_next == NULL) & found
                     & no_live_enqueuer(st, now, lvl, p))
            e_d = jnp.where(linked, my_next, e_g)
            dn = st.window[nw(lvl, jnp.maximum(e_d, 0))]
            ok = linked | ghost
            # Dead node has a linked successor: pass it the grant the
            # dead would have received, and unhook the dead node.
            skip = ok & (dn != NULL)
            # Dead node was the queue tail: clear the tail — this
            # level's release is complete.
            fix = ok & ~skip & (cur == e_d)
            # Tail is beyond the dead node but its NEXT is still empty:
            # a live enqueuer is mid-link behind it.
            wait_mid = ok & ~skip & ~fix
            gval = jnp.where(at_root, r[NEXT_STAT],
                             jnp.asarray(ACQUIRE_PARENT, jnp.int32))
            wsucc = sw(lvl, jnp.maximum(dn, 0))
            win = st.window
            win = jnp.where(skip,
                            win.at[wsucc].set(gval)
                               .at[nw(lvl, e_d)].set(NULL), win)
            win = jnp.where(fix, win.at[t].set(NULL), win)
            repaired = skip | fix
            back = jnp.where(at_root, ROOT_WAITSUCC, UNW_WAIT)
            nxt = jnp.where(repaired, UNW_CHECK,
                            jnp.where(wait_mid, REC_TAILFIX, back))
            # The unwind resumes one level below the repaired one. Set,
            # never raised: entered from ROOT_GETSUCC, UL still holds the
            # CS's reset value N, and unwinding from there would skip
            # every level below the root and strand a successor that
            # linked after WR_READ.
            r = r.at[UL].set(jnp.where(repaired, lvl + 1, r[UL]))
            # An atomic on the tail, and a plain access of the word that
            # settles the step: the grant word of the dead node's
            # successor, or the dead node's NEXT word, read empty.
            settles = jnp.where(dn != NULL, wsucc,
                                nw(lvl, jnp.maximum(e_d, 0)))
            dur = env.lat_atomic(p, t) + env.lat_plain(p, settles)
            return finish_instr(env, st, p, now, key, dur=dur,
                                hot_word=jnp.where(repaired, t, _NOOP),
                                writes=[t, wsucc, nw(lvl, e_d)],
                                next_pc=nxt, regs_row=r, window=win,
                                block_a=jnp.where(wait_mid,
                                                  nw(lvl,
                                                     jnp.maximum(e_d, 0)),
                                                  _NOOP),
                                extra=engine.recovery_extra(repaired,
                                                            ~repaired))

        def rec_drain(p, now, key, st: SimState):
            """Zero dead readers' unmatched arrivals on counter K so
            W_SCTW_VERIFY can complete; our WRITE_FLAG is preserved.
            The quiescence guard is re-validated in this atomic step."""
            r = st.regs[p]
            k = r[K]
            wa, wd = env.arrive_w[k], env.depart_w[k]
            arr, dep = st.window[wa], st.window[wd]
            clear = (arr - WRITE_FLAG) == dep
            stale = (~clear) & ctr_quiescent(st, now, k)
            win = st.window.at[wa].set(
                jnp.where(stale, dep + WRITE_FLAG, arr))
            fixed = clear | stale
            last = k + 1 >= env.n_ctr
            r = r.at[K].set(jnp.where(fixed & ~last, k + 1,
                                      jnp.where(fixed & last, 0, k)))
            nxt = jnp.where(fixed & last, WA_START_PARENT, W_SCTW_VERIFY)
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, wa)
                                + env.lat_atomic(p, wa),
                                hot_word=wa, writes=[wa], next_pc=nxt,
                                regs_row=r, window=win,
                                extra=engine.recovery_extra(stale,
                                                            ~fixed))

        def r_unbar(p, now, key, st: SimState):
            """A barred reader clears a counter wedged by the dead:
            every writer is done or lease-expired and no live reader of
            this counter holds an arrival, so the stale flag/arrivals
            can never drain on their own — reset both words."""
            r = st.regs[p]
            c = env.ctr_of_p[p]
            wa, wd = env.arrive_w[c], env.depart_w[c]
            exp = expired(st, now)
            ok = (jnp.any(exp)
                  & jnp.all(~env.is_writer | st.done | exp)
                  & ctr_quiescent(st, now, c))
            arr, dep = st.window[wa], st.window[wd]
            win = (st.window.at[wa].set(jnp.where(ok, 0, arr))
                   .at[wd].set(jnp.where(ok, 0, dep)))
            r = r.at[BARRIER].set(jnp.where(ok, 0, r[BARRIER]))
            return finish_instr(env, st, p, now, key,
                                dur=2.0 * env.lat_plain(p, wa)
                                + 2.0 * env.lat_atomic(p, wa),
                                hot_word=wa, writes=[wa, wd],
                                next_pc=R_BARRIER, regs_row=r,
                                window=win,
                                extra=engine.recovery_extra(ok, ~ok))

        def trap(p, now, key, st: SimState):
            # Self-loop: pc 7 is unused, and a self-looping trap shows
            # up as a stuck SCC in the model checker if anything ever
            # mis-routes here, instead of silently limping onward.
            return finish_instr(env, st, p, now, key, dur=1.0, hot_word=-1,
                                writes=[], next_pc=7,
                                regs_row=st.regs[p])

        handlers = [trap] * N_PCS
        handlers[WA_PREP] = wa_prep
        handlers[WA_ENQ] = wa_enq
        handlers[WA_LINK] = wa_link
        handlers[WA_SPIN] = wa_spin
        handlers[WA_START_PARENT] = wa_start_parent
        handlers[W_SCTW_FLAG] = w_sctw_flag
        handlers[W_SCTW_VERIFY] = w_sctw_verify
        handlers[CS] = cs_instr
        handlers[WR_READ] = wr_read
        handlers[WR_DECIDE] = wr_decide
        handlers[ROOT_DECIDE] = root_decide
        handlers[ROOT_RESET] = root_reset
        handlers[ROOT_CAS] = root_cas
        handlers[ROOT_WAITSUCC] = root_waitsucc
        handlers[ROOT_PASS] = root_pass
        handlers[UNW_CHECK] = unw_check
        handlers[UNW_WAIT] = unw_wait
        handlers[UNW_PUT] = unw_put
        handlers[DONE_ONE] = done_one
        handlers[ROOT_GETSUCC] = root_getsucc
        handlers[R_BARRIER] = r_barrier
        handlers[R_FAO] = r_fao
        handlers[R_CHECK_TAIL] = r_check_tail
        handlers[R_BACKOFF] = r_backoff
        handlers[R_CS] = r_cs
        handlers[R_RELEASE] = r_release
        handlers[R_RESET] = r_reset
        handlers[R_DONE] = r_done
        handlers[R_RECOVER] = r_recover
        handlers[REC_INHERIT] = rec_inherit
        handlers[REC_TAILFIX] = rec_tailfix
        handlers[REC_DRAIN] = rec_drain
        handlers[R_UNBAR] = r_unbar
        return engine.handler_table(handlers, PC_NAMES)


def rma_rw() -> HierProgram:
    return HierProgram(has_readers=True)


def rma_mcs() -> HierProgram:
    return HierProgram(has_readers=False)


d_mcs = rma_mcs  # D-MCS is RMA-MCS on a 1-level machine (single queue).
