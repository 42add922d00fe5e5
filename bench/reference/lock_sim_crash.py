"""Plain reference of the lock simulator under process loss: RMA-RW with
one crash per schedule and lease-based recovery.

The fault-free reference (`bench/reference/lock_sim.py`) plus the crash
semantics the configuration states, written apart from the program (it
imports nothing of `repro`), event by event on the host in float32:

  * a crash plan names one victim and a time. The crash takes effect at
    the victim's first scheduling point at or after that time: the
    instruction it was about to run is not executed, its window words
    keep whatever it had written, its critical-section occupancy is
    released, and it never runs again. The crash is an event of its
    own: it takes one draw of the schedule's jitter stream;
  * a survivor presumes the victim dead once its lease has run out,
    `lease` simulated microseconds after the crash took effect. Every
    recovery decision is keyed off that predicate, and a survivor also
    sees the pc and registers the victim stopped at (the lease service
    publishes each process's last protocol phase);
  * the survivors repair what the victim abandoned: a waiter takes over
    its dead predecessor's queue node (`REC_INHERIT`), a releaser passes
    over a dead successor (`REC_TAILFIX`), a writer drains a dead
    reader's arrival (`REC_DRAIN`), and a barred reader clears a counter
    wedged by a dead writer (`R_UNBAR`). Each repair re-checks its guard
    in the same step: one that finds nothing to repair yet waits and is
    counted as a retry.

`run(cfg, seeds, victims, crash_t)` returns one dict per run with the
fields a run reports, `t_crash` and `t_recover` among them. It models
one crash per run and no revive; anything else raises `Unsupported`.
"""
from __future__ import annotations

import heapq

from bench.reference import lock_sim as base
from bench.reference.lock_sim import (
    ACQUIRE_PARENT, ACQUIRE_START, CS, DONE_ONE, F, INF, MODE_CHANGE,
    NULL, R_BARRIER, R_FAO, R_RECOVER, ROOT_CAS, ROOT_DECIDE,
    ROOT_GETSUCC, ROOT_PASS, ROOT_RESET, ROOT_WAITSUCC, UNW_CHECK,
    UNW_PUT, UNW_WAIT, W_SCTW_FLAG, W_SCTW_VERIFY, WA_ENQ, WA_LINK,
    WA_PREP, WA_SPIN, WA_START_PARENT, WAIT, WR_DECIDE, WR_READ,
    Unsupported)

# Recovery pcs, named as the fault-free reference names the others.
REC_INHERIT, REC_TAILFIX, R_UNBAR = 30, 31, 33
# Reader pcs at which a reader holds no arrival on its counter.
READER_FREE = base.READER_FREE + (R_UNBAR,)
ACQUIRE_SIDE = (WA_PREP, WA_ENQ, WA_LINK, WA_SPIN, WA_START_PARENT)
RELEASE_BEFORE_ROOT_GRANT = (WR_READ, WR_DECIDE, ROOT_DECIDE, ROOT_RESET,
                             ROOT_GETSUCC, ROOT_CAS, ROOT_WAITSUCC,
                             ROOT_PASS)
UNWIND = (UNW_CHECK, UNW_WAIT, UNW_PUT)


class CrashRun(base.Run):
    """One schedule in which process `victim` crashes at `crash_t`."""

    def __init__(self, m: base.Machine, target_acq: int, max_events: int,
                 victim: int, crash_t: float, lease: float):
        super().__init__(m, target_acq, max_events)
        self.victim = int(victim)
        self.crash_at = F(crash_t)
        self.lease = F(lease)
        self.crashed = False
        self.t_crash = INF
        self.in_cs = [False] * m.P
        self.now = F(0)
        # Writers that can act as each queue entity (level, entity).
        self.writers_of = {}
        for lvl in range(m.N):
            for q in range(m.P):
                if m.is_writer[q]:
                    self.writers_of.setdefault((lvl, m.ent(lvl, q)),
                                               []).append(q)
        self.writers = [q for q in range(m.P) if m.is_writer[q]]

    # ---------------------------------------------------------- engine
    def advance(self, plain, cs, base_event):
        """Events base_event .. base_event + len(plain) - 1, the crash
        among them."""
        end = base_event + len(plain)
        heap = self.heap
        while self.running() and self.events < end:
            t, p = heapq.heappop(heap)
            if self.done[p] or t != float(self.t_ready[p]):
                continue
            self.now = now = self.t_ready[p]
            if p == self.victim and now >= self.crash_at:
                self.crash(p, now)
                continue
            i = self.events - base_event
            self.step(p, now, F(plain[i]), F(cs[i]))

    def crash(self, p, now):
        """The victim stops before its instruction: its CS occupancy is
        released, it watches nothing, and it leaves the schedule."""
        self.crashed = True
        self.t_crash = now
        if self.in_cs[p]:
            self.cs_exit(p)
        self.watch(p, -1, -1)
        self.t_ready[p] = INF
        self.left -= 1
        self.events += 1

    def cs_enter(self, p, now):
        super().cs_enter(p, now)
        self.in_cs[p] = True

    def cs_exit(self, p):
        super().cs_exit(p)
        self.in_cs[p] = False

    # ---------------------------------------------- lease registry reads
    def expired(self, q) -> bool:
        """q crashed, and its lease ran out by now."""
        return (q == self.victim and self.crashed
                and self.now >= F(self.t_crash + self.lease))

    def any_expired(self) -> bool:
        return self.expired(self.victim)

    def entity_dead(self, lvl, e, p) -> bool:
        """Every writer that can act as entity `e` at `lvl`, other than
        p, is lease-expired or done, and one is expired: the node is
        abandoned."""
        others = [q for q in self.writers_of.get((lvl, e), ()) if q != p]
        return (any(self.expired(q) for q in others)
                and all(self.expired(q) or self.done[q] for q in others))

    def no_live_enqueuer(self, lvl, p) -> bool:
        """No live process sits between its tail swap and its link at
        `lvl`."""
        return not any(self.pc[q] == WA_LINK and self.reg[q]["L"] == lvl
                       and not self.done[q] and q != p
                       and not (q == self.victim and self.crashed)
                       for q in self.writers)

    def ghost_succ(self, lvl, e, p):
        """(entity, its NEXT word) of the expired process that stopped
        between its tail swap and its link at `lvl` behind e, or None
        where there is no such process."""
        v = self.victim
        if not (v != p and self.expired(v) and self.pc[v] == WA_LINK
                and self.reg[v]["L"] == lvl and self.reg[v]["PRED"] == e):
            return None
        e_v = self.m.ent(lvl, v)
        return e_v, self.win[self.m.next_w[lvl][e_v]]

    def quiescent(self, c) -> bool:
        """No live reader of counter c holds an unmatched arrival."""
        return all(self.done[q] or self.expired(q)
                   or self.pc[q] in READER_FREE
                   for q in self.m.readers_of_ctr[c])

    def writers_gone(self) -> bool:
        return (self.any_expired()
                and all(self.done[q] or self.expired(q)
                        for q in self.writers))

    def recovered(self, reclaimed, retried):
        def after(fin):
            self.reclaims += int(reclaimed)
            self.retries += int(retried)
            if reclaimed:
                self.t_recover = min(self.t_recover, fin)
        return after

    # ---------------------------------------------------- instructions
    def step(self, p, now, jit, jit_cs):
        pc = self.pc[p]
        if pc == WA_SPIN:
            self.wa_spin(p, now, jit)
        elif pc == WR_DECIDE:
            self.wr_decide(p, now, jit)
        elif pc == ROOT_GETSUCC:
            self.root_getsucc(p, now, jit)
        elif pc in (ROOT_WAITSUCC, UNW_WAIT):
            self.wait_succ(p, now, jit)
        elif pc == UNW_CHECK:
            self.unw_check(p, now, jit)
        elif pc == R_BARRIER:
            self.r_barrier(p, now, jit)
        elif pc == REC_INHERIT:
            self.rec_inherit(p, now, jit)
        elif pc == REC_TAILFIX:
            self.rec_tailfix(p, now, jit)
        elif pc == R_UNBAR:
            self.r_unbar(p, now, jit)
        else:
            super().step(p, now, jit, jit_cs)

    def wa_spin(self, p, now, jit):
        """Spin on own STATUS; a waiter whose predecessor's node is
        abandoned takes it over in REC_INHERIT."""
        m, r = self.m, self.reg[p]
        lvl = r["L"]
        w = m.status_w[lvl][m.ent(lvl, p)]
        s = r["STATUS"] = self.win[w]
        waiting = s == WAIT
        pred_gone = waiting and self.entity_dead(lvl, r["PRED"], p)
        if pred_gone:
            nxt = REC_INHERIT
        elif waiting:
            nxt = WA_SPIN
        elif s == ACQUIRE_PARENT:
            nxt = WA_START_PARENT
        elif lvl == 0 and s == MODE_CHANGE:
            nxt = W_SCTW_FLAG
        else:
            nxt = CS
        self.finish(p, now, jit, m.plain(p, w), -1, [], [], nxt,
                    block=(w if waiting and not pred_gone else -1, -1))

    def wr_decide(self, p, now, jit):
        """Pass locally, but never into an abandoned node."""
        m, r = self.m, self.reg[p]
        lvl = r["L"]
        succ = r["SUCC"][lvl]
        succ_dead = succ != NULL and self.entity_dead(lvl, succ, p)
        can_pass = (succ != NULL and not succ_dead
                    and r["STATUS"] < m.T_L[lvl] and lvl > 0)
        if can_pass:
            r["UL"] = lvl + 1
            w = m.status_w[lvl][succ]
            self.finish(p, now, jit, m.plain(p, w), -1,
                        [(w, r["STATUS"] + 1)], [w], UNW_CHECK)
        else:
            r["L"] = lvl - 1
            self.finish(p, now, jit, F(0.02), -1, [], [],
                        WR_READ if lvl - 1 >= 1 else ROOT_DECIDE)

    def root_getsucc(self, p, now, jit):
        """Read the root successor; pass over an abandoned one."""
        m, r = self.m, self.reg[p]
        nw = m.next_w[0][m.ent(0, p)]
        succ = r["SUCC"][0] = self.win[nw]
        sdead = succ != NULL and self.entity_dead(0, succ, p)
        r["K"], r["TMP"] = 0, (0 if sdead else ROOT_CAS)
        if sdead:
            nxt = REC_TAILFIX
        elif succ != NULL:
            nxt = ROOT_PASS
        elif r["CRESET"] == 0:
            nxt = ROOT_RESET
        else:
            nxt = ROOT_CAS
        self.finish(p, now, jit, m.plain(p, nw), -1, [], [], nxt)

    def wait_succ(self, p, now, jit):
        """Wait for the late successor at the root (ROOT_WAITSUCC) or
        one level of the unwind (UNW_WAIT). A successor that died
        before its link, or whose linked node is abandoned, is passed
        over in REC_TAILFIX."""
        m, r = self.m, self.reg[p]
        root = self.pc[p] == ROOT_WAITSUCC
        ul = 0 if root else min(r["UL"], m.N - 1)
        e = m.ent(ul, p)
        w = m.next_w[ul][e]
        succ = r["SUCC"][ul] = self.win[w]
        sdead = succ != NULL and self.entity_dead(ul, succ, p)
        rec = sdead
        ghost = self.ghost_succ(ul, e, p) if succ == NULL else None
        if ghost is not None:
            e_d, dn = ghost
            tail = self.win[m.tail_w[ul][m.elem[ul][p]]]
            rec = (self.no_live_enqueuer(ul, p)
                   and (dn != NULL or tail == e_d))
        if rec:
            r["TMP"] = ul
            nxt = REC_TAILFIX
        elif succ == NULL:
            nxt = ROOT_WAITSUCC if root else UNW_WAIT
        else:
            nxt = ROOT_PASS if root else UNW_PUT
        self.finish(p, now, jit, m.plain(p, w), -1, [], [], nxt,
                    block=(w if succ == NULL and not rec else -1, -1))

    def unw_check(self, p, now, jit):
        """Clear the tail or find the late successor, one level of the
        unwind; an abandoned successor goes to REC_TAILFIX."""
        m, r, win, N = self.m, self.reg[p], self.win, self.m.N
        ul = r["UL"]
        fin = ul > N - 1
        ulc = min(ul, N - 1)
        succ = r["SUCC"][ulc]
        t = m.tail_w[ulc][m.elem[ulc][p]]
        do_cas = not fin and succ == NULL
        cas_ok = do_cas and win[t] == m.ent(ulc, p)
        if fin or cas_ok:
            r["UL"] = ul + (0 if fin else 1)
        sdead = not fin and succ != NULL and self.entity_dead(ulc, succ, p)
        if sdead:
            r["TMP"] = ulc
        if fin:
            nxt = DONE_ONE
        elif succ != NULL:
            nxt = REC_TAILFIX if sdead else UNW_PUT
        else:
            nxt = UNW_CHECK if cas_ok else UNW_WAIT
        self.finish(p, now, jit, m.atomic(p, t) if do_cas else F(0.02),
                    t if do_cas else -1,
                    [(t, NULL)] if cas_ok else [], [t], nxt)

    def r_barrier(self, p, now, jit):
        """A barred reader: un-bar a counter wedged by the dead first,
        else reset it once the root tail is empty, else wait."""
        m, r = self.m, self.reg[p]
        c = m.ctr_of_p[p]
        wa = m.arrive_w[c]
        t = m.tail_w[0][0]
        over = r["BARRIER"] == 1 and self.win[wa] >= m.T_R
        unbar = over and self.writers_gone() and self.quiescent(c)
        recover = over and not unbar and self.win[t] == NULL
        barred = over and not unbar and not recover
        nxt = (R_UNBAR if unbar else R_RECOVER if recover
               else R_BARRIER if barred else R_FAO)
        dur = (F(m.plain(p, wa) + m.plain(p, t)) if r["BARRIER"] == 1
               else F(0.02))
        self.finish(p, now, jit, dur, -1, [], [], nxt,
                    block=(wa, t) if barred else (-1, -1))

    def rec_inherit(self, p, now, jit):
        """Take over the abandoned node of p's predecessor: consume a
        grant that raced in, inherit the dead's unconsumed grant, adopt
        its acquire continuation, take its CS, take over its release
        (all of it before the root grant; from its unwind level after),
        or claim the level as the sole live contender. Else wait."""
        m, r, win = self.m, self.reg[p], self.win
        lvl = r["L"]
        e = m.ent(lvl, p)
        pred = r["PRED"]
        my_w = m.status_w[lvl][e]
        pw = m.status_w[lvl][pred]
        pnw = m.next_w[lvl][pred]
        own_s, sp = win[my_w], win[pw]
        own_granted = own_s != WAIT
        # p acts only on an abandoned node; with one crash it is the
        # victim's, and the victim's last pc and registers say what of
        # its hold p takes over.
        live = not own_granted and self.entity_dead(lvl, pred, p)
        inherit = adopt = take_cs = rel_take = rel_climb = claim = False
        if live:
            dpc, dr = self.pc[self.victim], self.reg[self.victim]
            dL, dUL = dr["L"], dr["UL"]
            grant = sp in (ACQUIRE_PARENT, MODE_CHANGE) or sp >= 1
            inherit = (grant and dpc in (WA_SPIN, WA_START_PARENT)
                       and dL == lvl)
            adopt = not inherit and ((dpc in ACQUIRE_SIDE and dL < lvl)
                                     or (dpc == WA_START_PARENT
                                         and dL == lvl)
                                     or dpc in (W_SCTW_FLAG, W_SCTW_VERIFY))
            # Frozen in the CS, or in WA_SPIN below this level on a
            # local pass it had not consumed: the pass handed it the
            # whole hold, and it would have entered the CS next.
            passed_below = (dpc == WA_SPIN and dL > lvl
                            and win[m.status_w[dL][m.ent(dL, self.victim)]]
                            >= 1)
            take_cs = (not (inherit or adopt)
                       and (dpc == CS or passed_below))
            rel_take = (dpc in RELEASE_BEFORE_ROOT_GRANT
                        and not (inherit or adopt or take_cs))
            rel_climb = (dpc in UNWIND and dUL <= lvl
                         and not (inherit or adopt or take_cs or rel_take))
            claim = (not (inherit or adopt or take_cs or rel_take
                          or rel_climb)
                     and all(self.done[q] or self.expired(q) or q == p
                             for q in range(m.P)))

        s_eff = own_s if own_granted else sp
        if own_granted or inherit:
            if s_eff == ACQUIRE_PARENT:
                nxt = WA_START_PARENT
            elif lvl == 0 and s_eff == MODE_CHANGE:
                nxt = W_SCTW_FLAG
            else:
                nxt = CS
        elif adopt:
            nxt = dpc
        elif take_cs:
            nxt = CS
        elif rel_take:
            # The dead may have part-cleared the write flags: flag
            # every counter again (set-if-unset) before the CS.
            nxt = W_SCTW_FLAG
        elif rel_climb:
            nxt = WA_START_PARENT
        elif claim:
            nxt = W_SCTW_FLAG if lvl == 0 else WA_START_PARENT
        else:
            nxt = REC_INHERIT

        took = adopt or take_cs or rel_take or rel_climb or claim
        if own_granted or inherit:
            r["STATUS"] = s_eff
        elif took:
            r["STATUS"] = ACQUIRE_START
        if adopt:
            r["L"], r["PRED"], r["K"] = dL, dr["PRED"], dr["K"]
        elif rel_climb:
            r["L"] = dUL
        elif rel_take:
            r["L"], r["K"] = 0, 0

        mods = []
        if inherit or (took and not claim):
            mods.append((my_w, s_eff if inherit else ACQUIRE_START))
        if (inherit or took) and pred != e:
            mods += [(pw, WAIT), (pnw, NULL)]
        resolved = own_granted or inherit or took
        self.finish(p, now, jit,
                    F(F(2.0) * m.plain(p, my_w)) + m.atomic(p, pw),
                    pw if resolved else -1, mods, [my_w, pw, pnw], nxt,
                    block=(-1, -1) if resolved else (pw, my_w),
                    after=self.recovered(resolved and not own_granted,
                                         not resolved))

    def rec_tailfix(self, p, now, jit):
        """Pass over a dead successor at level TMP during a release: hand
        its own successor the grant it would have had, or clear the tail
        if it was the last; wait while a live enqueuer behind it has yet
        to link. It costs an atomic on the tail and a plain access of
        the grant word of the dead node's successor, or, where the dead
        node has none, of the dead node's NEXT word, read empty."""
        m, r, win = self.m, self.reg[p], self.win
        lvl = min(r["TMP"], m.N - 1)
        e = m.ent(lvl, p)
        my_next = win[m.next_w[lvl][e]]
        t = m.tail_w[lvl][m.elem[lvl][p]]
        if my_next != NULL:
            # Entered on a linked successor, which stays dead.
            e_d, ok = my_next, self.entity_dead(lvl, my_next, p)
        else:
            # Entered on a ghost, which stays one: only a live enqueuer
            # starting its link holds the repair back.
            e_d = self.ghost_succ(lvl, e, p)[0]
            ok = self.no_live_enqueuer(lvl, p)
        dead_next = m.next_w[lvl][e_d]
        dn = win[dead_next]
        skip = ok and dn != NULL
        fix = ok and not skip and win[t] == e_d
        wait_mid = ok and not skip and not fix
        gval = r["NEXT_STAT"] if lvl == 0 else ACQUIRE_PARENT
        mods = []
        if skip:
            wsucc = m.status_w[lvl][dn]
            mods = [(wsucc, gval), (dead_next, NULL)]
        elif fix:
            mods = [(t, NULL)]
        repaired = skip or fix
        if repaired:
            # This level's release is done: the unwind resumes below it.
            r["UL"] = lvl + 1
            nxt = UNW_CHECK
        elif wait_mid:
            nxt = REC_TAILFIX
        else:
            nxt = ROOT_WAITSUCC if lvl == 0 else UNW_WAIT
        settles = m.status_w[lvl][dn] if dn != NULL else dead_next
        self.finish(p, now, jit, F(m.atomic(p, t) + m.plain(p, settles)),
                    t if repaired else -1, mods, [w for w, _ in mods], nxt,
                    block=(dead_next if wait_mid else -1, -1),
                    after=self.recovered(repaired, not repaired))

    def r_unbar(self, p, now, jit):
        """Every writer is done or dead and no live reader of p's
        counter holds an arrival: clear the counter's stale flag and
        arrivals."""
        m, r, win = self.m, self.reg[p], self.win
        c = m.ctr_of_p[p]
        wa, wd = m.arrive_w[c], m.depart_w[c]
        ok = self.writers_gone() and self.quiescent(c)
        if ok:
            r["BARRIER"] = 0
        dur = F(F(2.0) * m.plain(p, wa)) + F(F(2.0) * m.atomic(p, wa))
        self.finish(p, now, jit, dur, wa,
                    [(wa, 0), (wd, 0)] if ok else [], [wa, wd], R_BARRIER,
                    after=self.recovered(ok, not ok))

    def metrics(self) -> dict:
        out = super().metrics()
        crashed = [q == self.victim and self.crashed for q in range(self.m.P)]
        out.update(completed=all(d or c for d, c in zip(self.done, crashed)),
                   n_crashed=int(self.crashed),
                   t_crash=float(self.t_crash),
                   t_recover=float(self.t_recover))
        return out


def run(cfg: dict, seeds, victims, crash_t, chunk: int = 1 << 15) -> list:
    """Simulate run s of the configuration `cfg`: schedule seed
    `seeds[s]`, in which process `victims[s]` crashes at `crash_t[s]`
    simulated microseconds. One dict per run."""
    lock, wl = cfg["lock"], cfg["workload"]
    failure = cfg.get("failure", {})
    if lock["kind"] != "rma_rw":
        raise Unsupported(f"the reference models rma_rw, not {lock['kind']}")
    if wl["cs_kind"] != 0 or wl["think"]:
        raise Unsupported("the reference models the empty critical "
                          "section without think time")
    if int(failure.get("crashes_per_schedule", 1)) != 1:
        raise Unsupported("the reference models one crash per schedule")
    if failure.get("revive", False):
        raise Unsupported("the reference models no revive")
    seeds, victims = list(seeds), list(victims)
    crash_t = list(crash_t)
    if not len(seeds) == len(victims) == len(crash_t):
        raise ValueError("one victim and one crash time per seed")
    m = base.Machine(cfg)
    lease = float(failure.get("lease_us", 2.0))
    runs = [CrashRun(m, int(wl["target_acq"]), int(wl["max_events"]),
                     v, t, lease) for v, t in zip(victims, crash_t)]
    keys, start = None, 0
    while any(sim.running() for sim in runs):
        keys, plain, cs = base.jitter_streams(seeds, chunk, m.jitter,
                                              keys=keys)
        for s, sim in enumerate(runs):
            sim.advance(plain[s], cs[s], start)
        start += chunk
    return [sim.metrics() for sim in runs]
