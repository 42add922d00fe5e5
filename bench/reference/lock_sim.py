"""Plain reference of the lock simulator: the RMA-RW protocol, fault-free.

A straightforward event-by-event implementation of the semantics the
benchmark's lock configurations state, written apart from the program
(it imports nothing of `repro`): the paper's hierarchical reader-writer
lock (Schmid, Besta, Hoefler, HPDC'16, Listings 4-10) on an N-level
machine, with the cost model of its configuration file, simulated one
instruction at a time in the order of the earliest ready process.

What it shares with the program is only what a configuration defines:
the machine, the cost constants, the roles drawn from `role_seed`, and
the schedule jitter, which is JAX's documented threefry stream of the
run's seed (one `split` per event, `uniform(0, jitter)` per instruction).
It runs on the host CPU in float32 scalars, so every simulated time is
rounded as the configuration's float32 arithmetic rounds it.

`run(cfg, seeds)` returns one dict per seed with the fields a run
reports. `exclusive=False` is the control: readers take their arrival
as a grant even when a writer has flagged their counter, which breaks
the reader-writer exclusion the configuration guarantees.
"""
from __future__ import annotations

import heapq

import numpy as np

F = np.float32
INF = F(3.4e38)
NULL, WAIT, ACQUIRE_PARENT, MODE_CHANGE, ACQUIRE_START = -1, -2, -3, -4, 0
WRITE_FLAG = 1 << 28
UNBOUNDED = 1 << 26

# Program counters of the protocol, named after the paper's listings.
(WA_PREP, WA_ENQ, WA_LINK, WA_SPIN, WA_START_PARENT, W_SCTW_FLAG,
 W_SCTW_VERIFY) = range(7)
CS, WR_READ, WR_DECIDE = 8, 9, 10
ROOT_DECIDE, ROOT_RESET, ROOT_CAS, ROOT_WAITSUCC, ROOT_PASS = 11, 12, 13, 14, 15
UNW_CHECK, UNW_WAIT, UNW_PUT, ROOT_GETSUCC, DONE_ONE = 16, 17, 18, 19, 20
(R_BARRIER, R_FAO, R_CHECK_TAIL, R_BACKOFF, R_CS, R_RELEASE, R_RESET,
 R_DONE, R_RECOVER) = range(21, 30)
REC_DRAIN = 32
# Reader pcs at which a reader holds no arrival on its counter.
READER_FREE = (R_BARRIER, R_FAO, R_DONE, R_RECOVER)


class Unsupported(RuntimeError):
    """The run reached a state this fault-free reference does not model."""


def writer_mask(P: int, writer_fraction: float, role_seed: int) -> np.ndarray:
    """The paper's random roles: round(P * F_W) writers drawn from the seed."""
    n = max(1, int(round(P * writer_fraction))) if writer_fraction > 0 else 0
    mask = np.zeros(P, bool)
    if n:
        mask[np.random.RandomState(role_seed).choice(P, size=n,
                                                     replace=False)] = True
    return mask


def jitter_streams(seeds, n: int, jitter: float, keys=None):
    """Per seed, the jitter of the next `n` events: (keys', plain, cs).

    Event i draws `sub_i` from `key, sub_i = split(key)`. An instruction
    jitters by `uniform(sub_i)`, a critical-section entry by
    `uniform(split(sub_i)[0])`. Computed on the host CPU.
    """
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        if keys is None:
            keys = jax.vmap(jax.random.PRNGKey)(
                jnp.asarray(np.asarray(seeds, np.int32)))
        fn = _stream_fn(n, float(jitter))
        keys, plain, cs = fn(jax.device_put(keys, cpu))
        return keys, np.asarray(plain), np.asarray(cs)


_STREAM_FNS = {}


def _stream_fn(n: int, jitter: float):
    import jax
    import jax.numpy as jnp

    if (n, jitter) in _STREAM_FNS:
        return _STREAM_FNS[(n, jitter)]

    def one(key):
        def body(k, _):
            k, sub = jax.random.split(k)
            return k, sub
        key, subs = jax.lax.scan(body, key, None, length=n)
        u = jax.vmap(lambda s: jax.random.uniform(
            s, (), jnp.float32, 0.0, jitter))
        cs = jax.vmap(lambda s: jax.random.uniform(
            jax.random.split(s)[0], (), jnp.float32, 0.0, jitter))
        return key, u(subs), cs(subs)

    fn = _STREAM_FNS[(n, jitter)] = jax.jit(jax.vmap(one))
    return fn


def pairwise_sum(x: np.ndarray) -> F:
    """float32 sum in the fixed pairwise order the configuration uses."""
    n = 1 << max(x.shape[0] - 1, 0).bit_length()
    x = np.concatenate([x.astype(F), np.zeros(n - x.shape[0], F)])
    while n > 1:
        n //= 2
        x = x[:n] + x[n:]
    return F(x[0])


class Machine:
    """Balanced N-level machine, the lock's words, and their latencies."""

    def __init__(self, cfg: dict):
        lock = cfg["lock"]
        self.P = P = int(lock["P"])
        fanout = [int(f) for f in lock["fanout"]]
        self.N = N = len(fanout) + 1
        n_elems = [1]
        for f in fanout:
            n_elems.append(n_elems[-1] * f)
        self.n_elems = n_elems
        per_leaf = P // n_elems[-1]
        # elem[lvl][p]: p's element at level lvl (0 = root).
        leaf = [p // per_leaf for p in range(P)]
        self.elem = [[lf // (n_elems[-1] // n_elems[lvl]) for lf in leaf]
                     for lvl in range(N)]
        self.T_DC = int(lock["T_DC"])
        self.T_L = [int(t) for t in lock["T_L"]]
        self.T_R = int(lock["T_R"])
        self.T_W = min(int(np.prod(np.asarray(self.T_L, np.int64))),
                       UNBOUNDED)
        cost = cfg["cost"]
        lat = np.asarray(cost["lat"], F)
        self.plain_by_dist = lat
        self.atomic_by_dist = (lat * cost["atomic_factor"]).astype(F)
        self.occupancy = F(cost["occupancy"])
        self.wake = F(cost["wake"])
        self.backoff0 = F(cost["backoff0"])
        self.backoff_max = F(cost["backoff_max"])
        self.jitter = float(cost["jitter"])
        self.is_writer = writer_mask(P, float(lock["writer_fraction"]),
                                     int(lock["role_seed"]))

        # Words: per level, NEXT and STATUS of each entity, TAIL of each
        # element; then ARRIVE and DEPART of each counter. An entity at
        # level lvl < N-1 is an element of level lvl+1, hosted on its
        # lowest rank; at the leaf it is the process itself.
        self.owner, self.init = [], []

        def alloc(owner, value):
            self.owner.append(owner)
            self.init.append(value)
            return len(self.owner) - 1

        def host(lvl, j):
            return j * (P // n_elems[lvl])

        self.next_w, self.status_w, self.tail_w = [], [], []
        for lvl in range(N):
            ents = P if lvl == N - 1 else n_elems[lvl + 1]
            hosts = (list(range(P)) if lvl == N - 1
                     else [host(lvl + 1, e) for e in range(ents)])
            self.next_w.append([alloc(h, NULL) for h in hosts])
            self.status_w.append([alloc(h, WAIT) for h in hosts])
            self.tail_w.append([alloc(host(lvl, j), NULL)
                                for j in range(n_elems[lvl])])
        ranks = list(range(0, P, self.T_DC))
        self.C = len(ranks)
        self.arrive_w = [alloc(r, 0) for r in ranks]
        self.depart_w = [alloc(r, 0) for r in ranks]
        self.ctr_of_p = [min(p // self.T_DC, self.C - 1) for p in range(P)]
        self.readers_of_ctr = [[] for _ in range(self.C)]
        for p in range(P):
            if not self.is_writer[p]:
                self.readers_of_ctr[self.ctr_of_p[p]].append(p)

    def ent(self, lvl, p):
        return p if lvl == self.N - 1 else self.elem[lvl + 1][p]

    def dist(self, p, q):
        if p == q:
            return 0
        if self.elem[self.N - 1][p] == self.elem[self.N - 1][q]:
            return 1
        for lvl in range(self.N):
            if self.elem[lvl][p] != self.elem[lvl][q]:
                return self.N - lvl + 1
        return 0

    def plain(self, p, w):
        d = min(self.dist(p, self.owner[w]), len(self.plain_by_dist) - 1)
        return self.plain_by_dist[d]

    def atomic(self, p, w):
        d = min(self.dist(p, self.owner[w]), len(self.atomic_by_dist) - 1)
        return self.atomic_by_dist[d]

    def same_leaf(self, p, q):
        return self.dist(p, q) <= 1


class Run:
    """One seed's schedule, simulated to completion."""

    def __init__(self, m: Machine, target_acq: int, max_events: int,
                 exclusive: bool = True):
        self.m, self.target, self.max_events = m, target_acq, max_events
        self.exclusive = exclusive
        P = m.P
        self.win = list(m.init)
        self.busy = [F(0)] * len(m.init)
        self.pc = [WA_PREP if m.is_writer[p] else R_BARRIER for p in range(P)]
        self.reg = [dict(L=m.N - 1, PRED=0, STATUS=0, NEXT_STAT=0, CRESET=0,
                         K=0, UL=0, BARRIER=0, TMP=0, SUCC=[0] * m.N)
                    for _ in range(P)]
        self.t_ready = [F(0)] * P
        self.blocked = [(-1, -1)] * P
        self.watchers = {}
        self.backoff = [m.backoff0] * P
        self.done = [False] * P
        self.left = P
        self.acq = [0] * P
        self.lat_sum = [F(0)] * P
        self.t_attempt = [F(0)] * P
        self.t_finish = F(0)
        self.events = 0
        self.writers_in = self.readers_in = 0
        self.violations = 0
        self.hold_rank = -1
        self.local_passes = self.total_passes = 0
        self.reclaims = self.retries = 0
        self.t_recover = INF
        self.heap = [(0.0, p) for p in range(P)]

    # ---------------------------------------------------------- engine
    def watch(self, p, a, b):
        for w in self.blocked[p]:
            if w >= 0:
                self.watchers[w].discard(p)
        self.blocked[p] = (a, b)
        for w in (a, b):
            if w >= 0:
                self.watchers.setdefault(w, set()).add(p)

    def finish(self, p, now, jit, dur, hot, mods, writes, next_pc,
               block=(-1, -1), reset_backoff=False, after=None):
        m = self.m
        busy_at = self.busy[hot] if hot >= 0 else F(0)
        start = max(now, busy_at)
        fin = F(F(start + dur) + jit)
        if hot >= 0:
            self.busy[hot] = F(start + m.occupancy)
        old = {w: self.win[w] for w in writes if w >= 0}
        for w, v in mods:
            self.win[w] = v
        self.watch(p, -1, -1)
        for w in writes:
            if w < 0 or old[w] == self.win[w]:
                continue
            for q in sorted(self.watchers.get(w, ())):
                if self.done[q]:
                    continue
                t = min(self.t_ready[q], F(fin + m.wake))
                self.watch(q, -1, -1)
                if t != self.t_ready[q]:
                    self.t_ready[q] = t
                    heapq.heappush(self.heap, (float(t), q))
        blocked = block[0] >= 0 or block[1] >= 0
        self.watch(p, *block)
        self.t_ready[p] = F(fin + (self.backoff[p] if blocked else F(0)))
        if blocked:
            self.backoff[p] = min(F(self.backoff[p] * F(2)), m.backoff_max)
        elif reset_backoff:
            self.backoff[p] = m.backoff0
        self.pc[p] = next_pc
        self.t_finish = max(self.t_finish, fin)
        self.events += 1
        if after is not None:
            after(fin)
        if not self.done[p]:
            heapq.heappush(self.heap, (float(self.t_ready[p]), p))

    def cs_enter(self, p, now):
        w = self.m.is_writer[p]
        if self.writers_in > 0 or (w and self.readers_in > 0):
            self.violations += 1
        if w:
            self.writers_in += 1
        else:
            self.readers_in += 1
        self.lat_sum[p] = F(self.lat_sum[p] + F(now - self.t_attempt[p]))
        if self.hold_rank >= 0 and self.m.same_leaf(self.hold_rank, p):
            self.local_passes += 1
        self.hold_rank = p
        self.total_passes += 1

    def cs_exit(self, p):
        if self.m.is_writer[p]:
            self.writers_in -= 1
        else:
            self.readers_in -= 1

    def quiescent(self, c):
        return all(self.done[q] or self.pc[q] in READER_FREE
                   for q in self.m.readers_of_ctr[c])

    def running(self) -> bool:
        return self.left > 0 and self.events < self.max_events

    def advance(self, plain, cs, base):
        """Run events base .. base + len(plain) - 1 of this schedule."""
        end = base + len(plain)
        heap = self.heap
        while self.running() and self.events < end:
            t, p = heapq.heappop(heap)
            if self.done[p] or t != float(self.t_ready[p]):
                continue
            i = self.events - base
            self.step(p, self.t_ready[p], F(plain[i]), F(cs[i]))

    def acquired(self, p, fin):
        self.t_attempt[p] = fin

    def count_acquire(self, p):
        self.acq[p] += 1
        if self.acq[p] >= self.target and not self.done[p]:
            self.done[p] = True
            self.left -= 1

    # ---------------------------------------------------- instructions
    def step(self, p, now, jit, jit_cs):
        m, r, win = self.m, self.reg[p], self.win
        pc, N = self.pc[p], m.N
        lvl = r["L"]
        if pc == WA_PREP:
            e = m.ent(lvl, p)
            sw = m.status_w[lvl][e]
            self.finish(p, now, jit, F(2.0) * m.plain(p, sw), -1,
                        [(m.next_w[lvl][e], NULL), (sw, WAIT)], [], WA_ENQ)
        elif pc == WA_ENQ:
            e = m.ent(lvl, p)
            t = m.tail_w[lvl][m.elem[lvl][p]]
            pred = win[t]
            r["PRED"], r["K"] = pred, 0
            if pred == NULL or pred == e:
                nxt = W_SCTW_FLAG if lvl == 0 else WA_START_PARENT
            else:
                nxt = WA_LINK
            self.finish(p, now, jit, m.atomic(p, t), t, [(t, e)], [t], nxt)
        elif pc == WA_LINK:
            w = m.next_w[lvl][r["PRED"]]
            self.finish(p, now, jit, m.plain(p, w), -1, [(w, m.ent(lvl, p))],
                        [w], WA_SPIN)
        elif pc == WA_SPIN:
            w = m.status_w[lvl][m.ent(lvl, p)]
            s = r["STATUS"] = win[w]
            if s == WAIT:
                nxt = WA_SPIN
            elif s == ACQUIRE_PARENT:
                nxt = WA_START_PARENT
            elif lvl == 0 and s == MODE_CHANGE:
                nxt = W_SCTW_FLAG
            else:
                nxt = CS
            self.finish(p, now, jit, m.plain(p, w), -1, [], [], nxt,
                        block=(w if s == WAIT else -1, -1))
        elif pc == WA_START_PARENT:
            w = m.status_w[lvl][m.ent(lvl, p)]
            r["L"] = lvl if lvl == 0 else lvl - 1
            self.finish(p, now, jit, m.plain(p, w), -1, [(w, ACQUIRE_START)],
                        [w], CS if lvl == 0 else WA_PREP)
        elif pc == W_SCTW_FLAG:
            k = r["K"]
            w = m.arrive_w[k]
            arr = win[w]
            last = k + 1 >= m.C
            r["K"] = 0 if last else k + 1
            nxt = W_SCTW_VERIFY if last else W_SCTW_FLAG
            self.finish(p, now, jit, m.atomic(p, w), w,
                        [(w, arr if arr >= WRITE_FLAG else arr + WRITE_FLAG)],
                        [w], nxt)
        elif pc == W_SCTW_VERIFY:
            k = r["K"]
            wa, wd = m.arrive_w[k], m.depart_w[k]
            clear = win[wa] - WRITE_FLAG == win[wd]
            stale = not clear and self.quiescent(k)
            last = k + 1 >= m.C
            if clear:
                r["K"] = 0 if last else k + 1
                nxt = WA_START_PARENT if last else W_SCTW_VERIFY
            else:
                nxt = REC_DRAIN if stale else W_SCTW_VERIFY
            block = (-1, -1) if clear or stale else (wa, wd)
            self.finish(p, now, jit, F(2.0) * m.plain(p, wa), -1, [], [], nxt,
                        block=block)
        elif pc == CS:
            self.cs_enter(p, now)
            r["L"], r["UL"] = N - 1, N
            self.finish(p, now, jit_cs, F(0), -1, [], [],
                        ROOT_DECIDE if N == 1 else WR_READ,
                        reset_backoff=True)
        elif pc == WR_READ:
            if N > 1 and lvl == N - 1:
                self.cs_exit(p)
            e = m.ent(lvl, p)
            sw = m.status_w[lvl][e]
            r["SUCC"][lvl] = win[m.next_w[lvl][e]]
            r["STATUS"] = win[sw]
            self.finish(p, now, jit, F(2.0) * m.plain(p, sw), -1, [], [],
                        WR_DECIDE)
        elif pc == WR_DECIDE:
            succ = r["SUCC"][lvl]
            can_pass = (succ != NULL and r["STATUS"] < m.T_L[lvl]
                        and lvl > 0)
            w = m.status_w[lvl][succ if succ != NULL else 0]
            if can_pass:
                r["UL"] = lvl + 1
                self.finish(p, now, jit, m.plain(p, w), -1,
                            [(w, r["STATUS"] + 1)], [w], UNW_CHECK)
            else:
                r["L"] = lvl - 1
                self.finish(p, now, jit, F(0.02), -1, [], [w],
                            WR_READ if lvl - 1 >= 1 else ROOT_DECIDE)
        elif pc == ROOT_DECIDE:
            if N == 1:
                self.cs_exit(p)
            sw = m.status_w[0][m.ent(0, p)]
            stat = win[sw]
            r["STATUS"], r["NEXT_STAT"], r["CRESET"] = stat, stat + 1, 0
            r["K"], r["TMP"] = 0, ROOT_GETSUCC
            nxt = ROOT_RESET if stat + 1 >= m.T_W else ROOT_GETSUCC
            self.finish(p, now, jit, m.plain(p, sw), -1, [], [], nxt)
        elif pc in (ROOT_RESET, R_RESET, R_RECOVER):
            if pc == ROOT_RESET:
                k = r["K"]
            else:
                k = m.ctr_of_p[p]
            wa, wd = m.arrive_w[k], m.depart_w[k]
            arr, dep = win[wa], win[wd]
            dur = F(F(2.0) * m.plain(p, wa)) + F(F(2.0) * m.atomic(p, wa))
            if pc == ROOT_RESET:
                flag = WRITE_FLAG if arr >= WRITE_FLAG else 0
                mods = [(wa, arr - dep - flag), (wd, 0)]
                last = k + 1 >= m.C
                r["K"] = 0 if last else k + 1
                if last:
                    r["NEXT_STAT"], r["CRESET"] = MODE_CHANGE, 1
                nxt = r["TMP"] if last else ROOT_RESET
            else:
                mods = [(wa, arr - dep), (wd, 0)]
                r["BARRIER"] = 0
                nxt = R_BACKOFF if pc == R_RESET else R_BARRIER
            self.finish(p, now, jit, dur, wa, mods, [wa, wd], nxt)
        elif pc == ROOT_GETSUCC:
            nw = m.next_w[0][m.ent(0, p)]
            succ = r["SUCC"][0] = win[nw]
            r["K"], r["TMP"] = 0, ROOT_CAS
            if succ != NULL:
                nxt = ROOT_PASS
            elif r["CRESET"] == 0:
                nxt = ROOT_RESET
            else:
                nxt = ROOT_CAS
            self.finish(p, now, jit, m.plain(p, nw), -1, [], [], nxt)
        elif pc == ROOT_CAS:
            t = m.tail_w[0][m.elem[0][p]]
            ok = win[t] == m.ent(0, p)
            r["UL"] = 1
            self.finish(p, now, jit, m.atomic(p, t), t,
                        [(t, NULL if ok else win[t])], [t],
                        UNW_CHECK if ok else ROOT_WAITSUCC)
        elif pc in (ROOT_WAITSUCC, UNW_WAIT):
            ul = 0 if pc == ROOT_WAITSUCC else min(r["UL"], N - 1)
            w = m.next_w[ul][m.ent(ul, p)]
            succ = r["SUCC"][ul] = win[w]
            later = ROOT_WAITSUCC if pc == ROOT_WAITSUCC else UNW_WAIT
            found = ROOT_PASS if pc == ROOT_WAITSUCC else UNW_PUT
            self.finish(p, now, jit, m.plain(p, w), -1, [], [],
                        later if succ == NULL else found,
                        block=(w if succ == NULL else -1, -1))
        elif pc == ROOT_PASS:
            w = m.status_w[0][r["SUCC"][0]]
            r["UL"] = 1
            self.finish(p, now, jit, m.plain(p, w), -1,
                        [(w, r["NEXT_STAT"])], [w], UNW_CHECK)
        elif pc == UNW_CHECK:
            ul = r["UL"]
            fin = ul > N - 1
            ulc = min(ul, N - 1)
            succ = r["SUCC"][ulc]
            t = m.tail_w[ulc][m.elem[ulc][p]]
            do_cas = not fin and succ == NULL
            cas_ok = do_cas and win[t] == m.ent(ulc, p)
            if fin or cas_ok:
                r["UL"] = ul + (0 if fin else 1)
            if fin:
                nxt = DONE_ONE
            elif succ != NULL:
                nxt = UNW_PUT
            else:
                nxt = UNW_CHECK if cas_ok else UNW_WAIT
            self.finish(p, now, jit, m.atomic(p, t) if do_cas else F(0.02),
                        t if do_cas else -1,
                        [(t, NULL)] if cas_ok else [], [t], nxt)
        elif pc == UNW_PUT:
            ul = min(r["UL"], N - 1)
            w = m.status_w[ul][r["SUCC"][ul]]
            r["UL"] = ul + 1
            self.finish(p, now, jit, m.plain(p, w), -1,
                        [(w, ACQUIRE_PARENT)], [w], UNW_CHECK)
        elif pc in (DONE_ONE, R_DONE):
            self.count_acquire(p)
            if pc == DONE_ONE:
                r["L"], r["CRESET"], r["K"] = N - 1, 0, 0
            else:
                r["BARRIER"] = 0
            self.finish(p, now, jit, F(0), -1, [], [],
                        WA_PREP if pc == DONE_ONE else R_BARRIER,
                        after=lambda fin: self.acquired(p, fin))
        elif pc == R_BARRIER:
            wa = m.arrive_w[m.ctr_of_p[p]]
            t = m.tail_w[0][0]
            over = r["BARRIER"] == 1 and win[wa] >= m.T_R
            recover = over and win[t] == NULL
            barred = over and not recover
            nxt = R_RECOVER if recover else (R_BARRIER if barred else R_FAO)
            dur = (F(m.plain(p, wa) + m.plain(p, t)) if r["BARRIER"] == 1
                   else F(0.02))
            self.finish(p, now, jit, dur, -1, [], [], nxt,
                        block=(wa, t) if barred else (-1, -1))
        elif pc == R_FAO:
            wa = m.arrive_w[m.ctr_of_p[p]]
            ret = win[wa]
            if ret < m.T_R or not self.exclusive:
                nxt = R_CS
            else:
                r["BARRIER"] = 1
                nxt = R_CHECK_TAIL if ret == m.T_R else R_BACKOFF
            self.finish(p, now, jit, m.atomic(p, wa), wa, [(wa, ret + 1)],
                        [wa], nxt)
        elif pc == R_CHECK_TAIL:
            t = m.tail_w[0][0]
            self.finish(p, now, jit, m.plain(p, t), -1, [], [],
                        R_RESET if win[t] == NULL else R_BACKOFF)
        elif pc == R_BACKOFF:
            wa = m.arrive_w[m.ctr_of_p[p]]
            self.finish(p, now, jit, m.atomic(p, wa), wa,
                        [(wa, win[wa] - 1)], [wa], R_BARRIER)
        elif pc == R_CS:
            self.cs_enter(p, now)
            self.finish(p, now, jit_cs, F(0), -1, [], [], R_RELEASE,
                        reset_backoff=True)
        elif pc == R_RELEASE:
            wd = m.depart_w[m.ctr_of_p[p]]
            self.cs_exit(p)
            self.finish(p, now, jit, m.atomic(p, wd), wd,
                        [(wd, win[wd] + 1)], [wd], R_DONE)
        elif pc == REC_DRAIN:
            k = r["K"]
            wa, wd = m.arrive_w[k], m.depart_w[k]
            arr, dep = win[wa], win[wd]
            clear = arr - WRITE_FLAG == dep
            stale = not clear and self.quiescent(k)
            fixed = clear or stale
            last = k + 1 >= m.C
            if fixed:
                r["K"] = 0 if last else k + 1
            dur = F(F(2.0) * m.plain(p, wa)) + m.atomic(p, wa)

            def recovered(fin):
                self.reclaims += int(stale)
                self.retries += int(not fixed)
                if stale:
                    self.t_recover = min(self.t_recover, fin)

            self.finish(p, now, jit, dur, wa,
                        [(wa, dep + WRITE_FLAG if stale else arr)], [wa],
                        WA_START_PARENT if fixed and last else W_SCTW_VERIFY,
                        after=recovered)
        else:
            raise Unsupported(f"pc {pc} is outside the fault-free protocol")

    def metrics(self) -> dict:
        total = sum(self.acq)
        mk = max(self.t_finish, F(1e-6))
        return dict(
            completed=all(self.done), violations=self.violations,
            makespan=float(mk), total_acquires=total,
            mean_latency=float(pairwise_sum(np.asarray(self.lat_sum, F))
                               / F(max(total, 1))),
            throughput=float(F(total) / F(mk * F(1e-6))),
            events=self.events,
            locality=float(F(self.local_passes)
                           / F(max(self.total_passes, 1))),
            per_proc_acq=np.asarray(self.acq, np.int64),
            n_crashed=0, reclaims=self.reclaims,
            recovery_retries=self.retries)


def run(cfg: dict, seeds, exclusive: bool = True,
        chunk: int = 1 << 15) -> list:
    """Simulate each seed of the configuration `cfg`; one dict per seed."""
    lock = cfg["lock"]
    if lock["kind"] != "rma_rw":
        raise Unsupported(f"the reference models rma_rw, not {lock['kind']}")
    wl = cfg["workload"]
    if wl["cs_kind"] != 0 or wl["think"]:
        raise Unsupported("the reference models the empty critical "
                          "section without think time")
    m = Machine(cfg)
    seeds = list(seeds)
    runs = [Run(m, int(wl["target_acq"]), int(wl["max_events"]),
                exclusive) for _ in seeds]
    keys, base = None, 0
    while any(sim.running() for sim in runs):
        keys, plain, cs = jitter_streams(seeds, chunk, m.jitter, keys=keys)
        for s, sim in enumerate(runs):
            sim.advance(plain[s], cs[s], base)
        base += chunk
    return [sim.metrics() for sim in runs]
