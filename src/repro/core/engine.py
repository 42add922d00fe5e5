"""Jitted discrete-event simulator for distributed RMA lock protocols.

Execution model (DESIGN.md §2.1): every protocol is compiled to a list
of *instructions* — atomic protocol actions consisting of one or a few
RMA operations (the paper always pairs ops with a Flush, so an
instruction's latency is the round-trip of its constituent ops). Each
process owns a program counter and a register file. The simulator is a
single `lax.while_loop`: per event it picks the process with the
smallest ready-time and executes its current instruction through
`lax.switch`. Atomicity of FAO/CAS is inherited from the
one-event-at-a-time semantics; *contention* is modeled by an occupancy
charge serializing atomics on a hot word; *spinning* is modeled by
block-on-word with wake-on-write (plus an exponential-backoff timeout so
no schedule can livelock the simulation) — semantically identical to the
paper's spin loops but O(1) events per wait.

Schedule randomization: every instruction duration receives seeded
uniform jitter. `vmap` over seeds yields thousands of distinct
interleavings per configuration — our executable analogue of the paper's
SPIN model checking (§4.4), used by the property tests. The exhaustive
counterpart lives in `repro.analysis`: a static analyzer + small-P model
checker over these same instruction handlers
(`python -m repro.analysis.locklint --all`), plus an opt-in runtime
sanitizer here (`REPRO_CHECKS=1` or `runtime_checks(True)`) that routes
the single-run simulation paths through `jax.experimental.checkify`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify

from repro.core import spans
from repro.core.cost import CostModel, DEFAULT_COST
from repro.core.topology import Machine, proc_distance_matrix
from repro.core.window import Layout, padded_level_table

INF = jnp.float32(3.4e38)

# ---------------------------------------------------------------------------
# Opt-in runtime sanitizer. When enabled (REPRO_CHECKS=1 in the
# environment, or `with runtime_checks(True):`), the single-dispatch run
# paths (`run_sim` / `run_sim_batch`) are traced through
# `jax.experimental.checkify` with index checks plus the protocol
# assertions below — every gather/scatter index is validated and
# `finish_instr`'s declared effects (hot word, write set, watch words)
# are bounds-checked and checked against the padded dead-counter slots.
# The static counterpart is `repro.analysis.locklint`. Off by default:
# checkify adds error plumbing through the while_loop carry and roughly
# doubles compile time, so production sweeps never pay for it.

_RUNTIME_CHECKS_OVERRIDE: bool | None = None
# True only while tracing a checkified variant — gates the
# checkify.check calls so the plain (fast) trace contains none of them.
_SANITIZE_TRACING = False


def checks_enabled() -> bool:
    """Whether runs should go through the checkify sanitizer."""
    if _RUNTIME_CHECKS_OVERRIDE is not None:
        return _RUNTIME_CHECKS_OVERRIDE
    return os.environ.get("REPRO_CHECKS", "0").lower() not in (
        "", "0", "false", "no")


@contextlib.contextmanager
def runtime_checks(enable: bool = True):
    """Force the runtime sanitizer on (or off) within a scope,
    overriding the REPRO_CHECKS environment variable."""
    global _RUNTIME_CHECKS_OVERRIDE
    prev = _RUNTIME_CHECKS_OVERRIDE
    _RUNTIME_CHECKS_OVERRIDE = bool(enable)
    try:
        yield
    finally:
        _RUNTIME_CHECKS_OVERRIDE = prev


def _sanitize_word(env: "Env", what: str, w, *, allow_none: bool):
    """checkify assertions for one declared word operand of an
    instruction: in [-1, W) (-1 = "none" where allowed) and never one of
    the padded dead counter slots (ctr_mask == False)."""
    w = jnp.asarray(w, jnp.int32)
    W = env.owner.shape[0]
    lo = -1 if allow_none else 0
    checkify.check((w >= lo) & (w < W),
                   what + " word {w} outside [" + str(lo) + ", W)", w=w)
    dead = (jnp.any((env.arrive_w == w) & ~env.ctr_mask)
            | jnp.any((env.depart_w == w) & ~env.ctr_mask))
    checkify.check(~dead, what + " word {w} is a padded dead counter slot",
                   w=w)


class SimState(NamedTuple):
    window: jnp.ndarray      # int32 [W]
    pc: jnp.ndarray          # int32 [P]
    regs: jnp.ndarray        # int32 [P, R]
    t_ready: jnp.ndarray     # float32 [P]
    blocked_a: jnp.ndarray   # int32 [P]  (watched word or -1)
    blocked_b: jnp.ndarray   # int32 [P]
    backoff: jnp.ndarray     # float32 [P]
    busy: jnp.ndarray        # float32 [W]
    clock: jnp.ndarray       # float32 [] start time of the latest event
    t_finish: jnp.ndarray    # float32 [] max instruction *finish* time
    done: jnp.ndarray        # bool [P]
    events: jnp.ndarray      # int32 []
    # metrics
    acq_count: jnp.ndarray   # int32 [P]
    lat_sum: jnp.ndarray     # float32 [P]
    t_attempt: jnp.ndarray   # float32 [P]
    writer_active: jnp.ndarray  # int32 []
    reader_active: jnp.ndarray  # int32 []
    violations: jnp.ndarray  # int32 []
    hold_rank: jnp.ndarray   # int32 [] rank of last CS enterer (locality stats)
    local_passes: jnp.ndarray   # int32 [] CS handoffs that stayed on-node
    total_passes: jnp.ndarray   # int32 []
    # crash-fault state (FaultPlan; all traced so crash times can vary
    # across vmapped runs without recompiling)
    crash_t: jnp.ndarray     # float32 [P] planned, then ACTUAL crash time (INF = never)
    revive_t: jnp.ndarray    # float32 [P] revive time (INF = never)
    crashed: jnp.ndarray     # bool [P]
    in_cs: jnp.ndarray       # int32 [P] 0 = out, 1 = reader in CS, 2 = writer in CS
    restart_pc: jnp.ndarray  # int32 [P] pc restored on revive
    restart_regs: jnp.ndarray   # int32 [P, R] register file restored on revive
    # recovery metrics
    reclaims: jnp.ndarray    # int32 [] abandoned nodes/words reclaimed
    rec_retries: jnp.ndarray    # int32 [] bounded-retry re-checks on stale words
    t_recover: jnp.ndarray   # float32 [] first successful reclaim time (INF = none)


@dataclasses.dataclass(frozen=True)
class Env:
    """Static (traced-constant) simulation environment shared by handlers."""

    P: int
    N: int
    plain: jnp.ndarray        # [P, P] plain op latency
    atomic: jnp.ndarray       # [P, P] atomic op latency
    owner: jnp.ndarray        # [W]
    next_t: jnp.ndarray       # [N, maxE] word tables
    status_t: jnp.ndarray     # [N, maxE]
    tail_t: jnp.ndarray       # [N, maxJ]
    arrive_w: jnp.ndarray     # [C_pad]
    depart_w: jnp.ndarray     # [C_pad]
    ctr_rank: jnp.ndarray     # [C_pad]
    ctr_of_p: jnp.ndarray     # [P]
    # Traced counter validity mask ([C_pad] bool; False = padded slot).
    # Replaces the old static `int C`: the number of live counters is a
    # VALUE, not a shape, so T_DC points share one compiled program.
    ctr_mask: jnp.ndarray
    # Scratch word indices ([extra_words]) — traced for the same
    # reason: absolute positions shift with counter padding, so
    # programs (the foMPI baselines) must read them from the env.
    scratch_w: jnp.ndarray
    ent_of_p: jnp.ndarray     # [N, P]
    elem_of_p: jnp.ndarray    # [N, P]
    same_leaf: jnp.ndarray    # [P, P] bool (locality statistics)
    T_L: jnp.ndarray          # [N] per-level local-pass thresholds (index 0 = root)
    T_R: int
    T_W: int
    is_writer: jnp.ndarray    # [P] bool
    target_acq: int
    cs_kind: int              # 0 empty, 1 single-op, 2 random 1-4us workload
    think: bool               # wait-after-release 1-4us (WARB)
    cost: CostModel
    # Lease duration (us): a process is presumed dead once it has been
    # crashed for `lease` simulated us (a live process always renews its
    # lease before expiry, so the simulator's oracle is exact).
    lease: float = 2.0

    def lat_plain(self, p, word):
        return self.plain[p, self.owner[word]]

    def lat_atomic(self, p, word):
        return self.atomic[p, self.owner[word]]

    @property
    def n_ctr(self):
        """Number of live counters — a traced value (the counter loops'
        bound), constant-folded when ctr_mask is concrete."""
        return jnp.sum(self.ctr_mask.astype(jnp.int32))


# Handler signature: (env, p, now, key, st) -> SimState
Handler = Callable


class FaultPlan(NamedTuple):
    """Crash-fault injection plan: crash process p at simulated time
    crash_t[p] (INF = never), optionally revive it at revive_t[p].

    Threaded through `SimState` as traced arrays, so a vmapped batch can
    run a different plan per lane without recompiling: one compiled
    program serves every fault point. A run given no plan at all cannot
    crash, and compiles the crash-free loop instead (`step_loop`).
    `lanes` builds one plan per lane of a batch (leaves [S, P]), as
    `Session.run_batch(faults=)` takes them.

    A crash takes effect at the victim's next scheduling point at or
    after crash_t — the victim's current instruction is NOT executed
    (its window words go stale exactly as a real process dying between
    RMA ops), its CS
    occupancy is released for accounting (the lease/fencing assumption:
    a dead holder's effects are fenced once its lease expires), and it
    never runs again until revive_t. A victim that has finished its
    acquires by crash_t has no such scheduling point and never crashes.
    """

    crash_t: jnp.ndarray    # float32 [P]
    revive_t: jnp.ndarray   # float32 [P]

    @classmethod
    def none(cls, P: int) -> "FaultPlan":
        return cls(jnp.full(P, INF, jnp.float32),
                   jnp.full(P, INF, jnp.float32))

    @classmethod
    def single(cls, P: int, victim: int, t: float,
               revive_at: float | None = None) -> "FaultPlan":
        plan = cls.none(P)
        crash = plan.crash_t.at[victim].set(jnp.float32(t))
        revive = plan.revive_t
        if revive_at is not None:
            revive = revive.at[victim].set(jnp.float32(revive_at))
        return cls(crash, revive)

    @classmethod
    def lanes(cls, P: int, victims, times) -> "FaultPlan":
        """One plan per lane: lane s crashes process victims[s] at
        times[s] and never revives it. Leaves are [S, P] host arrays."""
        victims = np.asarray(victims)
        times = np.asarray(times, np.float32)
        if victims.ndim != 1 or victims.shape != times.shape:
            raise ValueError(f"victims {victims.shape} and times "
                             f"{times.shape} must be one entry per lane")
        if victims.size and not (0 <= victims.min() and victims.max() < P):
            raise ValueError(f"victims must lie in [0, {P})")
        crash = np.full((victims.size, P), float(INF), np.float32)
        crash[np.arange(victims.size), victims] = times
        return cls(crash, np.full_like(crash, float(INF)))


def leases_expired(env: Env, st: SimState, now) -> jnp.ndarray:
    """[P] bool: process q crashed and its lease ran out at least
    `env.lease` simulated us ago. Recovery guards in the protocol
    programs must key every reclaim off this predicate — never off
    `st.crashed` directly — so the engine and the model checker agree
    on when a survivor may presume a peer dead."""
    return st.crashed & (now >= st.crash_t + env.lease)


def finish_instr(env: Env, st: SimState, p, now, key, *, dur, hot_word,
                 writes: Sequence, next_pc, regs_row,
                 block_a=None, block_b=None, window=None,
                 reset_backoff: bool = False,
                 extra: Callable = None) -> SimState:
    """Common bookkeeping tail of every instruction handler.

    writes: list of word indices written (watchers get woken).
    hot_word: word whose occupancy serializes this op (-1 = none).
    block_a/b: words to (re)watch; None = not blocked.
    """
    dur = jnp.asarray(dur, jnp.float32)
    jit_amt = jax.random.uniform(key, (), jnp.float32, 0.0, env.cost.jitter)
    hot = jnp.asarray(hot_word, jnp.int32)
    if _SANITIZE_TRACING:
        _sanitize_word(env, "hot", hot, allow_none=True)
        for w in writes:
            _sanitize_word(env, "write", w, allow_none=True)
        if block_a is not None:
            _sanitize_word(env, "block_a", block_a, allow_none=True)
        if block_b is not None:
            _sanitize_word(env, "block_b", block_b, allow_none=True)
        checkify.check(dur >= 0, "negative instruction duration {d}", d=dur)
    busy_at = jnp.where(hot >= 0, st.busy[jnp.maximum(hot, 0)], jnp.float32(0))
    start = jnp.maximum(now, busy_at)
    finish = start + dur + jit_amt
    busy = st.busy
    busy = jnp.where(hot >= 0, busy.at[jnp.maximum(hot, 0)].set(
        start + env.cost.occupancy), busy)

    window = st.window if window is None else window
    t_ready = st.t_ready
    blocked_a, blocked_b = st.blocked_a, st.blocked_b
    # The executing process always sheds its stale watch state first
    # (it may have been woken by timeout rather than by a write).
    blocked_a = blocked_a.at[p].set(-1)
    blocked_b = blocked_b.at[p].set(-1)
    # Wake watchers of written words — but only if the stored value
    # actually changed (a spinner only observes changes; a failed CAS or
    # an idempotent Put must not wake the herd). A -1 entry means "no
    # write this time" (data-dependent write sets); it must not match
    # the -1 in blocked_a/b, which marks a process as NOT blocked.
    for w in writes:
        w = jnp.asarray(w, jnp.int32)
        ws = jnp.maximum(w, 0)
        changed = (st.window[ws] != window[ws]) & (w >= 0)
        # Crashed processes must never be woken: a parked (crashed
        # forever) process sits at t_ready == INF and a crash-with-
        # revive one at exactly revive_t; a wake would pull either
        # forward and resurrect the dead.
        hit = ((blocked_a == w) | (blocked_b == w)) & (~st.done) \
            & (~st.crashed) & changed
        t_ready = jnp.where(hit, jnp.minimum(t_ready, finish + env.cost.wake),
                            t_ready)
        blocked_a = jnp.where(hit, -1, blocked_a)
        blocked_b = jnp.where(hit, -1, blocked_b)

    # block_a/block_b are runtime values: -1 (or None) means "not blocked".
    ba = jnp.asarray(-1 if block_a is None else block_a, jnp.int32)
    bb = jnp.asarray(-1 if block_b is None else block_b, jnp.int32)
    blocked_now = (ba >= 0) | (bb >= 0)
    blocked_a = blocked_a.at[p].set(ba)
    blocked_b = blocked_b.at[p].set(bb)
    t_ready = t_ready.at[p].set(
        finish + jnp.where(blocked_now, st.backoff[p], 0.0))
    # Exponential backoff semantics of a retry loop: grow while blocked,
    # persist across the loop's non-blocking instructions, reset only on
    # success (CS entry) — otherwise centralized locks livelock instead
    # of degrading, and we could not reproduce the paper's §5 contrasts.
    kept = env.cost.backoff0 if reset_backoff else st.backoff[p]
    backoff = st.backoff.at[p].set(
        jnp.where(blocked_now,
                  jnp.minimum(st.backoff[p] * 2.0, env.cost.backoff_max),
                  kept))

    st = st._replace(
        window=window, pc=st.pc.at[p].set(jnp.asarray(next_pc, jnp.int32)),
        regs=st.regs.at[p].set(regs_row), t_ready=t_ready,
        blocked_a=blocked_a, blocked_b=blocked_b, backoff=backoff,
        busy=busy, clock=now,
        # Makespan accounting: the simulation ends when the last
        # instruction FINISHES, not when it starts — `clock` alone
        # under-reports by one instruction latency.
        t_finish=jnp.maximum(st.t_finish, finish),
        events=st.events + 1)
    if extra is not None:
        st = extra(st, finish)
    return st


def cs_enter(env: Env, st: SimState, p, now) -> SimState:
    """Mutual-exclusion accounting at CS entry."""
    w = env.is_writer[p]
    viol = jnp.where(
        (st.writer_active > 0) | (w & (st.reader_active > 0)), 1, 0)
    # Clamp before the gather: -1 ("no holder yet") is masked out below,
    # so the wrapped row must never be fetched (it would also trip the
    # sanitizer's index checks).
    hr = jnp.maximum(st.hold_rank, 0)
    same = env.same_leaf[hr, p] & (st.hold_rank >= 0)
    return st._replace(
        violations=st.violations + viol,
        writer_active=st.writer_active + jnp.where(w, 1, 0),
        reader_active=st.reader_active + jnp.where(w, 0, 1),
        in_cs=st.in_cs.at[p].set(jnp.where(w, 2, 1)),
        lat_sum=st.lat_sum.at[p].add(now - st.t_attempt[p]),
        hold_rank=jnp.asarray(p, jnp.int32),
        local_passes=st.local_passes + jnp.where(same, 1, 0),
        total_passes=st.total_passes + 1)


def cs_exit(env: Env, st: SimState, p) -> SimState:
    w = env.is_writer[p]
    return st._replace(
        writer_active=st.writer_active - jnp.where(w, 1, 0),
        reader_active=st.reader_active - jnp.where(w, 0, 1),
        in_cs=st.in_cs.at[p].set(0))


def cs_duration(env: Env, key, p):
    if env.cs_kind == 0:
        return jnp.float32(0.0)
    if env.cs_kind == 1:
        return jnp.float32(env.cost.lat[2])  # one remote memory access
    return jax.random.uniform(key, (), jnp.float32, 1.0, 4.0)


def think_duration(env: Env, key):
    if not env.think:
        return jnp.float32(0.0)
    return jax.random.uniform(key, (), jnp.float32, 1.0, 4.0)


class Metrics(NamedTuple):
    completed: jnp.ndarray       # bool: every SURVIVOR reached its target
    violations: jnp.ndarray      # int: mutual-exclusion violations (must be 0)
    makespan: jnp.ndarray        # float: total simulated time (us)
    total_acquires: jnp.ndarray  # int
    mean_latency: jnp.ndarray    # float us per acquire
    throughput: jnp.ndarray      # acquires per second
    events: jnp.ndarray
    locality: jnp.ndarray        # fraction of CS handoffs staying on-node
    per_proc_acq: jnp.ndarray    # [P]
    # fault/recovery metrics (identity values on fault-free runs)
    n_crashed: jnp.ndarray       # int: processes crashed at the end
    reclaims: jnp.ndarray        # int: abandoned nodes/words reclaimed
    recovery_retries: jnp.ndarray   # int: stale-word re-check retries
    t_recover: jnp.ndarray       # float us: first reclaim time (INF = none)
    t_crash: jnp.ndarray         # float us: first actual crash time (INF = none)


def derive_tw(T_L) -> int:
    """Total writer batch T_W = prod(T_L), clamped to the unbounded
    sentinel. Single source of truth for make_env and swept T_L points."""
    T_L = np.asarray(T_L, np.int32)
    return int(np.minimum(np.prod(T_L.astype(np.int64)), 1 << 26))


MEMO_MAX_ENTRIES = 8


def memoized_build(cache: dict, env: Env, builder,
                   max_entries: int = MEMO_MAX_ENTRIES):
    """Per-env handler memoization shared by the program classes.

    Keyed by id but holding the env ref: the entry pins the object
    alive, so a freed-and-reused id can never alias a stale entry.
    Bounded LRU (most recent `max_entries` envs) so a program object
    streaming many envs through `build()` does not itself pin every env
    (and its device arrays) it ever saw. Scope of that bound: handlers
    that were *executed* through the jitted `_run`/`_run_batch` entry
    points stay referenced by JAX's own jit cache (they are static
    args) regardless of eviction here, and re-building an evicted env
    produces fresh closures, i.e. a recompile — callers that alternate
    more than `max_entries` live envs through ONE program should hold
    their own handler refs (as `Session` does) or raise the bound.
    Sweep/grid tracing is unaffected: it uses `_build` directly.
    """
    key = id(env)
    cached = cache.get(key)
    if cached is not None and cached[0] is env:
        cache[key] = cache.pop(key)       # refresh LRU position
        return cached[1]
    handlers = builder(env)
    cache.pop(key, None)                  # stale id-reuse entry, if any
    cache[key] = (env, handlers)
    while len(cache) > max_entries:
        cache.pop(next(iter(cache)))
    return handlers


def make_env(m: Machine, layout: Layout, *, T_L=None, T_R=1 << 26,
             is_writer=None, target_acq=8, cs_kind=0, think=False,
             cost: CostModel = DEFAULT_COST, lease: float = 2.0) -> Env:
    dist = proc_distance_matrix(m)
    plain, atomic = cost.tables(dist)
    if T_L is None:
        T_L = np.full(m.N, 1 << 26, np.int32)
    T_L = np.asarray(T_L, np.int32)
    T_W = derive_tw(T_L)
    if is_writer is None:
        is_writer = np.ones(m.P, bool)
    same_leaf = dist <= 1
    return Env(
        P=m.P, N=m.N,
        plain=jnp.asarray(plain), atomic=jnp.asarray(atomic),
        owner=jnp.asarray(layout.owner),
        next_t=jnp.asarray(padded_level_table(layout, "next_w")),
        status_t=jnp.asarray(padded_level_table(layout, "status_w")),
        tail_t=jnp.asarray(padded_level_table(layout, "tail_w")),
        arrive_w=jnp.asarray(layout.arrive_w),
        depart_w=jnp.asarray(layout.depart_w),
        ctr_rank=jnp.asarray(layout.ctr_rank),
        ctr_of_p=jnp.asarray(layout.ctr_of_p),
        ctr_mask=jnp.asarray(layout.ctr_mask),
        scratch_w=jnp.asarray(layout.scratch_w),
        ent_of_p=jnp.asarray(layout.ent_of_p),
        elem_of_p=jnp.asarray(layout.elem_of_p),
        same_leaf=jnp.asarray(same_leaf),
        T_L=jnp.asarray(T_L), T_R=int(T_R), T_W=T_W,
        is_writer=jnp.asarray(is_writer), target_acq=int(target_acq),
        cs_kind=int(cs_kind), think=bool(think), cost=cost,
        lease=float(lease))


def init_state(env: Env, layout: Layout, init_pc: np.ndarray,
               n_regs: int, init_regs: np.ndarray | None = None,
               fault: FaultPlan | None = None) -> SimState:
    P = env.P
    regs = (np.zeros((P, n_regs), np.int32)
            if init_regs is None else init_regs.astype(np.int32))
    plan = FaultPlan.none(P) if fault is None else fault
    return SimState(
        window=jnp.asarray(layout.init),
        pc=jnp.asarray(init_pc, jnp.int32),
        regs=jnp.asarray(regs),
        t_ready=jnp.zeros(P, jnp.float32),
        blocked_a=jnp.full(P, -1, jnp.int32),
        blocked_b=jnp.full(P, -1, jnp.int32),
        backoff=jnp.full(P, env.cost.backoff0, jnp.float32),
        busy=jnp.zeros(layout.W, jnp.float32),
        clock=jnp.float32(0), t_finish=jnp.float32(0),
        done=jnp.zeros(P, bool),
        events=jnp.int32(0),
        acq_count=jnp.zeros(P, jnp.int32),
        lat_sum=jnp.zeros(P, jnp.float32),
        t_attempt=jnp.zeros(P, jnp.float32),
        writer_active=jnp.int32(0), reader_active=jnp.int32(0),
        violations=jnp.int32(0), hold_rank=jnp.int32(-1),
        local_passes=jnp.int32(0), total_passes=jnp.int32(0),
        crash_t=jnp.asarray(plan.crash_t, jnp.float32),
        revive_t=jnp.asarray(plan.revive_t, jnp.float32),
        crashed=jnp.zeros(P, bool),
        in_cs=jnp.zeros(P, jnp.int32),
        restart_pc=jnp.asarray(init_pc, jnp.int32),
        restart_regs=jnp.asarray(regs),
        reclaims=jnp.int32(0), rec_retries=jnp.int32(0),
        t_recover=INF)


def recovery_extra(reclaimed, retried):
    """`finish_instr(extra=...)` hook for protocol recovery handlers:
    count a successful reclaim / a bounded-retry re-check and stamp the
    first recovery completion time."""
    def extra(s: SimState, finish):
        return s._replace(
            reclaims=s.reclaims + jnp.where(reclaimed, 1, 0),
            rec_retries=s.rec_retries + jnp.where(retried, 1, 0),
            t_recover=jnp.where(reclaimed,
                                jnp.minimum(s.t_recover, finish),
                                s.t_recover))
    return extra


def _fault_event(st: SimState, p, now) -> SimState:
    """Crash (first scheduling at/after crash_t) or revive (scheduling
    at revive_t) of process p. Env-free on purpose: `step_loop` has no
    env, so CS occupancy is recovered from `in_cs` (2 = writer).

    A crash releases the victim's CS occupancy for *accounting* only —
    its window words keep whatever half-written protocol state they had
    (that is the whole point of the fault model). The justification for
    not counting a dead holder against mutual exclusion is the lease
    assumption: survivors enter only after the victim's lease expired,
    at which point a real system would have fenced its RMA credentials.
    """
    reviving = st.crashed[p]
    crash = ~reviving
    # crash bookkeeping
    crashed = st.crashed.at[p].set(crash)
    writer_active = st.writer_active - jnp.where(crash & (st.in_cs[p] == 2),
                                                1, 0)
    reader_active = st.reader_active - jnp.where(crash & (st.in_cs[p] == 1),
                                                1, 0)
    # On crash: park until revive_t (INF = forever) and record the
    # ACTUAL crash time (>= planned) — leases run from this instant.
    # On revive: restore the initial pc/registers (a restarted process
    # re-enters the protocol from the top) and run immediately.
    t_next = jnp.where(reviving, now, st.revive_t[p])
    pc_next = jnp.where(reviving, st.restart_pc[p], st.pc[p])
    regs_row = jnp.where(reviving, st.restart_regs[p], st.regs[p])
    # Revive consumes the plan (crash_t := INF): otherwise the stamped
    # past crash time would re-trigger the fault forever.
    crash_stamp = jnp.where(reviving, INF, now)
    return st._replace(
        crashed=crashed,
        in_cs=st.in_cs.at[p].set(0),
        writer_active=writer_active, reader_active=reader_active,
        pc=st.pc.at[p].set(pc_next),
        regs=st.regs.at[p].set(regs_row),
        t_ready=st.t_ready.at[p].set(t_next),
        blocked_a=st.blocked_a.at[p].set(-1),
        blocked_b=st.blocked_b.at[p].set(-1),
        crash_t=st.crash_t.at[p].set(crash_stamp),
        clock=now, events=st.events + 1)


class StepTable(NamedTuple):
    """What `step_loop` runs: the handlers its switch holds, the slot in
    it of every pc, and whether the loop carries the fault branch.

    `slots` is None where the switch holds every pc at its own index.
    Hashable, so it is a static jit argument: equal tables share one
    compiled program.
    """

    handlers: tuple
    slots: tuple | None
    faults: bool


def unreachable_pcs(program, env: Env, *, faults: bool) -> frozenset:
    """The pcs of `program` that no run at `env` reaches: its declared
    `dead_pcs`, and without a fault plan (`faults` False) its
    `recovery_pcs` too. locklint proves both declarations."""
    meta = program.meta(env)
    if faults:
        return meta.dead_pcs
    return meta.dead_pcs | meta.recovery_pcs


def _trap(p, now, key, st: SimState) -> SimState:
    """The one slot of every pruned pc. Reaching it means a declaration
    of `unreachable_pcs` is wrong: under the sanitizer a check names the
    pc; without it the run ends with the process not done, its state
    unchanged but for `events`, set past any `max_events`."""
    if _SANITIZE_TRACING:
        checkify.check(jnp.bool_(False),
                       "pc {pc} was declared unreachable in this run",
                       pc=st.pc[p])
    return st._replace(events=jnp.int32(np.iinfo(np.int32).max))


def prune(handlers: Sequence[Callable], unreachable: frozenset, *,
          faults: bool) -> StepTable:
    """The step table of a full handler table: its live handlers, then
    one shared trap slot that every pc of `unreachable` maps to. Counts
    the pruned pcs as `program.pruned_pcs`."""
    live = [pc for pc in range(len(handlers)) if pc not in unreachable]
    spans.count("program.pruned_pcs", len(handlers) - len(live))
    if len(live) == len(handlers):
        return StepTable(tuple(handlers), None, faults)
    slot = {pc: i for i, pc in enumerate(live)}
    slots = tuple(slot.get(pc, len(live)) for pc in range(len(handlers)))
    return StepTable(tuple(handlers[pc] for pc in live) + (_trap,), slots,
                     faults)


def _as_table(table) -> StepTable:
    """A bare handler table runs every pc, with the fault branch."""
    if isinstance(table, StepTable):
        return table
    return StepTable(tuple(table), None, True)


_TABLES = {False: {}, True: {}}


def run_table(program, env: Env, *, faults: bool) -> StepTable:
    """The step table of `program.build(env)` for a run with (`faults`)
    or without a fault plan, derived once per handler table: cached by
    the table's identity, so a second run reuses the compiled program.
    In a table with `faults`, the handlers of the program's
    `recovery_pcs` also run under the named scope `recovery`."""
    def derive(handlers):
        if faults:
            recovery = program.meta(env).recovery_pcs
            handlers = tuple(_scoped(h, "recovery") if pc in recovery
                             else h for pc, h in enumerate(handlers))
        return prune(handlers, unreachable_pcs(program, env, faults=faults),
                     faults=faults)

    return memoized_build(_TABLES[faults], program.build(env), derive)


def step_loop(table, max_events: int, st: SimState, seed) -> SimState:
    """Traceable simulation core: run `st` to completion under `table`.

    `table` is a `StepTable`, or a bare handler table, which runs every
    pc with the fault branch. Each trip the scheduler (named scope
    `sched`) picks the ready process, and a switch runs its pc's handler
    (each branch under the scope `handlers`). A table with `faults`
    wraps the switch in a `lax.cond` against `_fault_event` (scope
    `fault`), which crashes or revives the process instead; a
    crash-free table has no such branch, and its loop drops the crashed
    terms, as nothing crashes.

    Plain function (no jit) so callers can embed it under their own
    jit/vmap — `run_sim_batch` vmaps it over seeds, `Session.sweep`
    additionally vmaps it over environment points.
    """
    handlers, slots, faults = _as_table(table)
    # The scope `handlers` names each branch, not the switch: under vmap
    # the switch broadcasts its operands (the env's [P, P] tables among
    # them) to every lane, which is the loop's cost, not a handler's.
    # The wrappers are new on every trace, and lax.switch caches traced
    # branches by function identity, so the checked and plain traces of
    # one table (`_SANITIZE_TRACING` on and off) never share a jaxpr.
    branches = tuple(_scoped(h, "handlers") for h in handlers)
    key0 = jax.random.PRNGKey(seed)

    def out_of_play(st):
        # A crashed-forever process is parked at t_ready == INF; it must
        # not keep the loop alive (nor may a done process).
        if not faults:
            return st.done
        return st.done | (st.crashed & (st.t_ready >= INF))

    def cond(carry):
        st, _ = carry
        return jnp.any(~out_of_play(st)) & (st.events < max_events)

    def body(carry):
        st, key = carry
        with jax.named_scope("sched"):
            key, sub = jax.random.split(key)
            tr = jnp.where(out_of_play(st), INF, st.t_ready)
            p = jnp.argmin(tr).astype(jnp.int32)
            now = tr[p]
            pc = st.pc[p]
            if slots is not None:
                pc = jnp.asarray(slots, jnp.int32)[pc]
            if faults:
                # Fault injection: a live process whose crash time has
                # come crashes INSTEAD of executing its instruction; a
                # crashed one being scheduled is (by the t_ready
                # protocol) due for revive.
                fault = st.crashed[p] | (now >= st.crash_t[p])

        def fault_event(op):
            with jax.named_scope("fault"):
                return _fault_event(op[0], op[1], op[2])

        def instruction(op):
            return jax.lax.switch(pc, branches, op[1], op[2], sub, op[0])

        if faults:
            st = jax.lax.cond(fault, fault_event, instruction, (st, p, now))
        else:
            st = instruction((st, p, now))
        return st, key

    st, _ = jax.lax.while_loop(cond, body, (st, key0))
    return st


def handler_table(handlers: Sequence[Callable], pc_names) -> tuple:
    """The handler tuple a program's `_build` returns: handler `pc` runs
    under the named scope `pc.<pc_names[pc]>`, which names its ops in
    the compiled program. Counts one `program.builds`."""
    spans.count("program.builds")
    return tuple(_scoped(h, "pc." + name)
                 for h, name in zip(handlers, pc_names, strict=True))


def _scoped(handler, scope: str):
    @functools.wraps(handler)
    def run(*args):
        with jax.named_scope(scope):
            return handler(*args)
    return run


@functools.partial(jax.jit, static_argnames=("table", "max_events"))
def _run_jit(table, max_events: int, st: SimState, seed) -> SimState:
    return step_loop(table, max_events, st, seed)


_CHECK_ERRORS = checkify.index_checks | checkify.user_checks


@functools.lru_cache(maxsize=MEMO_MAX_ENTRIES)
def _checked_run(table, max_events: int):
    return jax.jit(checkify.checkify(
        lambda st, seed: step_loop(table, max_events, st, seed),
        errors=_CHECK_ERRORS))


@functools.lru_cache(maxsize=MEMO_MAX_ENTRIES)
def _checked_run_batch(table, max_events: int):
    # checkify cannot wrap a batched while-loop, so the transform order
    # is vmap-of-checkify: each seed's run carries its own error slot
    # and `.throw()` on the batched error reports the first failure.
    checked = checkify.checkify(
        lambda st, s: step_loop(table, max_events, st, s),
        errors=_CHECK_ERRORS)

    def batched(st, seeds):
        err, final = jax.vmap(lambda s: checked(st, s))(seeds)
        return err, jax.vmap(summarize)(final)
    return jax.jit(batched)


def _call_checked(fn, *args):
    """Invoke a checkified variant with the sanitizer assertions traced
    in, and raise its first pending error (if any)."""
    global _SANITIZE_TRACING
    prev = _SANITIZE_TRACING
    _SANITIZE_TRACING = True
    try:
        err, out = fn(*args)
    finally:
        _SANITIZE_TRACING = prev
    err.throw()
    return out


def _run(table, max_events: int, st: SimState, seed) -> SimState:
    if checks_enabled():
        return _call_checked(_checked_run(table, max_events), st, seed)
    spans.note_dispatch(_run_jit, (table, max_events), (st, seed))
    return _run_jit(table, max_events, st, seed)


def pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of a float vector in one fixed pairwise order.

    XLA picks the order of a `jnp.sum` itself, and on the TPU that order
    changes with the batch shape around the reduction. Elementwise adds
    are never reordered, so halving the vector until one element is left
    gives the same bits whether a run is dispatched alone, in a grid, or
    sharded over devices.
    """
    n = 1 << max(x.shape[0] - 1, 0).bit_length()
    x = jnp.pad(x, (0, n - x.shape[0]))
    while n > 1:
        n //= 2
        x = x[:n] + x[n:]
    return x[0]


def summarize(st: SimState) -> Metrics:
    """Reduce a final SimState to Metrics (traceable; vmap for batches).

    Makespan is the finish time of the last instruction (`st.t_finish`),
    not the start time of the last event (`st.clock`) — the difference
    is one instruction round-trip, a bias that grows with per-op latency
    and would otherwise inflate every throughput figure.
    """
    total = jnp.sum(st.acq_count)
    mk = jnp.maximum(st.t_finish, 1e-6)
    return Metrics(
        # Survivors must all finish; crashed processes are excused.
        completed=jnp.all(st.done | st.crashed),
        violations=st.violations,
        makespan=mk,
        total_acquires=total,
        mean_latency=pairwise_sum(st.lat_sum) / jnp.maximum(total, 1),
        throughput=total.astype(jnp.float32) / (mk * 1e-6),
        events=st.events,
        locality=st.local_passes / jnp.maximum(st.total_passes, 1),
        per_proc_acq=st.acq_count,
        n_crashed=jnp.sum(st.crashed.astype(jnp.int32)),
        reclaims=st.reclaims,
        recovery_retries=st.rec_retries,
        t_recover=st.t_recover,
        t_crash=jnp.min(jnp.where(st.crashed, st.crash_t, INF)))


@functools.partial(jax.jit, static_argnames=("table", "max_events"))
def _run_batch_jit(table, max_events: int, st: SimState,
                   seeds: jnp.ndarray) -> Metrics:
    final = jax.vmap(lambda s: step_loop(table, max_events, st, s))(seeds)
    return jax.vmap(summarize)(final)


def _run_batch(table, max_events: int, st: SimState,
               seeds: jnp.ndarray) -> Metrics:
    if checks_enabled():
        return _call_checked(_checked_run_batch(table, max_events),
                             st, seeds)
    spans.note_dispatch(_run_batch_jit, (table, max_events), (st, seeds))
    return _run_batch_jit(table, max_events, st, seeds)


def _lane(table, max_events: int, st: SimState, seed, crash_t, revive_t):
    """One lane of a batch with per-lane fault plans."""
    return step_loop(table, max_events,
                     st._replace(crash_t=crash_t, revive_t=revive_t), seed)


@functools.partial(jax.jit, static_argnames=("table", "max_events"))
def _run_lanes_jit(table, max_events: int, st: SimState, seeds, crash_t,
                   revive_t) -> Metrics:
    final = jax.vmap(functools.partial(_lane, table, max_events, st))(
        seeds, crash_t, revive_t)
    return jax.vmap(summarize)(final)


def run_lanes(table, max_events: int, st: SimState, seeds: jnp.ndarray,
              plan: FaultPlan) -> Metrics:
    """The batched dispatch of runs that carry fault plans: lane s runs
    seed `seeds[s]` under the plan `plan.crash_t[s]`, `plan.revive_t[s]`
    (leaves [S, P]) from `st`, whose own plan is replaced. Metrics
    leaves gain a leading [S] axis. Under the sanitizer each lane is a
    checked single run: checkify cannot wrap a batched while-loop."""
    if checks_enabled():
        checked = _checked_run(table, max_events)
        runs = [summarize(_call_checked(
            checked, st._replace(crash_t=c, revive_t=r), s))
            for s, c, r in zip(seeds, plan.crash_t, plan.revive_t)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *runs)
    args = (st, seeds, plan.crash_t, plan.revive_t)
    spans.note_dispatch(_run_lanes_jit, (table, max_events), args)
    return _run_lanes_jit(table, max_events, *args)


def run_sim(program, env: Env, layout: Layout, *, seed=0,
            max_events: int = 2_000_000,
            fault: FaultPlan | None = None) -> Metrics:
    """Run a protocol program to completion and summarize metrics.

    Without `fault` the run cannot crash, so it takes the crash-free
    loop; any `FaultPlan`, `FaultPlan.none` included, takes the full
    loop (see `step_loop`)."""
    table = run_table(program, env, faults=fault is not None)
    st = init_state(env, layout, program.init_pc(env), program.n_regs,
                    program.init_regs(env), fault=fault)
    return summarize(_run(table, max_events, st, seed))


def run_sim_batch(program, env: Env, layout: Layout, *, seeds,
                  max_events: int = 2_000_000,
                  fault: FaultPlan | None = None) -> Metrics:
    """Run one configuration under many seeds in a single jitted dispatch.

    vmap over seeds yields one distinct schedule interleaving per seed
    (the module docstring's SPIN-checking analogue). Returns Metrics
    whose leaves carry a leading [len(seeds)] axis. `fault` selects
    the loop as in `run_sim`; every lane runs under it, through the
    per-lane dispatch `run_lanes`.
    """
    seeds = jnp.asarray(seeds, jnp.int32)
    st = init_state(env, layout, program.init_pc(env), program.n_regs,
                    program.init_regs(env))
    if fault is None:
        return _run_batch(run_table(program, env, faults=False),
                          max_events, st, seeds)
    lanes = (seeds.shape[0], env.P)
    plan = FaultPlan(*(jnp.broadcast_to(jnp.asarray(x, jnp.float32), lanes)
                       for x in fault))
    return run_lanes(run_table(program, env, faults=True), max_events, st,
                     seeds, plan)
