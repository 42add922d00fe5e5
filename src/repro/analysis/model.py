"""Exhaustive small-P model checking of the compiled lock programs.

This is the repo's real analogue of the paper's SPIN verification
(§4.4). Instead of hand-writing a reference interpreter that could
drift from the engine, the checker reuses the *actual* compiled
instruction handlers: one jitted `vmap(lax.switch)` evaluates, for a
given logical state, the successor state of every process in a single
dispatch, and a breadth-first search enumerates every reachable state
of the canonical (timing-free) state space.

Canonical states and why they are sound:

  * The engine's blocking is "sleep with a backoff timeout": a blocked
    process always keeps a finite `t_ready` (engine.apply_effect), so
    wake-on-write only changes *when* it retries, never *whether* it
    can. The canonical state therefore drops `blocked_a/b` entirely and
    treats every non-done process as enabled — a strict superset of the
    schedules any seed can produce.
  * With `cs_kind=0` and `think=False` every PRNG draw lands in timing
    fields (jitter, backoff), which the canonical state also drops, so
    transitions are deterministic given the fixed model key and the
    exploration is exhaustive over the logical space. (Programs that
    branch on randomness — the DHT — are explored per fixed key; vary
    keys at the IR layer for footprint coverage.)

Checked properties:

  * Safety: `violations` (mutual exclusion + reader/writer exclusion,
    asserted by `engine.cs_enter`) never increments on any edge; a
    counterexample interleaving is reconstructed from BFS parents.
  * Deadlock/livelock freedom: every bottom SCC of the reachable state
    graph is a single all-done terminal state. A protocol that drops a
    release (or otherwise strands a waiter with no path to progress)
    leaves a non-terminal bottom SCC — the model-checker's deadlock.
  * Completion: terminal states have every process at `target_acq`
    acquires with zero active CS occupants.

The explorer additionally returns per-pc reachability, the pc-successor
relation (CFG edges actually taken), observed watch words, and sampled
states per pc — the inputs of `repro.analysis.ir` and the structural
lints.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine


# Pseudo-pc labeling a crash transition in counterexample traces.
CRASH_PC = -99


class Canon(NamedTuple):
    """Canonical (timing-free) logical state."""

    window: np.ndarray       # int32 [W]
    pc: np.ndarray           # int32 [P]
    regs: np.ndarray         # int32 [P, R]
    done: np.ndarray         # bool [P]
    acq: np.ndarray          # int32 [P]
    writer_active: np.ndarray  # int32 []
    reader_active: np.ndarray  # int32 []
    violations: np.ndarray   # int32 []
    # Crash-fault dimension: a crashed process is permanently disabled
    # (its lease is modeled as already expired — the canonical state
    # stands for "any time after expiry", the only sound abstraction in
    # a timing-free space). `in_cs` feeds both the crash transition's
    # occupancy release and the recovery guards, so it is part of the
    # canonical identity.
    crashed: np.ndarray      # bool [P]
    in_cs: np.ndarray        # int32 [P]


def canon_key(c: Canon) -> bytes:
    return b"".join(np.ascontiguousarray(x).tobytes() for x in c)


def canon_of_state(st: engine.SimState) -> Canon:
    return Canon(
        window=np.asarray(st.window, np.int32),
        pc=np.asarray(st.pc, np.int32),
        regs=np.asarray(st.regs, np.int32),
        done=np.asarray(st.done, bool),
        acq=np.asarray(st.acq_count, np.int32),
        writer_active=np.asarray(st.writer_active, np.int32),
        reader_active=np.asarray(st.reader_active, np.int32),
        violations=np.asarray(st.violations, np.int32),
        crashed=np.asarray(st.crashed, bool),
        in_cs=np.asarray(st.in_cs, np.int32))


def crash_canon(c: Canon, victim: int) -> Canon:
    """The canonical successor of `c` when `victim` crashes: disabled
    forever, CS occupancy released for accounting (the engine's lease/
    fencing assumption — see engine._fault_event), window words left
    exactly as they are (they go stale)."""
    crashed = c.crashed.copy()
    crashed[victim] = True
    in_cs = c.in_cs.copy()
    wact = c.writer_active - (1 if int(in_cs[victim]) == 2 else 0)
    ract = c.reader_active - (1 if int(in_cs[victim]) == 1 else 0)
    in_cs[victim] = 0
    return c._replace(crashed=crashed, in_cs=in_cs,
                      writer_active=np.int32(wact),
                      reader_active=np.int32(ract))


def make_stepper(handlers, env, layout, *, model_seed: int = 0):
    """Jitted all-process successor function over canonical states.

    Returns `step(canon) -> per-process stacked leaves`: index [p] of
    each output leaf is the canonical successor (plus the executed
    process's watch words) when process p runs its current instruction.

    `model_seed` fixes the PRNG key every instruction executes under —
    transitions stay deterministic (exploration stays exhaustive), but
    programs whose *branches* consume randomness (the DHT) take
    different branches under different seeds; union coverage over a few
    seeds is how those programs' alternate paths get explored.
    """
    P, W = env.P, layout.W
    key0 = jax.random.PRNGKey(model_seed)

    @jax.jit
    def step(window, pc, regs, done, acq, wact, ract, viol, crashed,
             in_cs):
        st = engine.SimState(
            window=window, pc=pc, regs=regs,
            t_ready=jnp.zeros(P, jnp.float32),
            blocked_a=jnp.full(P, -1, jnp.int32),
            blocked_b=jnp.full(P, -1, jnp.int32),
            backoff=jnp.full(P, env.cost.backoff0, jnp.float32),
            busy=jnp.zeros(W, jnp.float32),
            clock=jnp.float32(0), t_finish=jnp.float32(0),
            done=done, events=jnp.int32(0), acq_count=acq,
            lat_sum=jnp.zeros(P, jnp.float32),
            t_attempt=jnp.zeros(P, jnp.float32),
            writer_active=wact, reader_active=ract, violations=viol,
            hold_rank=jnp.int32(-1),
            local_passes=jnp.int32(0), total_passes=jnp.int32(0),
            # A crashed process's lease is already expired in the
            # timing-free model (crash_t = -INF, now = 0): the recovery
            # guards (engine.leases_expired) see exactly the post-expiry
            # world every canonical crashed state stands for.
            crash_t=jnp.where(crashed, -engine.INF, engine.INF),
            revive_t=jnp.full(P, engine.INF, jnp.float32),
            crashed=crashed, in_cs=in_cs,
            restart_pc=pc, restart_regs=regs,
            reclaims=jnp.int32(0), rec_retries=jnp.int32(0),
            t_recover=engine.INF)

        def one(p):
            now = jnp.float32(0.0)
            eff = jax.lax.switch(st.pc[p], handlers, p, now, key0, st)
            out = engine.apply_effect(env, st, eff, p, now)
            return (out.window, out.pc, out.regs, out.done,
                    out.acq_count, out.writer_active, out.reader_active,
                    out.violations, out.crashed, out.in_cs,
                    out.blocked_a[p], out.blocked_b[p])

        return jax.vmap(one)(jnp.arange(P, dtype=jnp.int32))

    def run(c: Canon):
        out = step(*c)
        return [np.asarray(x) for x in out]

    return run


@dataclasses.dataclass
class ModelFinding:
    """One property violation found by the explorer."""

    kind: str                 # "safety" | "stuck" | "incomplete"
    message: str
    trace: tuple = ()         # ((p, pc), ...) interleaving from init

    def render_trace(self, meta=None) -> str:
        if not self.trace:
            return "<init>"
        base = (meta.pc_name if meta is not None
                else lambda k: f"pc{k}")
        name = lambda k: "CRASH" if k == CRASH_PC else base(k)
        return " -> ".join(f"p{p}:{name(k)}" for p, k in self.trace)


@dataclasses.dataclass
class ExploreResult:
    n_states: int
    n_edges: int
    n_terminals: int
    capped: bool              # hit max_states; properties only cover
    findings: list            # the explored prefix when True
    pc_reached: set
    pc_successors: dict       # pc -> set of observed next pcs
    watch_words: dict         # pc -> set of observed watched words
    samples: dict             # pc -> [(Canon, p), ...]
    n_interleavings: int = 0
    interleavings_capped: bool = False

    @property
    def ok(self) -> bool:
        return not self.findings


class Explorer:
    """BFS over all interleavings of a program at one configuration.

    With `crash_victim=v`, every state where v is still alive (and not
    done) additionally has a crash transition: v stops forever, its CS
    occupancy is released for accounting, its window words stay as they
    are. The BFS then covers "v crashes at ANY point of ANY
    interleaving" — the exhaustive analogue of FaultPlan's timed crash
    — and the properties become: survivors never violate exclusion
    (safety) and always complete (no stuck bottom SCC, every terminal
    has all survivors at target_acq).
    """

    def __init__(self, program, env, layout, *, max_states=200_000,
                 samples_per_pc=3, model_seed: int = 0,
                 crash_victim: int | None = None):
        self.program = program
        self.env = env
        self.layout = layout
        self.handlers = program.build(env)
        self.stepper = make_stepper(self.handlers, env, layout,
                                    model_seed=model_seed)
        self.max_states = int(max_states)
        self.samples_per_pc = int(samples_per_pc)
        self.P = int(env.P)
        self.target_acq = int(env.target_acq)
        self.crash_victim = (None if crash_victim is None
                             else int(crash_victim))

    def init_canon(self) -> Canon:
        st0 = engine.init_state(
            self.env, self.layout, self.program.init_pc(self.env),
            self.program.n_regs, self.program.init_regs(self.env))
        return canon_of_state(st0)

    # -------------------------------------------------------- explore
    def explore(self, *, count_paths_cap: int = 50_000) -> ExploreResult:
        c0 = self.init_canon()
        k0 = canon_key(c0)
        states = {k0: c0}
        parents = {k0: None}          # key -> (parent_key, p, pc)
        graph = {}                    # key -> [(p, succ_key), ...]
        pc_reached, pc_succ, watch = set(), {}, {}
        samples = {}
        findings = []
        n_edges = 0
        capped = False

        dq = deque([k0])
        while dq:
            k = dq.popleft()
            c = states[k]
            enabled = [p for p in range(self.P)
                       if not c.done[p] and not c.crashed[p]]
            graph[k] = []
            # Crash transition: the victim may die at ANY reachable
            # state where it is still alive.
            v = self.crash_victim
            if v is not None and not c.crashed[v] and not c.done[v]:
                nc = crash_canon(c, v)
                nk = canon_key(nc)
                n_edges += 1
                graph[k].append((v, nk))
                if nk not in states:
                    states[nk] = nc
                    parents[nk] = (k, v, CRASH_PC)
                    if len(states) >= self.max_states:
                        capped = True
                        dq.clear()
                    else:
                        dq.append(nk)
            if not enabled or capped:
                if capped:
                    break
                continue              # terminal (all done or crashed)
            out = self.stepper(c)
            (win, pc, regs, done, acq, wact, ract, viol, crashed,
             in_cs, ba, bb) = out
            for p in enabled:
                k_exec = int(c.pc[p])
                pc_reached.add(k_exec)
                nc = Canon(win[p], pc[p], regs[p], done[p], acq[p],
                           wact[p], ract[p], viol[p], crashed[p],
                           in_cs[p])
                nk = canon_key(nc)
                n_edges += 1
                graph[k].append((p, nk))
                pc_succ.setdefault(k_exec, set()).add(int(nc.pc[p]))
                for b in (int(ba[p]), int(bb[p])):
                    if b >= 0:
                        watch.setdefault(k_exec, set()).add(b)
                bucket = samples.setdefault(k_exec, [])
                if len(bucket) < self.samples_per_pc:
                    bucket.append((c, p))
                if int(nc.violations) > int(c.violations):
                    findings.append(ModelFinding(
                        kind="safety",
                        message=(f"exclusion violation when p{p} "
                                 f"executes pc {k_exec}"),
                        trace=self._trace_of(parents, k) + ((p, k_exec),)))
                if nk not in states:
                    states[nk] = nc
                    parents[nk] = (k, p, k_exec)
                    if len(states) >= self.max_states:
                        capped = True
                        dq.clear()
                        break
                    dq.append(nk)
            if capped:
                break

        terminals = [k for k, succs in graph.items() if not succs
                     and bool((states[k].done | states[k].crashed).all())]
        for k in terminals:
            c = states[k]
            if (int(c.writer_active) != 0 or int(c.reader_active) != 0):
                findings.append(ModelFinding(
                    kind="incomplete",
                    message=(f"terminal state with active CS occupants "
                             f"(writer={int(c.writer_active)}, "
                             f"reader={int(c.reader_active)})"),
                    trace=self._trace_of(parents, k)))
            survivors_ok = bool(
                ((c.acq == self.target_acq) | c.crashed).all())
            if not survivors_ok:
                findings.append(ModelFinding(
                    kind="incomplete",
                    message=(f"terminal state with acquire counts "
                             f"{c.acq.tolist()} != target "
                             f"{self.target_acq} (crashed="
                             f"{c.crashed.tolist()})"),
                    trace=self._trace_of(parents, k)))

        if not capped:
            findings.extend(self._stuck_findings(states, parents, graph))

        if capped:
            # A truncated graph has few complete root->terminal paths;
            # the DFS would mostly wander the frontier. Skip it.
            n_paths, paths_capped = 0, True
        else:
            n_paths, paths_capped = _count_interleavings(
                graph, k0, set(terminals), cap=count_paths_cap)

        return ExploreResult(
            n_states=len(states), n_edges=n_edges,
            n_terminals=len(terminals), capped=capped,
            findings=findings, pc_reached=pc_reached,
            pc_successors=pc_succ, watch_words=watch, samples=samples,
            n_interleavings=n_paths, interleavings_capped=paths_capped)

    # ------------------------------------------------------- internals
    @staticmethod
    def _trace_of(parents, key, limit=80):
        steps = []
        k = key
        while parents.get(k) is not None:
            k, p, pc = parents[k]
            steps.append((p, pc))
        steps.reverse()
        return tuple(steps[-limit:])

    def _stuck_findings(self, states, parents, graph):
        """Bottom SCCs that are not all-done terminals = states from
        which no schedule (not even timeout retries) completes."""
        findings = []
        for scc in _bottom_sccs(graph):
            rep = next(iter(scc))
            c = states[rep]
            if len(scc) == 1 and bool((c.done | c.crashed).all()):
                continue              # a proper terminal
            waiting = [p for p in range(self.P)
                       if not c.done[p] and not c.crashed[p]]
            pcs = sorted({int(states[k].pc[p])
                          for k in scc for p in waiting})
            findings.append(ModelFinding(
                kind="stuck",
                message=(f"deadlock/livelock: {len(scc)} state(s) with "
                         f"no path to completion; waiting procs "
                         f"{waiting} cycle through pcs {pcs}"),
                trace=self._trace_of(parents, rep)))
        return findings


def _bottom_sccs(graph):
    """Tarjan SCCs (iterative); yield SCCs with no edge leaving them."""
    index = {}
    low = {}
    onstack = {}
    stack = []
    sccs = []
    counter = [0]

    for root in graph:
        if root in index:
            continue
        work = [(root, iter(graph.get(root, ())))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        onstack[root] = True
        while work:
            v, it = work[-1]
            advanced = False
            for _, w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    onstack[w] = True
                    work.append((w, iter(graph.get(w, ()))))
                    advanced = True
                    break
                if onstack.get(w):
                    low[v] = min(low[v], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                u = work[-1][0]
                low[u] = min(low[u], low[v])
            if low[v] == index[v]:
                scc = set()
                while True:
                    w = stack.pop()
                    onstack[w] = False
                    scc.add(w)
                    if w == v:
                        break
                sccs.append(scc)

    # Callers only run this on uncapped explorations, where BFS has
    # expanded every state, so each successor key appears in `graph`.
    for scc in sccs:
        if all(w in scc for v in scc for _, w in graph.get(v, ())):
            yield scc


def _count_interleavings(graph, root, terminals, *, cap=50_000,
                         step_cap=2_000_000):
    """Count distinct maximal interleavings (paths root -> terminal),
    skipping on-path cycles, up to `cap` paths (and `step_cap` DFS
    steps, so cyclic graphs with few terminals stay bounded). Returns
    (count, capped)."""
    if root in terminals:
        return 1, False
    count = 0
    steps = 0
    onpath = {root}
    stack = [(root, iter(graph.get(root, ())))]
    while stack:
        steps += 1
        if count >= cap or steps >= step_cap:
            return count, True
        node, it = stack[-1]
        nxt = next(it, None)
        if nxt is None:
            stack.pop()
            onpath.discard(node)
            continue
        _, succ = nxt
        if succ in onpath:
            continue
        if succ in terminals:
            count += 1
            continue
        if succ not in graph:
            continue                  # unexplored frontier (capped run)
        onpath.add(succ)
        stack.append((succ, iter(graph.get(succ, ()))))
    return count, False
