"""Compiled lock sessions: one spec, one compile, many runs.

A `Session` realizes a `LockSpec` under a fixed workload (target
acquires per process, critical-section kind, think time), compiles the
jitted simulator once, and then offers three execution shapes:

  * `run(seed)`        — one schedule, scalar Metrics.
  * `run_batch(seeds)` — vmap over seeds in a SINGLE jitted dispatch,
    stacked Metrics ([S] leading axis). One seed = one distinct
    schedule interleaving, so a batch is the executable analogue of the
    paper's SPIN model checking (§4.4) — and of its throughput error
    bars. `run_batch(seeds, faults=plan)` gives each lane its own
    crash plan (`engine.FaultPlan`, leaves [S, P]).
  * `sweep(axis, values, seeds=...)` — jit-batched scan over one axis
    of the paper's parameter space as a SINGLE dispatch vmapped over
    (points x seeds). `T_L`, `T_R`, and `writer_fraction` only change
    *values* in the environment. `T_DC` changes counter placement, but
    layouts are padded to a common max-C (`build_layout`'s
    `pad_counters_to`) with a traced `ctr_mask`, so its points are
    shape-stable too and the whole axis traces once. This turns the
    paper's Fig. 4 threshold sweeps and Fig. 5 writer-fraction scans
    into one call each.
  * `grid(t_dc, t_l, t_r, seeds=...)` — the paper's FULL 3D parameter
    space (§3.2) × seeds as one jitted dispatch; Metrics leaves gain
    leading [D, L, R, S] axes. This is the substrate of the
    `repro.core.tuner` auto-tuner and of multi-device sharded
    exploration.

Multi-device sharding: every execution shape takes a `devices=` knob
(constructor default + per-call override). With devices given, the
flattened (lattice points × seeds) batch is padded to a device
multiple with dead entries, sharded over a 1D mesh
(`launch.mesh.make_batch_mesh`) via `jax.shard_map`, and the Metrics
are unpadded back — per-entry results are bitwise-equal to the
single-device dispatch because entries never interact (the vmapped
`lax.while_loop` keeps each lane's trajectory independent, and
`engine.pairwise_sum` fixes the one float reduction's order).
`devices=None` (the default) keeps the classic single-device dispatch.

Crash-free loop: a Session carries no `engine.FaultPlan`, so nothing in
it crashes unless a call passes plans. Every execution shape, and
`run_batch` without `faults`, runs `engine.step_loop` without the fault
branch, over a switch that holds only the pcs a crash-free run reaches
(`engine.unreachable_pcs`: the program's declared dead and recovery pcs
go to one trap slot). `run_batch(seeds, faults=plan)` runs the full
loop, with the fault branch and the recovery handlers, compiled on the
first such call and reused by every later one.

Seed-level caching: the jitted program is cached per (step table,
max_events) by JAX, and handlers are cached per environment by the
program, so repeated `run`/`run_batch` calls on one Session never
recompile. Sweep and sharded dispatch functions are cached per set of
pruned pcs (and device tuple).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, spans
from repro.core.spec import EXTRA_WORDS, LockSpec
from repro.core.topology import counter_ranks
from repro.core.window import build_layout

# Sentinel for "devices not passed": per-call `devices=None` forces the
# single-device path even on a Session constructed with devices.
_UNSET = object()

# Axes of `sweep`. ALL axes share one compiled program: T_L / T_R /
# writer_fraction are plain traced values, and T_DC points are padded to
# a common counter-slot count so even counter placement is a traced
# value (ctr_mask), never a shape.
DYNAMIC_AXES = ("T_DC", "T_L", "T_R", "writer_fraction")
SWEEP_AXES = DYNAMIC_AXES


def metrics_at(m: engine.Metrics, *index) -> engine.Metrics:
    """Select one element from stacked Metrics (e.g. `metrics_at(m, k, s)`
    for sweep output, `metrics_at(m, s)` for run_batch output)."""
    return engine.Metrics(*(leaf[index] for leaf in m))


def resolve_devices(devices):
    """Normalize a `devices=` argument to a tuple of jax devices.

    Accepts None (single-device classic dispatch — returns None), an
    int N (first N local devices), or an explicit device sequence
    (e.g. `jax.local_devices()`).
    """
    if devices is None:
        return None
    if isinstance(devices, int):
        local = jax.local_devices()
        if not 1 <= devices <= len(local):
            raise ValueError(
                f"devices={devices} but this host has {len(local)} local "
                f"device(s); force more with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
        return tuple(local[:devices])
    devices = tuple(devices)
    if not devices:
        raise ValueError("devices must be None, an int >= 1, or a "
                         "non-empty device sequence")
    return devices


def _tl_dyn(spec: LockSpec) -> dict:
    """Env overrides realizing one spec's T_L point (shared by sweep and
    grid so the threshold encoding cannot drift between them)."""
    T_L = np.asarray(spec.T_L if spec.T_L is not None
                     else [1 << 26] * spec.n_levels, np.int32)
    return {"T_L": jnp.asarray(T_L),
            "T_W": jnp.int32(engine.derive_tw(T_L))}


def _tr_dyn(spec: LockSpec) -> dict:
    return {"T_R": jnp.int32(spec.T_R)}


class Session:
    """A compiled (spec, workload) pair ready to run under many seeds."""

    def __init__(self, spec: LockSpec, *, target_acq: int = 8,
                 cs_kind: int = 0, think: bool = False,
                 max_events: int = 2_000_000,
                 extra_words: int = EXTRA_WORDS, devices=None):
        with spans.span("session.build"):
            self.spec = spec
            self.devices = resolve_devices(devices)
            self.target_acq = int(target_acq)
            self.cs_kind = int(cs_kind)
            self.think = bool(think)
            self.max_events = int(max_events)
            self.extra_words = int(extra_words)
            with spans.span("session.layout"):
                self.machine = spec.machine()
                self.layout = spec.layout(self.machine,
                                          extra_words=extra_words)
                self.is_writer = spec.roles()
            with spans.span("session.handlers"):
                self.program = spec.program(self.layout)
                self.env = engine.make_env(
                    self.machine, self.layout, T_L=spec.T_L, T_R=spec.T_R,
                    is_writer=self.is_writer, target_acq=self.target_acq,
                    cs_kind=self.cs_kind, think=self.think, cost=spec.cost)
                self.handlers = self.program.build(self.env)
                self.table = engine.run_table(self.program, self.env,
                                              faults=False)
            with spans.span("session.init_state"):
                self.state0 = engine.init_state(
                    self.env, self.layout, self.program.init_pc(self.env),
                    self.program.n_regs, self.program.init_regs(self.env))
            self._sweep_fns = {}      # pruned pcs -> jitted sweep fn
            self._shard_fns = {}      # (devices, pruned pcs) -> jitted fn
            self.fault_table = None   # the full loop's, on first use

    def _devices(self, devices):
        """Per-call `devices=` override (the constructor's value when
        not passed; explicit None forces the single-device path)."""
        return (self.devices if devices is _UNSET
                else resolve_devices(devices))

    # ------------------------------------------------------ execution
    def run_state(self, seed: int = 0) -> engine.SimState:
        """One schedule to completion; returns the final simulator state
        (for invariant checks that need more than Metrics)."""
        return engine._run(self.table, self.max_events, self.state0, seed)

    def run(self, seed: int = 0) -> engine.Metrics:
        return engine.summarize(self.run_state(seed))

    def run_batch(self, seeds, *, faults: engine.FaultPlan | None = None,
                  devices=_UNSET) -> engine.Metrics:
        """Execute all seeds in one jitted dispatch; Metrics leaves gain
        a leading [len(seeds)] axis. With `devices`, the seed batch is
        sharded across them (padded to a device multiple, unpadded in
        the returned Metrics).

        `faults` is one crash plan per lane: a `FaultPlan` whose
        `crash_t` and `revive_t` are [len(seeds), P] (`FaultPlan.lanes`
        builds one). Lane s runs seed s under plan s through the full
        loop (`engine.run_table(..., faults=True)`), compiled once for
        the Session: later calls with other plans reuse it. Without
        `faults` the call runs the crash-free loop. Plans cannot yet be
        sharded: `faults` with `devices` raises NotImplementedError."""
        with spans.span("session.run_batch"):
            with spans.span("session.seeds_to_device"):
                seeds = jnp.asarray(seeds, jnp.int32)
            devices = self._devices(devices)
            if faults is not None:
                return self._run_lanes(seeds, faults, devices)
            with spans.span("session.dispatch"):
                if devices is None:
                    return engine._run_batch(self.table, self.max_events,
                                             self.state0, seeds)
                # One-point "lattice": shard the flattened (1 x S) batch.
                st0 = jax.tree.map(lambda x: x[None], self.state0)
                m = self._dispatch({}, st0, seeds, devices,
                                   self._unreachable([self.env]))
            return metrics_at(m, 0)

    def _run_lanes(self, seeds, faults: engine.FaultPlan,
                   devices) -> engine.Metrics:
        """`run_batch` with one fault plan per lane."""
        lanes = (seeds.shape[0], self.spec.P)
        crash_t, revive_t = (np.asarray(x, np.float32) for x in faults)
        if crash_t.shape != lanes or revive_t.shape != lanes:
            raise ValueError(
                f"faults must hold one plan per lane: crash_t and revive_t "
                f"of shape {lanes}, got {crash_t.shape} and "
                f"{revive_t.shape}")
        if devices is not None:
            raise NotImplementedError(
                "run_batch cannot shard fault plans over devices; pass "
                "devices=None")
        spans.count("session.fault_lanes",
                    int(np.any(crash_t < float(engine.INF), axis=1).sum()))
        with spans.span("session.faults_to_device"):
            plan = engine.FaultPlan(jnp.asarray(crash_t),
                                    jnp.asarray(revive_t))
        if self.fault_table is None:
            self.fault_table = engine.run_table(self.program, self.env,
                                                faults=True)
        with spans.span("session.dispatch"):
            return engine.run_lanes(self.fault_table, self.max_events,
                                    self.state0, seeds, plan)

    # --------------------------------------------------------- sweeps
    def specs_along(self, axis: str, values) -> list:
        """The derived LockSpec for every point of a sweep (validated)."""
        if axis not in SWEEP_AXES:
            raise ValueError(f"axis must be one of {SWEEP_AXES}, "
                             f"got {axis!r}")
        return [self.spec.replace(**{axis: v}) for v in values]

    def sweep(self, axis: str, values, *, seeds=(0,),
              devices=_UNSET) -> engine.Metrics:
        """Scan one parameter axis under a batch of seeds — ONE jitted
        dispatch for every axis, including T_DC (points are padded to a
        common counter-slot count, so counter placement is a traced
        value rather than a shape). With `devices`, the flattened
        (points × seeds) batch is sharded across them.

        Returns stacked Metrics with leading axes [len(values),
        len(seeds)]; index with `metrics_at(m, k, s)`.
        """
        with spans.span("session.sweep"):
            specs = self.specs_along(axis, values)
            seeds = jnp.asarray(seeds, jnp.int32)
            with spans.span("session.stack"):
                dyn, st0, pruned = self._sweep_points(axis, specs)
            return self._dispatch(dyn, st0, seeds, self._devices(devices),
                                  pruned)

    def grid(self, t_dc, t_l, t_r, *, seeds=(0,),
             devices=_UNSET) -> engine.Metrics:
        """Scan the paper's full 3D (T_DC, T_L, T_R) lattice under a
        batch of seeds as ONE jitted dispatch.

        `t_l` entries are per-level threshold tuples (or None for
        unbounded). Roles (writer_fraction) are those of the session's
        spec. Returns stacked Metrics with leading axes
        [len(t_dc), len(t_l), len(t_r), len(seeds)]; index with
        `metrics_at(m, d, l, r, s)`. Each lattice point is bitwise-equal
        to a fresh per-point `Session.run_batch` — padding only adds
        dead masked counter slots, never dynamics. With `devices` (a
        device list or an int count; defaults to the constructor's),
        the flattened (lattice points × seeds) batch is data-parallel
        across devices, still one compile, still bitwise-equal per
        point.
        """
        t_dc = [int(v) for v in t_dc]
        t_l = [v if v is None else tuple(int(x) for x in v) for v in t_l]
        t_r = [int(v) for v in t_r]
        if not (t_dc and t_l and t_r):
            raise ValueError("grid axes must be non-empty")
        with spans.span("session.grid"):
            seeds = jnp.asarray(seeds, jnp.int32)
            with spans.span("session.stack"):
                dyn, st0, pruned = self._grid_points(t_dc, t_l, t_r)
            m = self._dispatch(dyn, st0, seeds, self._devices(devices),
                               pruned)
        shape = (len(t_dc), len(t_l), len(t_r))
        return engine.Metrics(
            *(leaf.reshape(shape + leaf.shape[1:]) for leaf in m))

    def _unreachable(self, envs) -> frozenset:
        """The pcs that no crash-free run at any of `envs` reaches."""
        return frozenset.intersection(*(
            engine.unreachable_pcs(self.program, env, faults=False)
            for env in envs))

    def _grid_points(self, t_dc, t_l, t_r):
        """Stacked env overrides + initial states of the lattice, and
        the pcs no point reaches."""
        C_pad = max(len(counter_ranks(self.machine, d)) for d in t_dc)
        dyns, states = [], []
        for d in t_dc:
            layout_d, ldyn = self._layout_dyn(d, C_pad)
            # Roles are fixed across the lattice, so the initial state
            # only depends on the (padded, T_DC-invariant) layout.
            st_d = engine.init_state(
                self.env, layout_d, self.program.init_pc(self.env),
                self.program.n_regs, self.program.init_regs(self.env))
            for tl in t_l:
                for r in t_r:
                    spec_k = self.spec.replace(T_DC=d, T_L=tl, T_R=r)
                    dyns.append(dict(ldyn, **_tl_dyn(spec_k),
                                     **_tr_dyn(spec_k)))
                    states.append(st_d)
        pruned = self._unreachable(
            dataclasses.replace(self.env, **dd) for dd in dyns)
        dyn = {k: jnp.stack([dd[k] for dd in dyns]) for k in dyns[0]}
        st0 = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        return dyn, st0, pruned

    def _layout_dyn(self, T_DC: int, C_pad: int):
        """Padded layout for one T_DC point + the env overrides that
        realize it (all shape-stable at C_pad counter slots)."""
        layout = build_layout(self.machine, T_DC,
                              extra_words=self.extra_words,
                              pad_counters_to=C_pad)
        dyn = {"owner": jnp.asarray(layout.owner),
               "arrive_w": jnp.asarray(layout.arrive_w),
               "depart_w": jnp.asarray(layout.depart_w),
               "ctr_rank": jnp.asarray(layout.ctr_rank),
               "ctr_of_p": jnp.asarray(layout.ctr_of_p),
               "ctr_mask": jnp.asarray(layout.ctr_mask),
               "scratch_w": jnp.asarray(layout.scratch_w)}
        return layout, dyn

    def _sweep_points(self, axis: str, specs):
        """Stacked per-point env overrides + initial states (numpy), and
        the pcs no point reaches."""
        C_pad = (max(len(counter_ranks(self.machine, s.T_DC))
                     for s in specs) if axis == "T_DC" else None)
        dyns, states, envs = [], [], []
        for s in specs:
            layout = self.layout
            if axis == "T_R":
                dyn = _tr_dyn(s)
            elif axis == "T_L":
                dyn = _tl_dyn(s)
            elif axis == "T_DC":
                layout, dyn = self._layout_dyn(s.T_DC, C_pad)
            else:                 # writer_fraction: roles change
                dyn = {"is_writer": jnp.asarray(s.roles())}
            env_k = dataclasses.replace(self.env, **{
                k: v for k, v in dyn.items()})
            # init_pc depends on roles (readers start in the reader
            # program), so the initial state is built per point.
            states.append(engine.init_state(
                env_k, layout, self.program.init_pc(env_k),
                self.program.n_regs, self.program.init_regs(env_k)))
            dyns.append(dyn)
            envs.append(env_k)
        dyn = {k: jnp.stack([d[k] for d in dyns]) for k in dyns[0]}
        st0 = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        return dyn, st0, self._unreachable(envs)

    def _dispatch(self, dyn, st0, seeds, devices, pruned) -> engine.Metrics:
        """Run the stacked points × seeds batch, with the pcs `pruned`
        sent to the trap; Metrics leaves come back with leading [K, S]
        axes. `devices=None` is the classic single-device dispatch;
        otherwise the flattened (K × S) batch is sharded across the
        device tuple."""
        if devices is None:
            fn = self._sweep_fns.get(pruned)
            if fn is None:
                fn = self._sweep_fns[pruned] = self._build_sweep_fn(pruned)
            spans.note_dispatch(fn, (), (dyn, st0, seeds))
            return fn(dyn, st0, seeds)
        return self._dispatch_sharded(dyn, st0, seeds, devices, pruned)

    def _dispatch_sharded(self, dyn, st0, seeds, devices,
                          pruned) -> engine.Metrics:
        """Flatten (points × seeds), pad to a device multiple with dead
        entries, shard, and unpad the Metrics.

        Entries never interact (independent lanes of one vmap), so the
        pad entries — replays of (point 0, seed 0) — cannot perturb live
        entries, and per-entry results are bitwise-equal to the
        single-device dispatch.
        """
        K = jax.tree.leaves(st0)[0].shape[0]
        S = seeds.shape[0]
        B = K * S
        D = len(devices)
        idx = jnp.repeat(jnp.arange(K, dtype=jnp.int32), S)
        sds = jnp.tile(seeds, K)
        pad = (-B) % D
        if pad:
            idx = jnp.concatenate([idx, jnp.zeros(pad, jnp.int32)])
            sds = jnp.concatenate([sds, jnp.broadcast_to(seeds[:1], (pad,))])
        fn = self._shard_fns.get((devices, pruned))
        if fn is None:
            fn = self._shard_fns[devices, pruned] = self._build_shard_fn(
                devices, pruned)
        spans.note_dispatch(fn, (), (dyn, st0, idx, sds))
        m = fn(dyn, st0, idx, sds)
        return engine.Metrics(
            *(leaf[:B].reshape((K, S) + leaf.shape[1:]) for leaf in m))

    def _point_entry(self, pruned, dyn, st0, i, seed):
        """One flattened (point, seed) entry: realize point i's env and
        run seed's schedule to completion (traceable)."""
        env_k = dataclasses.replace(
            self.env, **jax.tree.map(lambda x: x[i], dyn))
        st_k = jax.tree.map(lambda x: x[i], st0)
        # _build, not build: the memoizing build() would retain this
        # traced env (and its tracers) past the trace.
        table = engine.prune(self.program._build(env_k), pruned,
                             faults=False)
        final = engine.step_loop(table, self.max_events, st_k, seed)
        return engine.summarize(final)

    def _build_shard_fn(self, devices, pruned):
        """Jitted sharded dispatch over a 1D mesh of `devices`: each
        device runs its contiguous chunk of the flattened batch through
        one vmapped entry body (ONE trace — the point program is built
        once for the whole mesh)."""
        from repro.launch.mesh import make_batch_mesh

        mesh = make_batch_mesh(devices)

        def tile(dyn, st0, idx, seeds):
            return jax.vmap(functools.partial(
                self._point_entry, pruned, dyn, st0))(idx, seeds)

        P = jax.sharding.PartitionSpec
        # Every output is explicitly batch-sharded, so the varying-axes
        # check adds nothing.
        return jax.jit(jax.shard_map(
            tile, mesh=mesh, in_specs=(P(), P(), P("batch"), P("batch")),
            out_specs=P("batch"), check_vma=False))

    def _build_sweep_fn(self, pruned):
        program, env, max_events = self.program, self.env, self.max_events

        @jax.jit
        def sweep_fn(dyn, st0, seeds):
            def point(dyn_k, st0_k):
                env_k = dataclasses.replace(env, **dyn_k)
                # _build, not build: the memoizing build() would retain
                # this traced env (and its tracers) past the trace.
                table = engine.prune(program._build(env_k), pruned,
                                     faults=False)
                final = jax.vmap(functools.partial(
                    engine.step_loop, table, max_events, st0_k))(seeds)
                return jax.vmap(engine.summarize)(final)
            return jax.vmap(point)(dyn, st0)

        return sweep_fn
