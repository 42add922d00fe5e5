"""Eager instruction-handler replay with footprint recording.

The instruction handlers are plain jnp-on-arrays functions, so they run
eagerly on numpy inputs. `TraceArray` is an ndarray subclass whose
integer indexing and `.at[...]` updates report to a `Recorder` before
mimicking jax semantics (clamped gathers, dropped out-of-bounds
scatters) — replaying a handler on a TraceArray-backed `SimState`
recovers the *observed* window-word read/write footprint and register
indices of that instruction without touching the engine.

Declared effects (`finish_instr` keyword arguments: duration class,
hot word, declared writes, next pc, watch words) are captured by
temporarily patching the `finish_instr` / `cs_enter` / `cs_exit`
globals of each handler's defining module: the programs import those
names from `repro.core.engine` at module scope, so rebinding the module
attribute intercepts the call while `patched(...)` is active. The
handler's returned `engine.Effect` is then applied by
`engine.apply_effect`, the step loop's own tail.

Recording is a best-effort superset/subset pair by design: reads and
writes funneled through `jnp.where`-combined arrays lose the TraceArray
wrapper, while `.at` updates on untaken branches of a `jnp.where` are
still recorded. Both are fine for the analyzer: the bounds lints check
that every address an instruction *can compute* stays in its segment,
and the wake/successor lints use the declared effects, which are exact.
"""
from __future__ import annotations

import sys

import numpy as np

from repro.core import engine

_REAL_FINISH = engine.finish_instr
_REAL_CS_ENTER = engine.cs_enter
_REAL_CS_EXIT = engine.cs_exit

# Channels with per-index recording; everything else traces silently
# (so `.at` updates still work) under channel None.
CH_WINDOW = "window"
CH_REGS = "regs"            # 2-D register file; rows re-channel below
CH_REGS_ROW = "regs_row"    # 1-D register row: indices are reg numbers


def _intlike(idx) -> bool:
    if isinstance(idx, (bool, np.bool_)):
        return False
    if isinstance(idx, (int, np.integer)):
        return True
    return (hasattr(idx, "ndim") and getattr(idx, "ndim", None) == 0
            and np.issubdtype(np.asarray(idx).dtype, np.integer))


class Recorder:
    """Sink for one handler invocation's observed + declared effects."""

    def __init__(self):
        self.active = True
        self.window_reads = set()
        self.window_writes = set()
        self.reg_reads = set()
        self.reg_writes = set()
        # Declared effects from finish_instr (exact).
        self.hot_word = None
        self.declared_writes = []
        self.next_pc = None
        self.block_words = set()
        self.regs_row_len = None
        self.entered_cs = False
        self.exited_cs = False
        self.finished = False

    # ---- TraceArray callbacks ---------------------------------------
    def note_read(self, chan, idx):
        if not self.active:
            return
        if chan == CH_WINDOW:
            self.window_reads.add(int(idx))
        elif chan == CH_REGS_ROW:
            self.reg_reads.add(int(idx))

    def note_write(self, chan, idx):
        if not self.active:
            return
        if chan == CH_WINDOW:
            self.window_writes.add(int(idx))
        elif chan == CH_REGS_ROW:
            self.reg_writes.add(int(idx))

    # ---- patched-global callbacks -----------------------------------
    def note_finish(self, hot_word, writes, next_pc, block_a, block_b,
                    regs_row):
        self.finished = True
        self.hot_word = int(hot_word)
        self.declared_writes = [int(w) for w in writes]
        self.next_pc = int(next_pc)
        for b in (block_a, block_b):
            if b is not None and int(b) >= 0:
                self.block_words.add(int(b))
        row = np.asarray(regs_row)
        self.regs_row_len = int(row.shape[0]) if row.ndim == 1 else None


class TraceArray(np.ndarray):
    """ndarray that reports integer gathers/scatters to a Recorder."""

    def __array_finalize__(self, obj):
        self._rec = getattr(obj, "_rec", None)
        self._chan = getattr(obj, "_chan", None)

    def __getitem__(self, idx):
        rec = getattr(self, "_rec", None)
        if rec is not None and _intlike(idx):
            i = int(idx)
            if self.ndim == 1:
                rec.note_read(self._chan, i)
            n = self.shape[0]
            # jax dynamic gathers clamp instead of raising; record the
            # RAW index above so the lint sees the real address.
            i = max(min(i, n - 1), -n)
            out = super().__getitem__(i)
            if self.ndim == 2 and isinstance(out, TraceArray):
                out._chan = (CH_REGS_ROW if self._chan == CH_REGS
                             else None)
            return out
        return super().__getitem__(idx)

    @property
    def at(self):
        return _At(self)


class _At:
    def __init__(self, arr: TraceArray):
        self._arr = arr

    def __getitem__(self, idx):
        return _AtIdx(self._arr, idx)


class _AtIdx:
    def __init__(self, arr: TraceArray, idx):
        self._arr = arr
        self._idx = idx

    def set(self, val):
        return self._apply(val, accumulate=False)

    def add(self, val):
        return self._apply(val, accumulate=True)

    def _apply(self, val, *, accumulate):
        arr = self._arr
        idx = self._idx
        out = arr.copy()              # copy preserves subclass + recorder
        if not _intlike(idx):
            raise TypeError(
                f"TraceArray.at expects an integer index, got {idx!r}")
        i = int(idx)
        rec = getattr(arr, "_rec", None)
        if rec is not None:
            rec.note_write(arr._chan, i)
        n = arr.shape[0]
        if -n <= i < n:               # jax scatters drop OOB updates
            v = np.asarray(val, dtype=arr.dtype)
            if accumulate:
                out[i] = out[i] + v
            else:
                out[i] = v
        return out


def trace_array(values, rec: Recorder, chan=None) -> TraceArray:
    t = np.array(values, copy=True).view(TraceArray)
    t._rec = rec
    t._chan = chan
    return t


def traced_state(canon, env, layout, rec: Recorder) -> engine.SimState:
    """A full SimState over TraceArrays for one canonical model state
    (repro.analysis.model.Canon); timing fields take their init values.
    """
    P, W = env.P, layout.W
    f32 = np.float32
    crashed = np.asarray(canon.crashed, bool)
    # Crashed processes' leases are modeled as already expired (crash_t
    # = -INF): the model checker abstracts time away, so a canonical
    # crashed state stands for "any real time after lease expiry".
    crash_t = np.where(crashed, f32(-np.inf), f32(np.inf)).astype(f32)
    return engine.SimState(
        window=trace_array(canon.window, rec, CH_WINDOW),
        pc=trace_array(canon.pc, rec),
        regs=trace_array(canon.regs, rec, CH_REGS),
        t_ready=trace_array(np.zeros(P, f32), rec),
        blocked_a=trace_array(np.full(P, -1, np.int32), rec),
        blocked_b=trace_array(np.full(P, -1, np.int32), rec),
        backoff=trace_array(np.full(P, env.cost.backoff0, f32), rec),
        busy=trace_array(np.zeros(W, f32), rec),
        clock=f32(0.0), t_finish=f32(0.0),
        done=trace_array(canon.done, rec),
        events=np.int32(0),
        acq_count=trace_array(canon.acq, rec),
        lat_sum=trace_array(np.zeros(P, f32), rec),
        t_attempt=trace_array(np.zeros(P, f32), rec),
        writer_active=np.int32(canon.writer_active),
        reader_active=np.int32(canon.reader_active),
        violations=np.int32(canon.violations),
        hold_rank=np.int32(-1),
        local_passes=np.int32(0), total_passes=np.int32(0),
        crash_t=trace_array(crash_t, rec),
        revive_t=trace_array(np.full(P, np.inf, f32), rec),
        crashed=trace_array(crashed, rec),
        in_cs=trace_array(canon.in_cs, rec),
        restart_pc=trace_array(canon.pc, rec),
        restart_regs=trace_array(canon.regs, rec, CH_REGS),
        reclaims=np.int32(0), rec_retries=np.int32(0),
        t_recover=f32(np.inf))


class patched:
    """Context manager: route the engine tail calls of `handlers` (and
    of the engine module itself) through recorder-aware wrappers."""

    _NAMES = ("finish_instr", "cs_enter", "cs_exit")

    def __init__(self, handlers, rec: Recorder):
        self._rec = rec
        mods = {engine}
        for h in handlers:
            mod = sys.modules.get(getattr(h, "__module__", None))
            if mod is not None:
                mods.add(mod)
        self._mods = [m for m in mods
                      if any(hasattr(m, n) for n in self._NAMES)]
        self._saved = []

    def __enter__(self):
        rec = self._rec

        def finish(env, st, p, now, key, *, hot_word, writes, next_pc,
                   regs_row, block_a=None, block_b=None, **effect):
            rec.note_finish(hot_word, writes, next_pc, block_a, block_b,
                            regs_row)
            rec.active = False        # engine internals are not program
            try:                      # address expressions
                return _REAL_FINISH(
                    env, st, p, now, key, hot_word=hot_word,
                    writes=writes, next_pc=next_pc, regs_row=regs_row,
                    block_a=block_a, block_b=block_b, **effect)
            finally:
                rec.active = True

        def enter(env, st, p, now):
            rec.entered_cs = True
            rec.active = False
            try:
                return _REAL_CS_ENTER(env, st, p, now)
            finally:
                rec.active = True

        def exit_(env, st, p):
            rec.exited_cs = True
            rec.active = False
            try:
                return _REAL_CS_EXIT(env, st, p)
            finally:
                rec.active = True

        repl = {"finish_instr": finish, "cs_enter": enter,
                "cs_exit": exit_}
        for mod in self._mods:
            for name, fn in repl.items():
                if hasattr(mod, name):
                    self._saved.append((mod, name, getattr(mod, name)))
                    setattr(mod, name, fn)
        return rec

    def __exit__(self, *exc):
        for mod, name, orig in reversed(self._saved):
            setattr(mod, name, orig)
        self._saved = []
        return False


def record_step(handlers, env, layout, canon, pc: int, p: int,
                key) -> Recorder:
    """Replay one instruction eagerly and return its recorded effects.

    `canon` is a model state in which process `p` is at `pc`; the
    handler runs on a TraceArray-backed SimState under the patched
    engine tails. The returned Recorder holds both the observed window/
    register footprint and the declared finish_instr effects.
    """
    rec = Recorder()
    st = traced_state(canon, env, layout, rec)
    p, now = np.int32(p), np.float32(0.0)
    with patched(handlers, rec):
        eff = handlers[pc](p, now, key, st)
    if not rec.finished:
        raise RuntimeError(
            f"handler for pc {pc} returned without calling finish_instr")
    rec.active = False
    try:
        engine.apply_effect(env, st, eff, p, now)
    finally:
        rec.active = True
    return rec
