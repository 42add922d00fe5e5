"""Host spans and counters of the program, and the scopes of its ops.

    with spans.span("session.run_batch"):
        ...

A span records its start and end on `time.perf_counter_ns`, the span
that was open around it, and the counters filed while it was the
innermost open span. Finished spans stay in memory in a bounded ring
(`records()`), and every name keeps a running total (`totals()`): how
often it ran, its seconds, and its self seconds, which leave out what
its child spans cover. Each span also opens a
`jax.profiler.TraceAnnotation` named `repro.<name>`, so that a profiler
recording shows it on the device trace's clock; with no profiler
recording the annotation costs about a microsecond.

`count(name, value)` files a counter under the innermost open span of
the calling thread and adds it to the process's totals (`counters()`).
This module holds the program's only `jax.monitoring` listeners. They
file JAX's compile events as counters:

  jit.trace_s      /jax/core/compile/jaxpr_trace_duration
  jit.traces       one per jaxpr trace
  jit.lower_s      /jax/core/compile/jaxpr_to_mlir_module_duration
  jit.compile_s    /jax/core/compile/backend_compile_duration, which
                   holds the persistent-cache lookup and load
  jit.cache_load_s /jax/compilation_cache/cache_retrieval_time_sec,
                   the part of jit.compile_s spent loading a cached
                   executable
  jit.cache_hits   /jax/compilation_cache/cache_hits

`note_dispatch` keeps what is needed to lower the last dispatched
program again: its jitted function, static arguments and the abstract
shapes of its arguments. `op_scopes()` maps each op of that program's
compiled HLO, by the name a device trace shows (`fusion.12`), to its
`op_name` scope (`jit(f)/vmap()/while/body/sched/argmin`).
"""
from __future__ import annotations

import collections
import dataclasses
import re
import threading
import time

import jax

RING_SIZE = 4096
ANNOTATION_PREFIX = "repro."

_DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load_s",
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Total:
    """Running totals of one span name."""

    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0


class Span:
    """One span: opened by `with`, kept in the ring once it ends."""

    __slots__ = ("name", "parent", "start_ns", "end_ns", "child_ns",
                 "counters", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.parent = None
        self.start_ns = self.end_ns = 0
        self.child_ns = 0
        self.counters = {}

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def self_seconds(self) -> float:
        return (self.end_ns - self.start_ns - self.child_ns) * 1e-9

    def __enter__(self) -> "Span":
        stack = _open_spans()
        if stack:
            self.parent = stack[-1].name
        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        stack = _open_spans()
        stack.pop()
        self._annotation.__exit__(*exc)
        self._annotation = None
        dur = self.end_ns - self.start_ns
        if stack:
            stack[-1].child_ns += dur
        with _lock:
            total = _totals.get(self.name)
            if total is None:
                total = _totals[self.name] = Total()
            total.count += 1
            total.total_s += dur * 1e-9
            total.self_s += (dur - self.child_ns) * 1e-9
            _ring.append(self)
        return False


_local = threading.local()
_lock = threading.Lock()
_ring = collections.deque(maxlen=RING_SIZE)
_totals = {}
_counters = collections.Counter()
_dispatched = None


def _open_spans() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str) -> Span:
    """A span `name`, to be opened with `with`."""
    return Span(name)


def count(name: str, value=1):
    """Add `value` to counter `name`, under the innermost open span."""
    stack = _open_spans()
    with _lock:
        _counters[name] += value
        if stack:
            c = stack[-1].counters
            c[name] = c.get(name, 0) + value


def records() -> list:
    """The finished spans still in the ring, oldest first."""
    with _lock:
        return list(_ring)


def totals() -> dict:
    """Per span name, its running `Total` (a copy)."""
    with _lock:
        return {k: dataclasses.replace(v) for k, v in _totals.items()}


def counters() -> collections.Counter:
    """The process's counter totals (a copy; absent names read 0)."""
    with _lock:
        return collections.Counter(_counters)


def _on_duration(event: str, duration_secs: float, **_):
    name = _DURATION_COUNTERS.get(event)
    if name is not None:
        count(name, duration_secs)
        if event == _TRACE_EVENT:
            count("jit.traces")


def _on_event(event: str, **_):
    if event == _CACHE_HIT_EVENT:
        count("jit.cache_hits")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


# ------------------------------------------------------------ op scopes
def note_dispatch(fn, static: tuple, args: tuple):
    """Remember a dispatch of `fn(*static, *args)`, a jitted function
    whose leading positional arguments `static` are static: only the
    shape, dtype and weak type of each array of `args` are kept, not
    the arrays (`jax.ShapeDtypeStruct`s are built when needed, as they
    cost ten times more)."""
    global _dispatched
    leaves, tree = jax.tree.flatten(args)
    _dispatched = (fn, static, tree, [_spec(x) for x in leaves])


def _spec(x) -> tuple:
    if not isinstance(x, jax.Array):
        x = jax.typeof(x)              # a Python scalar or NumPy array
    return x.shape, x.dtype, x.weak_type


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_FUSED = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")


def hlo_op_scopes(hlo_text: str) -> dict:
    """Op name -> `op_name` scope of each op outside fused computations
    of an HLO module's text (ops without metadata map to "")."""
    lines = hlo_text.splitlines()
    fused = {m.group(1) for line in lines
             for m in [_HLO_FUSED.search(line)] if m}
    out, inside = {}, False
    for line in lines:
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = _HLO_COMPUTATION.match(line)
            inside = m is not None and m.group(1) not in fused
            continue
        if not inside:
            continue
        m = _HLO_OP.match(line)
        if m:
            scope = _HLO_OP_NAME.search(line)
            out[m.group(1)] = scope.group(1) if scope else ""
    return out


def op_scopes() -> dict:
    """`hlo_op_scopes` of the last program `note_dispatch` saw ({} if
    none). Lowers and compiles it again, which JAX's caches answer for
    a program that was dispatched in this process."""
    if _dispatched is None:
        return {}
    fn, static, tree, leaves = _dispatched
    args = jax.tree.unflatten(tree, [
        jax.ShapeDtypeStruct(shape, dtype, weak_type=weak)
        for shape, dtype, weak in leaves])
    compiled = fn.lower(*static, *args).compile()
    return hlo_op_scopes(compiled.as_text())
