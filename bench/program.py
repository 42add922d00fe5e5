"""What a reader takes from the program's own spans, counters and scopes.

The program keeps its host spans and counters in memory
(`repro.core.spans`): each span's name, start and end on the clock of
`time.perf_counter`, and the counters filed while it was the innermost
open span, among them the seconds of JAX's compile events. It also maps
each op of its last dispatched program to the named scope the op came
from. Set-up is what started before the window's first call.

A checkout whose program has no such module gives nothing: every
function here then returns None, and so does the reader.
"""
from __future__ import annotations

import statistics


def spans_module():
    """`repro.core.spans`, or None where the program has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans


def window_start_ns(driver) -> int | None:
    """The first timed call's start, on the program spans' clock."""
    calls = getattr(driver, "calls", None)
    if not calls:
        return None
    return int(calls[0][2] * 1e9)


def _records(ctx):
    spans = spans_module()
    t0 = window_start_ns(ctx.get("driver"))
    if spans is None or t0 is None:
        return None, None
    return spans.records(), t0


def setup_seconds(ctx, name: str) -> float | None:
    """Seconds of the spans `name` that ran in set-up."""
    records, t0 = _records(ctx)
    if records is None:
        return None
    spans = [r for r in records if r.name == name and r.start_ns < t0]
    return sum(r.seconds for r in spans) if spans else None


def setup_counter(ctx, counter: str) -> float | None:
    """Counter `counter` filed under `session.*` spans in set-up."""
    records, t0 = _records(ctx)
    if records is None:
        return None
    spans = [r for r in records
             if r.name.startswith("session.") and r.start_ns < t0]
    if not spans:
        return None
    return float(sum(r.counters.get(counter, 0) for r in spans))


def window_median_ms(ctx, name: str) -> float | None:
    """Median duration of the spans `name` of the window's calls, ms."""
    records, t0 = _records(ctx)
    if records is None:
        return None
    got = [r.seconds for r in records if r.name == name and r.start_ns >= t0]
    return statistics.median(got) * 1e3 if got else None


def scope_us_per_trip(ctx, scope: str) -> float | None:
    """Device time per trip of the step loop in ops whose named scope
    holds `scope` (as `/sched/`), in microseconds: their seconds in the
    traced slice (the mean over the devices) over the devices' mean
    trips, counted as `sim.us_per_trip` counts them."""
    trace, spans = ctx.get("trace"), spans_module()
    if trace is None or spans is None or not trace.devices:
        return None
    scopes = spans.op_scopes()
    trips = [t for t in trace.trips() if t > 0]
    if not scopes or not trips:
        return None
    seconds = trace.op_seconds(lambda op: scope in scopes.get(op, ""))
    return sum(seconds.values()) / (sum(trips) / len(trips)) * 1e6
