"""Reduction of a profiler trace to device busy time, idle gaps and op time.

A `--trace 1` run records the window with `jax.profiler` and reads the
`.xplane.pb` back with `jax.profiler.ProfileData`. Device planes are
named `/device:TPU:<n>`; their "XLA Ops" line holds one event per
operation the core ran, named by its HLO text (`%fusion.5 = ...`), with
its start and duration in nanoseconds on the clock the host spans use.
Async copies sit on another line and overlap compute; they are not
counted as busy. The benchmark's own host spans (`TraceAnnotation`s
named `bench.*`) sit on the host plane and give the window's bounds and
what the host was doing during each idle gap.

  busy   the union of a device's op intervals inside the window
  idle   the window less busy
  ops    seconds per op name (the mean over the devices used)
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def op_name(text: str) -> str:
    """`fusion.5` from an op event's HLO text `%fusion.5 = s32[...] ...`."""
    return text.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class DeviceOps:
    """The op events of one device: (start_ns, end_ns, name)."""

    name: str
    ops: list


@dataclasses.dataclass
class TraceSummary:
    window: tuple                  # (start_ns, end_ns)
    devices: list                  # [DeviceOps]
    spans: list                    # [(start_ns, end_ns, name)] host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, dev: DeviceOps) -> list:
        lo, hi = self.window
        return clip(union_ns((s, e) for s, e, _ in dev.ops), lo, hi)

    def busy_s_by_device(self) -> list:
        """Seconds in which an op ran, per device."""
        return [sum(e - s for s, e in self.busy_intervals(d)) * 1e-9
                for d in self.devices]

    def busy_s(self) -> float:
        """Seconds in which an op ran, the mean over the devices."""
        busy = self.busy_s_by_device()
        return sum(busy) / len(busy) if busy else 0.0

    def idle_share(self) -> float | None:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, match=None) -> dict:
        """Seconds per op name inside the window (mean over devices);
        `match(name)` keeps only the ops it accepts."""
        lo, hi = self.window
        out = collections.Counter()
        for d in self.devices:
            for s, e, name in d.ops:
                if (match is None or match(name)) and e > lo and s < hi:
                    out[name] += (min(e, hi) - max(s, lo)) * 1e-9
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def trips(self) -> list:
        """Per device, how often its most frequent op ran in the window:
        the trips of a loop whose body is the bulk of the ops."""
        lo, hi = self.window
        out = []
        for d in self.devices:
            n = collections.Counter(name for s, e, name in d.ops
                                    if s >= lo and e <= hi)
            out.append(max(n.values()) if n else 0)
        return out

    def gaps(self) -> list:
        """Idle gaps of every device: (start_ns, end_ns)."""
        lo, hi = self.window
        out = []
        for d in self.devices:
            t = lo
            for s, e in self.busy_intervals(d):
                if s > t:
                    out.append((t, s))
                t = max(t, e)
            if hi > t:
                out.append((t, hi))
        return out

    def host_label(self, start: int, end: int) -> str:
        """The host span that covers most of [start, end): the innermost
        of those that cover it equally."""
        best, best_cover, best_len = "no bench span", 0, None
        for s, e, name in self.spans:
            if name == WINDOW_SPAN:
                continue
            cover = min(e, end) - max(s, start)
            if cover <= 0:
                continue
            if cover > best_cover or (cover == best_cover
                                      and e - s < best_len):
                best, best_cover, best_len = name, cover, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[name, s] for name, s in ops[:top]],
            "idle_gaps": [[self.host_label(s, e), (e - s) * 1e-9]
                          for s, e in gaps],
        }


def read_xspace(path: str) -> TraceSummary:
    """Reduce one `.xplane.pb` to its device ops and bench host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            op_name(ev.name)) for ev in line.events]
                    if ops:
                        devices.append(DeviceOps(plane.name, ops))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return summarize(devices, spans)


def summarize(devices: list, spans: list) -> TraceSummary:
    """The window is the `bench.window` host span, else the ops' extent."""
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        window = (min(s for s, _ in win), max(e for _, e in win))
    else:
        all_ops = [op for d in devices for op in d.ops]
        window = (min(s for s, _, _ in all_ops),
                  max(e for _, e, _ in all_ops)) if all_ops else (0, 0)
    devices = sorted(devices, key=lambda d: d.name)
    return TraceSummary(window=window, devices=devices, spans=spans)


class Tracer:
    """Profiles what a run asks it to; host spans cost nothing else.

    `window(True)` records the device and the bench spans while its
    block runs; `read()` reduces the recording after the run's window,
    so that the reduction's own time stays out of it.
    """

    def __init__(self):
        self.summary = None
        self._dir = None

    @contextlib.contextmanager
    def window(self, enabled: bool):
        import jax

        if not enabled:
            yield
            return
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._dir)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def read(self):
        """The recorded window's TraceSummary (None if nothing was)."""
        if self._dir is None:
            return None
        try:
            paths = glob.glob(os.path.join(self._dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if paths:
                self.summary = read_xspace(paths[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        return self.summary

    def span(self, name: str):
        """A host span `bench.<name>` in the recording."""
        import jax

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
