"""The one reduction from a profiler trace to device times.

`jax.profiler` writes an `.xplane.pb`; `jax.profiler.ProfileData` reads it
with nothing but JAX. A TPU shows up as a plane `/device:TPU:<n>` with,
among others, a line `XLA Modules` (one event per executed program, so one
per train step) and a line `XLA Ops` (one event per operation). The host
shows up as `/host:CPU`, with one line per thread, on which the benchmark's
`jax.profiler.TraceAnnotation`s appear by name.

Everything is reduced over a **steady window**: from the start of the first
step event that is kept to the end of the last one, on each device's own
plane. Per-step numbers divide by the step events in that window, so
dispatch that ran ahead of the device (which made the old reduction in
`tools/tpu_perf_session.py` count more device time than wall time) cannot
leak work in or out.

Times are seconds on the trace's clock. `clock_offset` (perf_counter minus
trace clock) comes from the `bench.clock_sync` marks and lets spans taken
with `perf_counter` be laid over the device's idle gaps.
"""

from __future__ import annotations

import functools
import gzip
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES, OPS = "XLA Modules", "XLA Ops"
CLOCK_SYNC = "bench.clock_sync"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


# ------------------------------------------------------------ interval sets
def union(intervals) -> list:
    """Sorted, disjoint `[(start, end)]` covering the same points."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def complement(disjoint, lo: float, hi: float) -> list:
    """The parts of `[lo, hi]` that the sorted, disjoint intervals leave."""
    out, at = [], lo
    for s, e in disjoint:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


# ------------------------------------------------------------------ a line
class Line:
    """The events of one line: names, and start and end in seconds."""

    def __init__(self, names, start, end):
        start = np.asarray(start, np.float64)
        end = np.asarray(end, np.float64)
        order = np.lexsort((-end, start))  # by start; the enclosing one first
        self.names = [names[i] for i in order]
        self.start, self.end = start[order], end[order]

    def __len__(self) -> int:
        return len(self.names)

    def within(self, lo: float, hi: float) -> "Line":
        """Events that start inside `[lo, hi)`."""
        keep = np.flatnonzero((self.start >= lo) & (self.start < hi))
        return Line([self.names[i] for i in keep], self.start[keep],
                    self.end[keep])

    def intervals(self) -> list:
        return list(zip(self.start.tolist(), self.end.tolist()))

    @functools.cached_property
    def self_seconds(self) -> np.ndarray:
        """Each event's duration less that of the events nested in it, so
        that a sum over events never counts a moment twice."""
        own = self.end - self.start
        stack = []
        for i in range(len(self.names)):
            while stack and self.end[stack[-1]] <= self.start[i]:
                stack.pop()
            if stack:
                own[stack[-1]] -= min(self.end[i], self.end[stack[-1]]) \
                    - self.start[i]
            stack.append(i)
        return np.maximum(own, 0.0)

    def seconds_by_name(self, key=None) -> dict:
        """Self seconds summed by name, or by `key(name)`."""
        out = {}
        for name, s in zip(self.names, self.self_seconds.tolist()):
            name = name if key is None else key(name)
            out[name] = out.get(name, 0.0) + s
        return out


def short_name(event_name: str) -> str:
    """On a TPU an operation's event is named by its whole HLO instruction,
    `%fusion.12 = bf16[...] fusion(...), kind=...`; what is kept is the
    instruction's own name, `fusion.12`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kind_of(name: str) -> str:
    """`fusion.12` and `fusion.13` are of one kind, `fusion`."""
    return re.sub(r"\.\d+$", "", name)


def _read_line(line) -> Line:
    names, start, end = [], [], []
    for event in line.events:
        names.append(short_name(event.name))
        start.append(event.start_ns * 1e-9)
        end.append((event.start_ns + event.duration_ns) * 1e-9)
    return Line(names, start, end)


# ------------------------------------------------------------- one device
class DevicePlane:
    """One chip's plane over its steady window."""

    def __init__(self, ordinal: int, modules: Line, ops: Line,
                 skip_steps: int):
        self.ordinal = ordinal
        # the step is the program the device spent most time in
        by_name = modules.seconds_by_name()
        self.step_name = max(by_name, key=by_name.get)
        mine = [i for i, n in enumerate(modules.names) if n == self.step_name]
        all_steps = Line([modules.names[i] for i in mine],
                         modules.start[mine], modules.end[mine])
        if len(all_steps) <= skip_steps:
            raise ValueError(f"device {ordinal}: {len(all_steps)} step "
                             f"event(s) in the trace, {skip_steps} to skip")
        self.window = (float(all_steps.start[skip_steps]),
                       float(all_steps.end[-1]))
        self.steps = all_steps.within(*self.window)
        self.ops = ops.within(*self.window)
        self.busy = clip(union(self.ops.intervals()), *self.window)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return total(self.busy)

    def idle_gaps(self) -> list:
        return complement(self.busy, *self.window)

    def step_seconds(self) -> np.ndarray:
        return self.steps.end - self.steps.start

    def op_seconds(self, pattern=None) -> float:
        """Device seconds, over the window, of the operations whose name
        `pattern` matches (all of them without one)."""
        by_name = self.ops.seconds_by_name()
        return float(sum(s for n, s in by_name.items()
                         if pattern is None or pattern.search(n)))


# ------------------------------------------------------------ whole trace
class DeviceTrace:
    def __init__(self, planes: dict, marks: dict):
        self.planes = planes        # ordinal -> DevicePlane
        self.marks = marks          # annotation name -> [(start, end)]

    @property
    def first(self) -> DevicePlane:
        return self.planes[min(self.planes)]

    @property
    def busy_s(self) -> float:
        return float(np.mean([p.busy_s for p in self.planes.values()]))

    @property
    def window_s(self) -> float:
        return float(np.mean([p.window_s for p in self.planes.values()]))

    def clock_offset(self, perf_counter_marks) -> float:
        """`perf_counter` minus the trace's clock: each `bench.clock_sync`
        annotation was opened just before `perf_counter` was read."""
        starts = [s for s, _ in self.marks.get(CLOCK_SYNC, ())]
        if not starts or len(starts) != len(perf_counter_marks):
            raise ValueError(f"{len(starts)} {CLOCK_SYNC} mark(s) in the "
                             f"trace, {len(perf_counter_marks)} taken")
        return float(np.mean(np.asarray(perf_counter_marks)
                             - np.asarray(sorted(starts))))

    def idle_by_host_span(self, spans, offset: float) -> dict:
        """The first device's idle seconds, by which spans were open on the
        host meanwhile. `spans` are `(name, start, end)` on `perf_counter`'s
        clock; the key joins the names open at that moment with `+`, and is
        `''` where none was (the loop itself, or dispatch)."""
        plane = self.first
        lo, hi = plane.window
        shifted = [(n, s - offset, e - offset) for n, s, e in spans
                   if e - offset > lo and s - offset < hi]
        cuts = sorted({lo, hi} | {t for _, s, e in shifted for t in (s, e)
                                  if lo < t < hi})
        out = {}
        for gap_lo, gap_hi in plane.idle_gaps():
            inner = [gap_lo] + [c for c in cuts if gap_lo < c < gap_hi] \
                + [gap_hi]
            for a, b in zip(inner, inner[1:]):
                mid = (a + b) / 2
                key = "+".join(sorted({n for n, s, e in shifted
                                       if s <= mid < e}))
                out[key] = out.get(key, 0.0) + (b - a)
        return out


def load(path: str, skip_steps: int = 0):
    """The `DeviceTrace` of an `.xplane.pb` (or a gzipped one), or None
    where it holds no TPU plane with a step on it (a trace taken on the
    CPU)."""
    import jax

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    planes, marks = {}, {}
    for plane in data.planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            lines = {line.name: line for line in plane.lines}
            if MODULES in lines and OPS in lines:
                modules = _read_line(lines[MODULES])
                if len(modules):
                    planes[int(found.group(1))] = DevicePlane(
                        int(found.group(1)), modules, _read_line(lines[OPS]),
                        skip_steps)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith("bench."):
                        marks.setdefault(event.name, []).append(
                            (event.start_ns * 1e-9,
                             (event.start_ns + event.duration_ns) * 1e-9))
    return DeviceTrace(planes, marks) if planes else None
