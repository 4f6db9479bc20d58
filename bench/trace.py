"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are those named ``/device:<KIND>:<n>``.  On each, the
``XLA Modules`` line holds one event per program run, named
``<program>(<fingerprint>)``; a device counts as busy while a program
runs.  (The per-operation line is not read: a stencil's time loop puts
hundreds of thousands of operations a second there.)  Host spans are the
``bench.*`` annotations this
benchmark's own files wrap around each call into a layer
(``jax.profiler.TraceAnnotation``).  All times are nanoseconds on the
trace's clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z_]+:\d+$")
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
RUN_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float
    end: float


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def program_name(module_event: str) -> str:
    """``jit_step_j(12)`` -> ``jit_step_j``: the program, not the launch."""
    return RUN_ID.sub("", module_event)


class Trace:
    def __init__(self, modules: Dict[str, List[Event]], spans: List[Event]) -> None:
        self.modules = modules  # device plane -> program-run events
        self.spans = spans  # bench.* host spans

    @classmethod
    def from_file(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        modules: Dict[str, List[Event]] = {}
        spans: List[Event] = []
        for plane in data.planes:
            device = bool(DEVICE_PLANE.match(plane.name))
            for line in plane.lines:
                if device and line.name == MODULES_LINE:
                    modules[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    ]
                elif not device:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns))
        return cls(modules, spans)

    # -- host spans ------------------------------------------------------------
    def span_intervals(self, name: str) -> List[Interval]:
        return sorted((s.start, s.end) for s in self.spans if s.name == name)

    def window(self, name: str = "bench.window") -> Interval:
        found = self.span_intervals(name)
        if not found:
            raise ValueError(f"the trace holds no {name!r} span")
        return found[0]

    # -- device time -----------------------------------------------------------
    def devices(self) -> List[str]:
        return sorted(d for d, evs in self.modules.items() if evs)

    def busy_intervals(self, device: str) -> List[Interval]:
        return union([(e.start, e.end) for e in self.modules.get(device, [])])

    def busy_seconds(self, window: Interval) -> float:
        """Seconds in ``window`` in which a program ran, averaged over the
        devices that ran anything."""
        devs = self.devices()
        if not devs:
            return 0.0
        busy = [total(clip(self.busy_intervals(d), window)) for d in devs]
        return sum(busy) / len(devs) * 1e-9

    def idle_share(self, window: Interval) -> float:
        """Share of ``window`` in which no program ran (0 to 1)."""
        return 1.0 - self.busy_seconds(window) / ((window[1] - window[0]) * 1e-9)

    def module_events(self, window: Interval, match: Callable[[str], bool]) -> List[Event]:
        """Program runs that start inside ``window`` and whose name matches."""
        lo, hi = window
        return [
            e
            for evs in self.modules.values()
            for e in evs
            if lo <= e.start < hi and match(e.name)
        ]

    def module_seconds(self, window: Interval, match: Callable[[str], bool]) -> float:
        return sum(e.end - e.start for e in self.module_events(window, match)) * 1e-9

    def modules_within(self, span_prefix: str) -> set:
        """Names of the program runs that started inside a host span whose
        name starts with ``span_prefix`` (a probe run alone on the host)."""
        found = set()
        for lo, hi in [(s.start, s.end) for s in self.spans if s.name.startswith(span_prefix)]:
            found |= {e.name for e in self.module_events((lo, hi), lambda _n: True)}
        return found

    # -- breakdown -------------------------------------------------------------
    def top_programs(self, window: Interval, k: int = 10) -> List[List]:
        by_name: Dict[str, float] = {}
        for e in self.module_events(window, lambda _n: True):
            name = program_name(e.name)
            by_name[name] = by_name.get(name, 0.0) + (e.end - e.start) * 1e-9
        n = max(len(self.modules), 1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        return [[name, secs / n] for name, secs in ranked]

    def host_activity(self, t: float, window: Interval) -> str:
        """The innermost bench span covering instant ``t``, leaving out the
        spans that cover the whole window (the window itself)."""
        covering = [
            s for s in self.spans
            if s.start <= t < s.end and not (s.start <= window[0] and s.end >= window[1])
        ]
        if not covering:
            return "host outside bench spans"
        return min(covering, key=lambda s: s.end - s.start).name

    def idle_gaps(self, window: Interval, k: int = 10) -> List[List]:
        """The longest stretches of ``window`` with no program on the
        first device, each named by what the host was doing at its middle."""
        devs = self.devices()
        if not devs:
            return []
        busy = clip(self.busy_intervals(devs[0]), window)
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [
            [self.host_activity(0.5 * (a + b), window), (b - a) * 1e-9] for a, b in gaps[:k]
        ]

    def breakdown(self, window: Interval) -> Dict[str, List[List]]:
        return {"device_ops": self.top_programs(window), "idle_gaps": self.idle_gaps(window)}
