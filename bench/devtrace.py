"""Profiler trace of the measured window and its reduction to numbers.

``Tracer`` wraps ``jax.profiler`` with the Python tracer off (it would
record every Python call of the serving loop) and returns the
``ProfileData`` of the run. ``device_ops`` reads the device planes' op
events; ``reduce`` turns events into busy time (the union of their
intervals), idle share, time per op name and the longest idle gaps, each
named after the host span that covers most of it.

Events are ``(name, start_ns, duration_ns)`` tuples in the profiler's
clock, so the reduction is a pure function that a test can feed.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]

WINDOW_SPAN = "bench.window"     # host annotation around the measured window
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Tracer:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self):
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            raise RuntimeError(f"no trace written under {self.log_dir}")
        return ProfileData.from_file(files[-1])


def line_events(profile, plane_pred: Callable[[str], bool],
                line_pred: Callable[[str], bool]) -> List[Event]:
    out = []
    for plane in profile.planes:
        if not plane_pred(plane.name):
            continue
        for line in plane.lines:
            if line_pred(line.name):
                out.extend((e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events)
    return out


def device_planes(profile) -> List[str]:
    return [p.name for p in profile.planes if p.name.startswith(DEVICE_PLANE)]


def device_ops(profile, plane: Optional[str] = None) -> List[Event]:
    return line_events(
        profile, lambda n: n == plane if plane else n.startswith(DEVICE_PLANE),
        lambda n: n == OPS_LINE)


def device_modules(profile) -> List[Event]:
    return line_events(profile, lambda n: n.startswith(DEVICE_PLANE),
                       lambda n: n == MODULES_LINE)


def host_spans(profile) -> List[Event]:
    return line_events(profile, lambda n: n.startswith("/host:"),
                       lambda n: True)


def describe(profile, top: int = 12) -> dict:
    """Plane and line names of the trace, and each device line's most
    frequent event families: what a reader of the trace needs to know."""
    out = {}
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            fam: Dict[str, int] = defaultdict(int)
            for e in line.events:
                fam[op_family(e.name)] += 1
            lines[line.name] = sorted(fam.items(), key=lambda kv: -kv[1])[
                :top if plane.name.startswith(DEVICE_PLANE) else 3]
        out[plane.name] = lines
    return out


def window_bounds(profile) -> Optional[Tuple[float, float]]:
    spans = [e for e in host_spans(profile) if e[0] == WINDOW_SPAN]
    if not spans:
        return None
    _, s, d = spans[0]
    return s, s + d


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to [t0, t1]; those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by the events."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


_SUFFIX = re.compile(r"[.:]\d+$")


def op_family(name: str) -> str:
    """Op name without its numeric instance suffix (fusion.12 -> fusion).
    The TPU names an op event by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): only the name before ``=``
    is kept."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", name)


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    by_name: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[float, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(events: Iterable[Event], t0: float, t1: float) -> Reduction:
    """Busy time, time per op family and idle gaps of device events in
    [t0, t1] (nanoseconds in, seconds out)."""
    ev = clip(events, t0, t1)
    iv = union(ev)
    busy = sum(b - a for a, b in iv)
    by: Dict[str, float] = defaultdict(float)
    for name, _, d in ev:
        by[op_family(name)] += d / 1e9
    edges = [t0] + [x for ab in iv for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return Reduction(window_s=(t1 - t0) / 1e9, busy_s=busy / 1e9,
                     by_name=dict(by), gaps=gaps)


def name_gaps(gaps: List[Tuple[float, float]], spans: List[Event],
              top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps, each named after the host span (other than
    the window's own) that covers most of it."""
    out = []
    spans = [s for s in spans if s[0] != WINDOW_SPAN and s[2] > 0]
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host: no span", 0.0
        for name, s, d in spans:
            c = min(b, s + d) - max(a, s)
            if c > cover:
                best, cover = name, c
        out.append((best, (b - a) / 1e9))
    return out
