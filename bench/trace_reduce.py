"""From a profiler trace (``.xplane.pb``) to per-op device time, the busy
union, idle gaps, and host spans on the same clock.

The benchmark brackets its traced window with a host annotation named
``WINDOW``; every device interval is clipped to it. Device operations are
the events of each TPU plane's ``XLA Ops`` line, module executions those
of its ``XLA Modules`` line. An op event's name is its HLO instruction
text (``%fusion.369 = f32[8,1,16384]{...} fusion(...)``); control flow
(``%while``) nests its body's ops on the same line. A Pallas kernel is a
``tpu_custom_call`` whose instruction is named after the kernel
(``%ivf_probe_scores.11``, ``%mwem_step.4``). Nothing here touches a
device or describes a topology.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


_LAYOUT = re.compile(r"\{[^{}]*\}")


def instruction(name: str) -> str:
    """``fusion.369`` of ``%fusion.369 = f32[...] fusion(...)``."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


def base(name: str) -> str:
    """The instruction name without its numeric suffix (``fusion``)."""
    head = instruction(name)
    stem, _, tail = head.rpartition(".")
    return stem if stem and tail.isdigit() else head


def short(name: str, width: int = 120) -> str:
    """An op's instruction text without layouts, cut to ``width``."""
    return _LAYOUT.sub("", name)[:width]


@dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per chip
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per chip
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _events(line) -> Iterable[Event]:
    for e in line.events:
        yield Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)


def from_profile(pd) -> Trace:
    """Reduce a `jax.profiler.ProfileData` to the benchmark's view."""
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                # parents before the ops they hold
                if line.name == OPS_LINE:
                    ops[name] = sorted(_events(line),
                                       key=lambda e: (e.start, -e.end))
                elif line.name == MODULES_LINE:
                    modules[name] = sorted(_events(line),
                                           key=lambda e: (e.start, -e.end))
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    marks = [e for e in host if e.name == WINDOW]
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW!r} host annotation")
    window = (marks[0].start, marks[0].end)
    return Trace(window=window, ops=ops, modules=modules,
                 host=sorted(host, key=lambda e: e.start))


def load(trace_dir: str) -> Trace:
    """The trace a `jax.profiler.start_trace(trace_dir)` session wrote."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)))


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Intervals of ``events`` cut to [t0, t1], empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e.start, t0), min(e.end, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(trace: Trace) -> float:
    """Busy union of the device ops inside the window, averaged over chips."""
    if not trace.ops:
        return 0.0
    t0, t1 = trace.window
    per_chip = [sum(b - a for a, b in union(clip(ev, t0, t1)))
                for ev in trace.ops.values()]
    return sum(per_chip) / len(per_chip)


def idle_gaps(trace: Trace, chip: Optional[str] = None) -> List[Tuple[float, float]]:
    """Intervals of the window in which no op ran on ``chip`` (the first
    chip by default), longest first."""
    t0, t1 = trace.window
    if not trace.ops:
        return [(t0, t1)]
    chip = chip or sorted(trace.ops)[0]
    gaps, cursor = [], t0
    for a, b in union(clip(trace.ops[chip], t0, t1)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        gaps.append((cursor, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(trace: Trace, a: float, b: float) -> str:
    """What the host was doing in [a, b]: the innermost benchmark or
    service span that covers at least half of it, else the span covering
    most of it, or ``host idle``."""
    spans = []
    for e in trace.host:
        if e.name == WINDOW or e.end <= a or e.start >= b:
            continue
        if e.name.startswith(("bench/", "serve/", "mwem/")):
            spans.append((min(e.end, b) - max(e.start, a), e))
    if not spans:
        return "host idle"
    half = [e for c, e in spans if c >= 0.5 * (b - a)]
    if half:
        return min(half, key=lambda e: e.dur).name
    return max(spans, key=lambda ce: ce[0])[1].name


def is_custom_call(e: Event) -> bool:
    """A Pallas kernel's op: a ``tpu_custom_call`` by its instruction."""
    return 'custom_call_target="tpu_custom_call"' in e.name


def kernel_ops(trace: Trace, kernel: str) -> List[Event]:
    """Whole ops of ``kernel`` inside the window, over all chips."""
    t0, t1 = trace.window
    return [e for ev in trace.ops.values() for e in ev
            if base(e.name) == kernel and e.start >= t0 and e.end <= t1]


def leaves(events: List[Event]) -> List[Event]:
    """The ops that hold no other op (drops ``while`` and other parents,
    whose time their body's ops already count). ``events`` sorted by
    start, as one line nests them."""
    out = []
    for i, e in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt.start >= e.end:
            out.append(e)
    return out


def wave_modules(trace: Trace, marker: str) -> List[Tuple[Event, List[Event]]]:
    """Module executions wholly inside the window that run ``marker`` (a
    kernel every wave calls), each with the ops that ran inside it."""
    t0, t1 = trace.window
    out = []
    for chip, mods in trace.modules.items():
        ops = trace.ops.get(chip, [])
        i = 0
        for mod in mods:
            if mod.start < t0 or mod.end > t1:
                continue
            while i < len(ops) and ops[i].start < mod.start:
                i += 1
            j = i
            inside = []
            while j < len(ops) and ops[j].start < mod.end:
                inside.append(ops[j])
                j += 1
            if any(base(o.name) == marker for o in inside):
                out.append((mod, inside))
    return out


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` ops (leaves, by instruction) that took most device time
    in the window, each as [its short instruction text, seconds]."""
    t0, t1 = trace.window
    tot: Dict[str, float] = {}
    for ev in trace.ops.values():
        for e in leaves(ev):
            d = min(e.end, t1) - max(e.start, t0)
            if d > 0:
                key = short(e.name)
                tot[key] = tot.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
