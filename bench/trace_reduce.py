"""Reduction of a profiler trace to the benchmark's per-layer numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes (with nothing but
JAX's ``ProfileData``).  The device side is the ``XLA Modules`` line of
each ``/device:TPU:<n>`` plane: one event per execution of a compiled
program, named ``<jit name>(<fingerprint>)``.  The host side is the
benchmark's own ``TraceAnnotation`` spans and JAX's host events on the
interpreter's threads (the lines of the ``/host:CPU`` plane named after
the interpreter, ``python`` or ``python3``), on the same clock.

* busy: the union of the module intervals of a device, averaged over
  the devices used; the window is the benchmark's outermost span;
* idle gaps: the stretches of the window in which no module ran, each
  named by the innermost host event that covers its middle and the
  module the device ran next;
* per module: total device seconds and execution counts, keyed by the
  jit name without its fingerprint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

#: the benchmark's outermost host span (``harness.run_window``)
WINDOW_SPAN = "bench.grid"
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


def module_name(event_name: str) -> str:
    """``jit_step_fn(5667094546585153720)`` -> ``jit_step_fn``."""
    return _FINGERPRINT.sub("", event_name)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that ``busy`` (merged) leaves idle."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduced:
    window: Interval                       # ns, host clock
    busy: List[Interval]                   # merged, clipped, one device
    n_devices: int
    modules: Dict[str, float]              # jit name -> device seconds
    module_counts: Dict[str, int]
    module_events: List[Tuple[int, int, str]]   # (start, end, jit name)
    host_events: List[Tuple[int, int, str]]     # (start, end, name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) / 1e9 / max(self.n_devices, 1)

    def module_seconds(self, prefix: str) -> float:
        return sum(v for k, v in self.modules.items() if k.startswith(prefix))

    def module_count(self, prefix: str) -> int:
        return sum(v for k, v in self.module_counts.items()
                   if k.startswith(prefix))

    def named_gaps(self) -> List[Tuple[str, float]]:
        """Each idle gap as (what the host did, seconds)."""
        out = []
        starts = [m[0] for m in self.module_events]
        for s, e in gaps(self.busy, *self.window):
            mid = (s + e) // 2
            cover = [h for h in self.host_events if h[0] <= mid < h[1]]
            host = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
                else "no host event"
            k = bisect.bisect_left(starts, e)
            nxt = (self.module_events[k][2] if k < len(starts)
                   else "end of window")
            out.append((f"{host} -> {nxt}", (e - s) / 1e9))
        return out

    def breakdown(self) -> Dict[str, List]:
        """The ten costliest device modules and idle-gap kinds."""
        by_gap: Dict[str, float] = collections.defaultdict(float)
        for name, sec in self.named_gaps():
            by_gap[name] += sec
        top = sorted(self.modules.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in sorted(
                    by_gap.items(), key=lambda kv: -kv[1])[:10]]}


def _device_planes(planes) -> list:
    return [(p, lines) for p, lines in planes
            if p.name.startswith("/device:TPU:")
            and any(ln.name == "XLA Modules" for ln in lines)]


def reduce_planes(planes) -> Reduced:
    """Reduce ``ProfileData.planes`` (or objects shaped like them).  JAX
    hands the planes out as a one-shot iterator: they are read once."""
    planes = [(p, list(p.lines)) for p in planes]
    host_events: List[Tuple[int, int, str]] = []
    windows: List[Interval] = []
    for p, lines in planes:
        if p.name != "/host:CPU":
            continue
        for ln in lines:
            if not ln.name.startswith("python"):   # interpreter threads
                continue
            for ev in ln.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                host_events.append((s, e, ev.name))
                if ev.name == WINDOW_SPAN:
                    windows.append((s, e))
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span on /host:CPU")
    window = (min(w[0] for w in windows), max(w[1] for w in windows))
    devs = _device_planes(planes)
    if not devs:
        raise ValueError("trace has no /device:TPU plane with an "
                         "'XLA Modules' line")
    busy_all: List[Interval] = []
    modules: Dict[str, float] = collections.defaultdict(float)
    counts: Dict[str, int] = collections.defaultdict(int)
    mod_events: List[Tuple[int, int, str]] = []
    for _p, lines in devs:
        ivs = []
        for ln in lines:
            if ln.name != "XLA Modules":
                continue
            for ev in ln.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if e <= window[0] or s >= window[1]:
                    continue
                name = module_name(ev.name)
                modules[name] += (e - s) / 1e9
                counts[name] += 1
                ivs.append((s, e))
                mod_events.append((s, e, name))
        busy_all += clip(union(ivs), *window)
    host_events = [h for h in host_events
                   if h[1] > window[0] and h[0] < window[1]]
    return Reduced(window=window, busy=union(busy_all) if len(devs) == 1
                   else busy_all, n_devices=len(devs),
                   modules=dict(modules), module_counts=dict(counts),
                   module_events=sorted(mod_events),
                   host_events=host_events)


def reduce_dir(log_dir: str) -> Reduced:
    """Reduce the one trace ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(files)}")
    return reduce_planes(ProfileData.from_file(files[0]).planes)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader gets: the reduced trace, the cell,
    the window's rows and counters, and the device."""

    reduced: Reduced
    cell: object
    rows: List[Dict]
    window: object                  # harness.Window
    lane_accesses: int
    device_kind: str
    program_traces: Dict = dataclasses.field(default_factory=dict)
