"""The program's spans and counters, on the profiler's clock.

Every :func:`span` enters ``jax.profiler.TraceAnnotation``, so a sweep run
under ``jax.profiler.trace`` carries its stages on the ``/host:CPU`` plane
beside the device's ``XLA Modules`` events, with no flag.  While
:func:`record` is active the spans, the counters of :func:`count` and the
host memory samples of :func:`sample_rss` are also kept in memory, and
:func:`take` hands them out::

    from repro import obs
    with obs.record():
        rows = run_sweep(cells)
    rec = obs.take()
    rec.self_s("lane.dispatch"), rec.counters["lane.batches"]

With recording off a span costs one flag check plus the annotation, and
keeps nothing.  The span and counter names are listed in
``repro/uvm/backends/README.md`` ("Sweep pipeline").
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

#: the host memory sample's name
RSS_SAMPLE = "host.rss_mib"


@dataclasses.dataclass
class SpanRecord:
    """One recorded span.  ``end_ns`` is 0 while it is open; ``parent``
    is the ``id`` of the span that was open on the same thread when this
    one opened."""

    id: int
    name: str
    start_ns: int
    end_ns: int
    thread: int
    parent: Optional[int]
    attrs: Dict


@dataclasses.dataclass
class Recording:
    """What :func:`take` returns: spans in the order they opened,
    counters and samples by name."""

    spans: List[SpanRecord]
    counters: Dict[str, float]
    samples: Dict[str, List[float]]

    def named(self, name: str) -> List[SpanRecord]:
        return [s for s in self.spans if s.name == name]

    def total_s(self, name: str) -> float:
        """Summed duration of the closed spans called ``name``."""
        return sum(s.end_ns - s.start_ns for s in self.named(name)
                   if s.end_ns) / 1e9

    def self_s(self, *names: str) -> float:
        """Summed self time of the closed spans called one of ``names``:
        each span's duration minus the part of it that its child spans
        (same thread) cover."""
        children: Dict[int, List[SpanRecord]] = {}
        for s in self.spans:
            if s.parent is not None and s.end_ns:
                children.setdefault(s.parent, []).append(s)
        total = 0
        for s in self.spans:
            if s.name not in names or not s.end_ns:
                continue
            covered, t = 0, s.start_ns
            for c in sorted(children.get(s.id, ()),
                            key=lambda c: c.start_ns):
                lo, hi = max(c.start_ns, t), min(c.end_ns, s.end_ns)
                if hi > lo:
                    covered += hi - lo
                    t = hi
            total += s.end_ns - s.start_ns - covered
        return total / 1e9


class _Recorder:
    """The process's one recorder: spans, counters and samples from every
    thread land here while :attr:`on`."""

    def __init__(self) -> None:
        self.on = False
        self.lock = threading.Lock()
        self.local = threading.local()     # .stack: this thread's open spans
        self.next_id = 0
        self._clear()

    def _clear(self) -> None:
        self.spans: List[SpanRecord] = []
        self.counters: Dict[str, float] = {}
        self.samples: Dict[str, List[float]] = {}
        self.sampled = False

    def open(self, name: str, attrs: Dict) -> SpanRecord:
        stack = getattr(self.local, "stack", None)
        if stack is None:
            stack = self.local.stack = []
        if not self.sampled:
            self.sampled = True
            self.sample(RSS_SAMPLE, rss_mib())
        with self.lock:
            rec = SpanRecord(self.next_id, name, time.perf_counter_ns(), 0,
                             threading.get_ident(),
                             stack[-1].id if stack else None, attrs)
            self.next_id += 1
            self.spans.append(rec)
        stack.append(rec)
        return rec

    def close(self, rec: SpanRecord) -> None:
        rec.end_ns = time.perf_counter_ns()
        stack = self.local.stack
        if stack and stack[-1] is rec:
            stack.pop()
        elif rec in stack:
            stack.remove(rec)

    def sample(self, name: str, value: Optional[float]) -> None:
        if value is None:
            return
        with self.lock:
            self.samples.setdefault(name, []).append(value)


_REC = _Recorder()


def span(name: str, **attrs):
    """``with span(name, **attrs):`` marks one stage.  It always enters
    ``jax.profiler.TraceAnnotation(name, **attrs)``; while recording it
    also keeps a :class:`SpanRecord`."""
    if not _REC.on:
        return TraceAnnotation(name, **attrs)
    return _RecordedSpan(name, attrs)


class _RecordedSpan:
    __slots__ = ("_name", "_attrs", "_ann", "_rec")

    def __init__(self, name: str, attrs: Dict) -> None:
        self._name = name
        self._attrs = attrs
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self) -> "_RecordedSpan":
        self._ann.__enter__()
        self._rec = _REC.open(self._name, self._attrs)
        return self

    def __exit__(self, *exc) -> None:
        _REC.close(self._rec)
        self._ann.__exit__(*exc)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name``, while recording."""
    if _REC.on:
        with _REC.lock:
            _REC.counters[name] = _REC.counters.get(name, 0) + n


def sample_rss() -> None:
    """Add a ``host.rss_mib`` sample (the process's resident memory now),
    while recording."""
    if _REC.on:
        _REC.sample(RSS_SAMPLE, rss_mib())


def rss_mib() -> Optional[float]:
    """The process's current resident memory in MiB, from
    ``/proc/self/statm``; None where that file does not exist."""
    try:
        with open("/proc/self/statm") as f:
            resident = int(f.read().split()[1])
    except (OSError, IndexError, ValueError):
        return None
    return resident * os.sysconf("SC_PAGE_SIZE") / 2 ** 20


@contextlib.contextmanager
def record() -> Iterator[None]:
    """Keep spans, counters and samples while the block runs (one
    recording at a time); :func:`take` hands them out."""
    if _REC.on:
        raise RuntimeError("obs.record() is already active")
    _REC.on = True
    try:
        yield
    finally:
        _REC.on = False


def take() -> Recording:
    """The spans, counters and samples kept so far; clears them.  The
    first span recorded after a take samples ``host.rss_mib`` again."""
    with _REC.lock:
        out = Recording(_REC.spans, _REC.counters, _REC.samples)
        _REC._clear()
    return out
