"""The per-layer metric read from the program's spans in the trace, on a
small synthetic trace laid out as the chip lays it out: the main thread
and two flush threads, all on interpreter lines."""
from types import SimpleNamespace as NS

import pytest

from bench import harness
from bench import trace_reduce as tr

METRIC = "sched_host_ms_per_batch"


def _ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def _planes(program_spans=True):
    ms = 1_000_000
    main = [_ev("bench.grid", 0, 100 * ms)]
    flush_a, flush_b = [], []
    if program_spans:
        main += [
            _ev("sweep.run", 1 * ms, 99 * ms),
            _ev("sweep.pack", 2 * ms, 3 * ms),
            _ev("sweep.pack", 4 * ms, 10 * ms),
            _ev("sweep.await", 5 * ms, 9 * ms),      # inside the pack
            _ev("sweep.await", 60 * ms, 90 * ms),    # after the loop
        ]
        flush_a += [
            _ev("sweep.prepare", 1 * ms, 2 * ms),
            _ev("lane.batch", 3 * ms, 50 * ms),
            _ev("lane.pad", 3 * ms, 5 * ms),
            _ev("lane.dispatch", 5 * ms, 6 * ms),
            _ev("lane.fetch", 6 * ms, 48 * ms),
            _ev("lane.unpack", 48 * ms, 49 * ms),
            _ev("sweep.finish_rows", 49 * ms, 50 * ms),
        ]
        flush_b += [
            _ev("sweep.prepare", 1 * ms, 4 * ms),
            _ev("trace.build", 2 * ms, 3 * ms),      # inside a prepare
            _ev("lane.batch", 10 * ms, 90 * ms),
            _ev("lane.pad", 10 * ms, 11 * ms),
            _ev("lane.dispatch", 11 * ms, 13 * ms),
            _ev("lane.fetch", 13 * ms, 88 * ms),
            _ev("lane.unpack", 88 * ms, 89 * ms),
            _ev("sweep.finish_rows", 89 * ms, 90 * ms),
        ]
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=main), NS(name="python", events=flush_a),
        NS(name="python", events=flush_b)])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[
        _ev("jit_wrapped(1)", 7 * ms, 47 * ms),
        _ev("jit_wrapped(2)", 47 * ms, 88 * ms)])])
    return [host, dev]


def test_self_time_of_the_scheduler_stages_per_batch():
    red = tr.reduce_planes(_planes())
    # prepare 1 + (3 - 1 of trace.build), pack 1 + (6 - 4 of await),
    # pad 2 + 1, dispatch 1 + 2, unpack 1 + 1, finish rows 1 + 1
    # = 16 ms over 2 lane batches
    assert harness.load_reader(METRIC)(NS(reduced=red)) == pytest.approx(8.0)


def test_nothing_without_the_programs_spans():
    """The parent's program has no spans: the reader gives nothing."""
    red = tr.reduce_planes(_planes(program_spans=False))
    assert harness.load_reader(METRIC)(NS(reduced=red)) is None
