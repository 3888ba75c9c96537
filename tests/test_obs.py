"""Spans and counters (``repro.obs``): recording off keeps nothing, self
time, per-thread parents, counters; the sweep's and the predictor's spans
under a recording, with rows identical to an unrecorded run."""
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.traces.trace import Trace, make_records
from repro.uvm import predcache
from repro.uvm.sweep import expand_grid, run_sweep

LANE_STAGES = ("lane.pad", "lane.dispatch", "lane.fetch", "lane.unpack")


@pytest.fixture(autouse=True)
def _empty_recorder():
    obs.take()
    yield
    obs.take()


def test_recording_off_keeps_nothing_and_allocates_no_record(monkeypatch):
    made = []

    class Counting(obs.SpanRecord):
        def __init__(self, *a, **k):
            made.append(1)
            super().__init__(*a, **k)

    monkeypatch.setattr(obs, "SpanRecord", Counting)
    with obs.span("outer", batch=1):
        with obs.span("inner"):
            obs.count("things", 3)
        obs.sample_rss()
    assert made == []
    rec = obs.take()
    assert rec.spans == [] and rec.counters == {} and rec.samples == {}
    with obs.record():
        with obs.span("outer"):
            pass
    assert made == [1]


def test_nested_spans_give_self_time():
    with obs.record():
        with obs.span("outer", batch=7):
            time.sleep(0.02)
            with obs.span("inner"):
                time.sleep(0.03)
            with obs.span("inner"):
                time.sleep(0.01)
    rec = obs.take()
    outer, = rec.named("outer")
    inners = rec.named("inner")
    assert outer.attrs == {"batch": 7} and outer.parent is None
    assert [s.parent for s in inners] == [outer.id, outer.id]
    child_ns = sum(s.end_ns - s.start_ns for s in inners)
    assert rec.self_s("outer") == pytest.approx(
        (outer.end_ns - outer.start_ns - child_ns) / 1e9)
    assert rec.self_s("outer") >= 0.02
    assert rec.self_s("inner") == pytest.approx(rec.total_s("inner"))
    assert rec.self_s("outer", "inner") == pytest.approx(
        rec.total_s("outer"))
    # the first recorded span sampled the host's memory
    assert len(rec.samples[obs.RSS_SAMPLE]) == 1
    assert rec.samples[obs.RSS_SAMPLE][0] > 0


def test_spans_on_two_threads_keep_their_own_parents():
    barrier = threading.Barrier(2, timeout=10)

    def work(n):
        with obs.span("lane.batch", batch=n):
            barrier.wait()          # both batches open at once
            with obs.span("lane.dispatch"):
                barrier.wait()

    with obs.record():
        threads = [threading.Thread(target=work, args=(n,))
                   for n in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    rec = obs.take()
    batches = {s.id: s for s in rec.named("lane.batch")}
    dispatches = rec.named("lane.dispatch")
    assert len(batches) == 2 and len(dispatches) == 2
    for d in dispatches:
        assert batches[d.parent].thread == d.thread
    assert {batches[d.parent].attrs["batch"] for d in dispatches} == {0, 1}


def test_counters_add_up_across_threads():
    def work():
        for _ in range(500):
            obs.count("lane.lanes", 2)
            obs.count("lane.batches")

    with obs.record():
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    obs.count("lane.batches")                # recording is off again
    rec = obs.take()
    assert rec.counters == {"lane.lanes": 4000, "lane.batches": 2000}


def test_one_recording_at_a_time():
    with obs.record():
        with pytest.raises(RuntimeError):
            with obs.record():
                pass
    with obs.span("after"):
        obs.count("after")
    assert obs.take().spans == []


def _strip_timing(rows):
    return [{k: v for k, v in r.items() if k != "seconds"} for r in rows]


def test_pallas_grid_records_every_lane_stage():
    """One ``lane.batch`` per batch, each with its four stages as
    children; ``lane.batches`` counts them; the rows are those of an
    unrecorded run."""
    cells = expand_grid(["ATAX"], ["none", "tree"], scales=[0.25],
                        device_fracs=[0.5], evictions=["lru", "random"],
                        backend="pallas")
    plain = run_sweep(cells, workers=1)
    with obs.record():
        rows = run_sweep(cells, workers=1)
    rec = obs.take()
    assert _strip_timing(rows) == _strip_timing(plain)
    assert [r["backend"] for r in rows] == ["pallas"] * len(rows)

    batches = rec.named("lane.batch")
    # family-homogeneous batches, each mixing both policies: (none, tree)
    # x (lru + random)
    assert len(batches) == 2 == rec.counters["lane.batches"]
    assert {(b.attrs["family"], b.attrs["policy"]) for b in batches} == {
        ("demand", "lru+random"), ("tree", "lru+random")}
    assert rec.counters["lane.mixed_batches"] == 2
    assert sorted(b.attrs["batch"] for b in batches) == [0, 1]
    assert sum(b.attrs["lanes"] for b in batches) == len(cells) \
        == rec.counters["lane.lanes"]
    assert sum(b.attrs["accesses"] for b in batches) == sum(
        r["n_accesses"] for r in rows) == rec.counters["lane.accesses"]
    for b in batches:
        kids = [s for s in rec.spans if s.parent == b.id]
        names = [s.name for s in kids]
        for stage in LANE_STAGES:
            assert names.count(stage) == 1, (stage, names)
        assert "sweep.finish_rows" in names
        assert all(s.thread == b.thread for s in kids)
        dispatch, = [s for s in kids if s.name == "lane.dispatch"]
        assert dispatch.attrs == {"family": b.attrs["family"],
                                  "policy": b.attrs["policy"]}
    run, = rec.named("sweep.run")
    assert run.attrs == {"cells": len(cells)} and run.parent is None
    assert len(rec.named("sweep.prepare")) == len(cells)
    assert rec.named("sweep.pack")
    # the trace was built before (memo); every cell found it there
    assert rec.counters["trace.memo_hits"] == len(cells)
    assert "trace.build" not in {s.name for s in rec.spans}
    # the first span and the end of the sweep sampled the host's memory
    assert len(rec.samples[obs.RSS_SAMPLE]) == 2
    for stage in LANE_STAGES + ("sweep.prepare", "sweep.pack",
                                "sweep.finish_rows"):
        assert 0 <= rec.self_s(stage) <= rec.total_s(stage)


def test_trace_build_is_a_span_and_a_memo_miss():
    from repro.uvm.sweep import load_trace

    with obs.record():
        load_trace("BICG", 0.125, 2 ** 31 + 77, 0.6)
        load_trace("BICG", 0.125, 2 ** 31 + 77, 0.6)
    rec = obs.take()
    build, = rec.named("trace.build")
    assert build.attrs == {"bench": "BICG", "seed": 2 ** 31 + 77}
    assert rec.counters["trace.memo_misses"] == 1
    assert rec.counters["trace.memo_hits"] == 1


def _mk_trace(pages):
    pages = np.asarray(pages, dtype=np.int64)
    recs = make_records(len(pages))
    recs["page"] = pages
    recs["sm"] = np.arange(len(pages)) % 4
    return Trace("synth", recs, {}, {}, len(pages) * 100)


def test_predictor_spans_and_cache_counters(tmp_path):
    """A tiny training: one fit and one predict span, one train step
    built, then a disk hit and a memo hit from the prediction cache."""
    tr = _mk_trace(np.arange(600) % 41)
    predcache.clear_memo()
    with obs.record():
        first = predcache.get_or_train(tr, steps=4, cache_dir=str(tmp_path))
        predcache.clear_memo()
        disk = predcache.get_or_train(tr, steps=4, cache_dir=str(tmp_path))
        memo = predcache.get_or_train(tr, steps=4, cache_dir=str(tmp_path))
    rec = obs.take()
    predcache.clear_memo()
    np.testing.assert_array_equal(first, disk)
    assert memo is disk
    fit, = rec.named("predictor.fit")
    assert fit.attrs == {"steps": 4}
    predict, = rec.named("predictor.predict")
    assert predict.start_ns >= fit.end_ns
    assert rec.counters["predictor.train_step_builds"] == 1
    assert rec.counters["predcache.misses"] == 1
    assert rec.counters["predcache.stores"] == 1
    assert rec.counters["predcache.hits"] == 2


@pytest.mark.parametrize("lanes", [4, 1])
def test_lane_steps_count_the_lockstep_loop(lanes):
    """``lane.steps`` counts the device loop's steps: a batch of
    equal-length lanes runs one step per trace position for all of them
    (``lane.accesses / lane.steps`` lanes a step), a 1-lane batch one
    step per access."""
    from repro.uvm import UVMConfig
    from repro.uvm.prefetchers import NoPrefetcher
    from repro.uvm.replay_core import ReplayRequest, get_backend

    pages = np.tile(np.arange(90), 3)
    requests = [ReplayRequest(_mk_trace(pages), NoPrefetcher(),
                              UVMConfig(device_pages=40 + 10 * i))
                for i in range(lanes)]
    with obs.record():
        get_backend("pallas").replay(requests)
    rec = obs.take()
    assert rec.counters["lane.batches"] == 1
    assert rec.counters["lane.steps"] == len(pages)
    assert rec.counters["lane.accesses"] == lanes * len(pages)
