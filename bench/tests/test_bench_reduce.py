"""The trace reduction on a small synthetic trace: busy union, idle share,
module attribution and gap naming."""
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr


def _ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def _planes():
    host = NS(name="/host:CPU", lines=[
        # as on the chip: the spans on one interpreter line, JAX's host
        # events on another of the same name
        NS(name="python3", events=[
            _ev("bench.grid", 1000, 2000),
            _ev("bench.grid", 2000, 3000),
        ]),
        NS(name="python3", events=[
            _ev("PjitFunction(step_fn)", 1500, 1510),
            _ev("np.asarray(jax.Array)", 1800, 2150),
        ]),
        NS(name="pjrt-tpu-tasks/1", events=[_ev("H2D Dispatch", 1100, 1900)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="Steps", events=[_ev("7", 900, 1200)]),
        NS(name="XLA Modules", events=[
            _ev("jit_wrapped(123)", 900, 1200),      # starts before window
            _ev("jit_step_fn(55)", 1500, 1600),
            _ev("jit_step_fn(55)", 1550, 1650),      # overlaps the last
            _ev("jit_wrapped(123)", 2200, 2800),
            _ev("jit_wrapped(123)", 3500, 3600),     # after the window
        ]),
        NS(name="XLA Ops", events=[_ev("%while.1 = ...", 2200, 2800)]),
    ])
    return [NS(name="/host:metadata", lines=[]), host, dev]


def test_busy_union_idle_share_and_modules():
    red = tr.reduce_planes(_planes())
    assert red.window == (1000, 3000)
    assert red.busy == [(1000, 1200), (1500, 1650), (2200, 2800)]
    assert red.busy_s == pytest.approx(950e-9)
    assert red.window_s == pytest.approx(2000e-9)
    # module seconds count whole executions that touch the window
    assert red.modules["jit_wrapped"] == pytest.approx(900e-9)
    assert red.module_count("jit_wrapped") == 2
    assert red.module_count("jit_step_fn") == 2
    assert red.module_seconds("jit_step_fn") == pytest.approx(200e-9)


def test_planes_are_read_once():
    """``ProfileData.planes`` is a one-shot iterator."""
    red = tr.reduce_planes(iter(_planes()))
    assert red.busy == tr.reduce_planes(_planes()).busy
    assert red.module_count("jit_wrapped") == 2


def test_gaps_are_named_by_the_host_event_and_next_module():
    red = tr.reduce_planes(_planes())
    named = red.named_gaps()
    assert [s for _, s in named] == pytest.approx(
        [300e-9, 550e-9, 200e-9])
    assert named[0][0] == "bench.grid -> jit_step_fn"
    assert named[1][0] == "np.asarray(jax.Array) -> jit_wrapped"
    assert named[2][0] == "bench.grid -> end of window"
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "jit_wrapped"
    assert len(bd["idle_gaps"]) == 3 and len(bd["device_ops"]) <= 10


def test_union_and_gaps_helpers():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.gaps([(2, 3), (5, 9)], 0, 10) == [(0, 2), (3, 5), (9, 10)]
    assert tr.module_name("jit__cls_conf(12578738036433940855)") \
        == "jit__cls_conf"


def test_a_trace_without_the_window_span_or_device_is_refused():
    planes = _planes()
    planes[1].lines[0].events = [_ev("other", 0, 10)]
    with pytest.raises(ValueError, match="bench.grid"):
        tr.reduce_planes(planes)
    planes = _planes()[:2]
    with pytest.raises(ValueError, match="XLA Modules"):
        tr.reduce_planes(planes)


def _ctx(modules, counts, *, rows=(), lane_accesses=0):
    from bench import harness

    red = tr.Reduced(window=(0, 2_000_000_000), busy=[(0, 500_000_000)],
                     n_devices=1, modules=modules, module_counts=counts,
                     module_events=[], host_events=[])
    cell = harness.load_cell("atax.replay")
    return tr.MetricContext(
        reduced=red, cell=cell, rows=list(rows),
        window=NS(compiles=3), lane_accesses=lane_accesses,
        device_kind="TPU v5 lite",
        program_traces={7: NS(working_set_pages=100)})


def test_metric_readers_on_a_reduced_trace():
    from bench import costs, harness

    rows = [{"backend": "pallas", "n_accesses": 1000, "prefetcher": "none",
             "eviction": "lru", "seed": 7},
            {"backend": "numpy", "n_accesses": 1000, "prefetcher": "none",
             "eviction": "lru", "seed": 7}]
    ctx = _ctx({"jit_wrapped": 0.25, "jit_other": 0.03},
               {"jit_wrapped": 1, "jit_other": 150},
               rows=rows, lane_accesses=1000)
    read = {m: harness.load_reader(m) for m in (
        "device_idle_pct", "lane_us_per_access", "lane_roofline_pct",
        "compiles_in_window")}
    assert read["device_idle_pct"](ctx) == pytest.approx(75.0)
    assert read["lane_us_per_access"](ctx) == pytest.approx(250.0)
    # only the row the lanes replayed counts
    nbytes = costs.lane_bytes(1000, 100, "none", "lru")
    assert read["lane_roofline_pct"](ctx) == pytest.approx(
        100 * nbytes / 8.19e11 / 0.25)
    assert read["compiles_in_window"](ctx) == 3.0


@pytest.mark.parametrize("metric", ["lane_us_per_access",
                                    "lane_roofline_pct"])
def test_metric_readers_return_nothing_when_nothing_ran(metric):
    from bench import harness

    assert harness.load_reader(metric)(_ctx({}, {})) is None
