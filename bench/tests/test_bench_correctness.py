"""The comparison that decides ``correct``, at a size a test run holds.

A sound run of the lanes (on XLA:CPU here) is correct; the control (the
reference replayed with a float32 timing state, put in the program's
place) and a run with the timed path broken underneath are not.  These
drive the harness's own run, minus its look for a chip.
"""
import dataclasses
import json
import os
import time

import pytest

from bench import compare, harness
from bench.reference import tracegen

SEED = 2 ** 31 + 7

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _small_cell(name: str) -> harness.Cell:
    """The cell at the size its traffic file's ``small`` block gives a
    test run: the scale, grid axes that replace the cell's own, and how
    many of the pass's traces.  Every batch has two lanes, so leaving
    half of one out is a fault the run can show."""
    cell = harness.load_cell(name)
    small = cell.workload["small"]
    config = dict(cell.config, scale=small["scale"])
    tr = tracegen.build_trace(config, cell.workload["trace_seeds"][0])
    config["pins"] = {"n_accesses": len(tr.accesses),
                      "n_instructions": tr.n_instructions}
    workload = dict(cell.workload,
                    grid=dict(cell.workload["grid"], **small["grid"]),
                    trace_seeds=cell.workload["trace_seeds"][
                        :small["traces"]])
    return dataclasses.replace(cell, config=config, workload=workload,
                               backend="pallas")


def _run(cell):
    return harness.run_cell(cell, SEED, 0.0, False,
                            t_start=time.perf_counter(),
                            require_chip=False, log=lambda _m: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = _small_cell(name)
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] == (len(cell.workload["trace_seeds"])
                                * len(harness.grid(cell, SEED)))
    assert list(res)[-1] == "checks"
    assert res["checks"]["int_mismatches"]["value"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = _small_cell(name)
    from repro.uvm.sweep import SweepCell
    sweep = [SweepCell(**c) for c in harness.grid(cell, SEED)]
    checks = compare.control_checks(cell.config, SEED, sweep)
    limits = cell.workload["limits"]
    assert any(checks[k] > limits[k] for k in limits), checks


def _state_unchanged(orig, self, requests):
    """Every lane returns its initial state."""
    return [dataclasses.replace(
        s, cycles=0.0, hits=0, late=0, faults=0, prefetch_issued=0,
        prefetch_used=0, pages_migrated=0, pages_evicted=0, pcie_bytes=0.0)
        for s in orig(self, requests)]


def _half_batch(orig, self, requests):
    """Half of the batch's lanes replayed; the rest copy the last one."""
    n = max(1, len(requests) // 2)
    kept = orig(self, list(requests)[:n])
    return kept + [kept[-1]] * (len(requests) - n)


def _answer_altered(orig, self, requests):
    """One lane's hit count is off by one where it is produced."""
    out = orig(self, requests)
    out[0] = dataclasses.replace(out[0], hits=out[0].hits + 1)
    return out


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, name):
    from repro.uvm.backends import pallas_backend

    orig = pallas_backend.PallasReplayBackend._replay_batch
    monkeypatch.setattr(pallas_backend.PallasReplayBackend, "_replay_batch",
                        lambda self, reqs: fault(orig, self, reqs))
    res = _run(_small_cell(name))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_a_row_left_out_of_the_grid_is_not_correct(monkeypatch, name):
    from repro.uvm import sweep

    orig = sweep.run_sweep
    monkeypatch.setattr(sweep, "run_sweep",
                        lambda cells, **kw: orig(cells, **kw)[:-1])
    cell = _small_cell(name)
    res = _run(cell)
    assert not res["correct"], res["checks"]
    assert res["checks"]["int_mismatches"]["value"] >= 1
    # attempted counts the rows the window asked for, not those it got
    assert res["attempted"] == (len(cell.workload["trace_seeds"])
                                * len(harness.grid(cell, SEED)))
