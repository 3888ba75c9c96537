"""Fault-injection plane: deterministic draws, bounded ledgers, artifact
corruption, transient backend-fault semantics, and the chaos convergence
harness (SIGKILLed drivers resume to byte-identical grids)."""
import json
import os
import time

import pytest

from repro.uvm import faults
from repro.uvm.faults import (FaultPlan, FaultSpec, InjectedFault,
                              attempt_budget, rows_digest)


def _plan(tmp_path, *specs, seed=0):
    return FaultPlan(seed=seed, ledger_dir=str(tmp_path / "ledger"),
                     specs=tuple(specs)).validate()


# ---------------------------------------------------------------------------
# plan validation + env plumbing
# ---------------------------------------------------------------------------

def test_spec_and_plan_validation(tmp_path):
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("nope.site", "kill").validate()
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("cell.start", "explode").validate()
    with pytest.raises(ValueError, match="prob"):
        FaultSpec("cell.start", "kill", prob=1.5).validate()
    with pytest.raises(ValueError, match="max_count"):
        FaultSpec("cell.start", "kill", max_count=0).validate()
    with pytest.raises(ValueError, match="fraction"):
        FaultSpec("cell.result.artifact", "truncate",
                  fraction=1.0).validate()
    # bounded specs demand the shared ledger
    with pytest.raises(ValueError, match="ledger_dir"):
        FaultPlan(seed=0, specs=(
            FaultSpec("cell.start", "kill", max_count=1),)).validate()
    # round-trip through JSON (the REPRO_FAULT_PLAN wire format)
    plan = _plan(tmp_path, FaultSpec("cell.start", "raise", prob=0.5))
    assert faults.plan_from_dict(json.loads(plan.to_json())) == plan


def test_active_injector_follows_env(tmp_path, monkeypatch):
    faults.reset()
    monkeypatch.delenv(faults.FAULT_PLAN_ENV, raising=False)
    assert faults.active() is None
    plan = _plan(tmp_path, FaultSpec("cell.start", "delay", delay_s=0.0))
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, plan.to_json())
    inj = faults.active()
    assert inj is not None and inj.plan == plan
    assert faults.active() is inj        # cached while the env is stable
    monkeypatch.delenv(faults.FAULT_PLAN_ENV)
    assert faults.active() is None
    faults.reset()


# ---------------------------------------------------------------------------
# determinism + the shared ledger
# ---------------------------------------------------------------------------

def test_draws_are_deterministic_and_seed_sensitive(tmp_path):
    spec = FaultSpec("cell.start", "raise", prob=0.5, max_count=None)
    fired = {}
    for seed in (0, 1):
        inj = faults.FaultInjector(FaultPlan(seed=seed, specs=(spec,)))
        hits = set()
        for key in (f"cell{i}" for i in range(64)):
            try:
                inj.fire("cell.start", key)
            except InjectedFault:
                hits.add(key)
        fired[seed] = hits
        # same plan, fresh injector: identical decisions
        inj2 = faults.FaultInjector(FaultPlan(seed=seed, specs=(spec,)))
        rehits = set()
        for key in (f"cell{i}" for i in range(64)):
            try:
                inj2.fire("cell.start", key)
            except InjectedFault:
                rehits.add(key)
        assert rehits == hits
    assert 8 < len(fired[0]) < 56        # prob=0.5 really is probabilistic
    assert fired[0] != fired[1]          # and the seed moves it


def test_ledger_bounds_firing_across_injectors(tmp_path):
    plan = _plan(tmp_path,
                 FaultSpec("cell.start", "raise", prob=1.0, max_count=2))
    n = 0
    for _ in range(5):
        # a fresh injector per attempt = a restarted worker/driver
        inj = faults.FaultInjector(plan)
        try:
            inj.fire("cell.start", "victim")
        except InjectedFault:
            n += 1
    assert n == 2                        # the on-disk ledger is shared
    # a different key has its own budget
    with pytest.raises(InjectedFault):
        faults.FaultInjector(plan).fire("cell.start", "other")


def test_match_narrows_and_delay_sleeps(tmp_path):
    plan = _plan(tmp_path,
                 FaultSpec("cell.start", "raise", prob=1.0, max_count=None,
                           match="abc"),
                 FaultSpec("worker.loop", "delay", prob=1.0,
                           max_count=None, delay_s=0.05))
    inj = faults.FaultInjector(plan)
    inj.fire("cell.start", "zzz")        # no match: no fault
    with pytest.raises(InjectedFault):
        inj.fire("cell.start", "xxabcxx")
    t0 = time.monotonic()
    inj.fire("worker.loop", "w0")
    assert time.monotonic() - t0 >= 0.05


def test_corrupt_truncates_and_flips_bits(tmp_path):
    data = bytes(range(256)) * 8
    plan = _plan(tmp_path,
                 FaultSpec("cell.result.artifact", "truncate", prob=1.0,
                           max_count=1, fraction=0.25),
                 FaultSpec("trace.artifact", "bitflip", prob=1.0,
                           max_count=1))
    inj = faults.FaultInjector(plan)

    p1 = str(tmp_path / "a.bin")
    with open(p1, "wb") as f:
        f.write(data)
    inj.corrupt("cell.result.artifact", p1, "k1")
    assert os.path.getsize(p1) == len(data) // 4
    inj.corrupt("cell.result.artifact", p1, "k1")   # budget spent
    assert os.path.getsize(p1) == len(data) // 4

    p2 = str(tmp_path / "b.bin")
    with open(p2, "wb") as f:
        f.write(data)
    inj.corrupt("trace.artifact", p2, "k2")
    with open(p2, "rb") as f:
        got = f.read()
    assert len(got) == len(data)
    diff = [i for i in range(len(data)) if got[i] != data[i]]
    assert len(diff) == 1                # exactly one flipped bit
    assert bin(got[diff[0]] ^ data[diff[0]]).count("1") == 1


# ---------------------------------------------------------------------------
# transient backend faults: retried, never degraded, never swallowed
# ---------------------------------------------------------------------------

def _small_request():
    from repro.uvm.replay_core import ReplayRequest
    from repro.uvm.sweep import SweepCell, prepare_cell

    trace, config, prefetcher, _ = prepare_cell(
        SweepCell("ATAX", "none", scale=0.25, backend="pallas"))
    return ReplayRequest(trace, prefetcher, config)


def test_injected_backend_fault_is_transient(tmp_path, monkeypatch):
    from repro.uvm.replay_core import TransientBackendFault

    plan = _plan(tmp_path, FaultSpec("backend.replay", "raise", prob=1.0,
                                     max_count=1))
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, plan.to_json())
    faults.reset()
    try:
        with pytest.raises(TransientBackendFault) as exc:
            faults.fire("backend.replay", "8:ATAX")
        assert isinstance(exc.value, InjectedFault)
        faults.fire("backend.replay", "8:ATAX")      # ledger spent: clean
    finally:
        monkeypatch.delenv(faults.FAULT_PLAN_ENV)
        faults.reset()


def test_dispatch_reraises_transient_instead_of_degrading(monkeypatch):
    """A transient pallas fault must NOT degrade to numpy (that would
    permanently change the row's backend column), and neither does a
    hard runtime fault: both reach the caller, on every backend — a
    device failure is never hidden behind a host replay, and the golden
    equivalence can never pass vacuously."""
    from repro.uvm.backends.numpy_backend import NumpyReplayBackend
    from repro.uvm.backends.pallas_backend import PallasReplayBackend
    from repro.uvm.replay_core import TransientBackendFault, dispatch

    req = _small_request()

    def _transient(self, requests):
        raise TransientBackendFault("device preempted")

    monkeypatch.setattr(PallasReplayBackend, "replay", _transient)
    with pytest.raises(TransientBackendFault):
        dispatch(req, "pallas")

    def _hard(self, requests):
        raise RuntimeError("lowering exploded")

    monkeypatch.setattr(PallasReplayBackend, "replay", _hard)
    with pytest.raises(RuntimeError, match="lowering exploded"):
        dispatch(req, "pallas")

    monkeypatch.setattr(NumpyReplayBackend, "replay", _hard)
    with pytest.raises(RuntimeError, match="lowering exploded"):
        dispatch(req, "numpy")


# ---------------------------------------------------------------------------
# convergence digests + attempt budgets
# ---------------------------------------------------------------------------

def test_rows_digest_ignores_only_volatile_columns():
    base = [{"bench": "ATAX", "hit_rate": 0.5, "seconds": 1.0,
             "retries": 0, "backend": "pallas", "quarantined": False}]
    same = [dict(base[0], seconds=9.0, retries=3)]
    assert rows_digest(base) == rows_digest(same)
    for col, val in (("hit_rate", 0.6), ("backend", "numpy"),
                     ("quarantined", True)):
        assert rows_digest([dict(base[0], **{col: val})]) \
            != rows_digest(base)


def test_attempt_budget_covers_worst_case_sabotage(tmp_path):
    plan = _plan(tmp_path,
                 FaultSpec("cell.start", "kill", max_count=2),
                 FaultSpec("cell.result.write", "kill", max_count=1),
                 FaultSpec("cell.result.artifact", "bitflip", max_count=3),
                 FaultSpec("backend.replay", "raise", max_count=1),
                 FaultSpec("worker.loop", "kill", max_count=5),
                 FaultSpec("cell.start", "delay", max_count=7))
    # 2+1+3+1 consuming, worker kills and delays don't burn attempts
    assert attempt_budget(plan, margin=2) == 9


# ---------------------------------------------------------------------------
# the chaos convergence harness (SIGKILLed drivers, corrupted artifacts)
# ---------------------------------------------------------------------------

def test_chaos_sweep_converges_byte_identical(tmp_path):
    """End to end: a serial sweep driver is SIGKILLed mid-cell and mid
    cell-file write, its cached trace is truncated, a backend fault is
    injected — and the restarted/resumed grid is byte-identical to the
    fault-free baseline with an empty quarantine manifest."""
    out = str(tmp_path / "chaos")
    plan = FaultPlan(seed=1, ledger_dir=os.path.join(out, "ledger"), specs=(
        FaultSpec("cell.start", "kill", prob=0.6, max_count=1),
        FaultSpec("cell.result.write", "kill", prob=0.6, max_count=1),
        FaultSpec("cell.result.artifact", "bitflip", prob=0.6,
                  max_count=1),
        FaultSpec("trace.artifact", "truncate", prob=1.0, max_count=1),
    ))
    report = faults.run_chaos_check(
        out, benches="ATAX,Pathfinder", prefetchers="none,tree",
        backend="numpy", workers=1, scale=0.25, plan=plan, verbose=False)
    assert report["cells"] == 4
    assert report["faults_fired"] >= 3   # the plan really injected
    assert report["restarts"] >= 1       # the driver really died
