"""Sweep orchestrator: grid expansion, worker determinism, resume,
round-trip, and train-once learned cells."""
import json
import os
import time

import numpy as np
import pytest

from repro.uvm import predcache
from repro.uvm.sweep import (ROW_FIELDS, SweepCell, expand_grid,
                             load_cell_row, load_trace, read_results,
                             read_results_csv, run_sweep, simulate_cell,
                             write_cell_row, write_results)

BENCHES = ["ATAX", "Pathfinder"]
PREFETCHERS = ["none", "tree"]


def _small_cells(**kw):
    return expand_grid(BENCHES, PREFETCHERS, scales=[0.25], **kw)


def _strip_timing(rows):
    # seconds and the lease-attempt counter are execution metadata — a
    # recomputed or resumed cell may legitimately differ in both
    return [{k: v for k, v in r.items() if k not in ("seconds", "retries")}
            for r in rows]


def test_grid_expansion_axes():
    cells = expand_grid(BENCHES, PREFETCHERS, scales=[0.25, 0.5],
                        device_fracs=[None, 0.5], prediction_us=[1.0, 10.0])
    assert len(cells) == 2 * 2 * 2 * 2 * 2
    # deterministic order and distinct cache keys
    assert [c.key() for c in cells] == [c.key() for c in cells]
    assert len({c.key() for c in cells}) == len(cells)
    # every axis value is represented
    assert {c.bench for c in cells} == set(BENCHES)
    assert {c.device_frac for c in cells} == {None, 0.5}


def test_trace_cache_roundtrip(tmp_path):
    cache = str(tmp_path / "cache")
    t1 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)
    assert any(f.startswith("trace_") for f in os.listdir(cache))
    t2 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)  # from disk
    assert t1.name == t2.name
    assert t1.n_instructions == t2.n_instructions
    np.testing.assert_array_equal(t1.accesses, t2.accesses)
    assert t1.array_pages == t2.array_pages


def test_simulate_cell_row_shape():
    row = simulate_cell(SweepCell("ATAX", "tree", scale=0.25))
    missing = [c for c in ROW_FIELDS if c not in row]
    assert not missing, missing
    assert row["hits"] + row["late"] + row["faults"] == row["n_accesses"]
    assert 0.0 <= row["hit_rate"] <= 1.0


def test_device_frac_resolves_capacity():
    row = simulate_cell(SweepCell("ATAX", "none", scale=0.25,
                                  device_frac=0.5))
    assert row["device_pages"] is not None and row["device_pages"] > 0
    assert row["pages_evicted"] > 0


def test_serial_and_parallel_match(tmp_path):
    cells = _small_cells()
    serial = run_sweep(cells, out_dir=str(tmp_path / "serial"), workers=1)
    parallel = run_sweep(cells, out_dir=str(tmp_path / "parallel"), workers=2)
    assert _strip_timing(serial) == _strip_timing(parallel)


def test_resume_from_partial_results(tmp_path):
    out = str(tmp_path / "out")
    cells = _small_cells()
    full = run_sweep(cells, out_dir=out, workers=1)

    # wipe half the cell files; poison the survivors so we can prove the
    # resumed sweep loaded them instead of recomputing
    cell_dir = os.path.join(out, "cells")
    kept = 0
    for i, cell in enumerate(cells):
        path = os.path.join(cell_dir, f"{cell.key()}.json")
        if i % 2 == 0:
            os.remove(path)
        else:
            row, reason = load_cell_row(path)
            assert reason == "ok"
            row["seconds"] = 12345.0
            write_cell_row(path, row)     # checksum must cover the poke
            kept += 1
    assert kept > 0

    resumed = run_sweep(cells, out_dir=out, workers=1)
    assert _strip_timing(resumed) == _strip_timing(full)
    marks = [r["seconds"] for r in resumed if r["seconds"] == 12345.0]
    assert len(marks) == kept          # loaded, not recomputed

    # resume=False recomputes everything
    fresh = run_sweep(cells, out_dir=out, workers=1, resume=False)
    assert not any(r["seconds"] == 12345.0 for r in fresh)


def test_results_json_csv_roundtrip(tmp_path):
    out = str(tmp_path / "out")
    cells = _small_cells(device_fracs=[None, 0.75])
    rows = run_sweep(cells, out_dir=out, workers=1)

    back = read_results(out)
    assert _strip_timing(back) == _strip_timing(rows)

    csv_rows = read_results_csv(os.path.join(out, "results.csv"))
    assert len(csv_rows) == len(rows)
    for got, want in zip(csv_rows, rows):
        assert got["bench"] == want["bench"]
        assert got["prefetcher"] == want["prefetcher"]
        assert got["n_accesses"] == want["n_accesses"]
        assert got["faults"] == want["faults"]
        assert got["device_frac"] == want["device_frac"]
        assert got["hit_rate"] == pytest.approx(want["hit_rate"], rel=1e-9)
        assert got["cycles"] == pytest.approx(want["cycles"], rel=1e-9)

    # write_results is idempotent over loaded rows
    write_results(back, out)
    assert _strip_timing(read_results(out)) == _strip_timing(rows)


def test_backend_choice_is_equivalent():
    base = dict(bench="ATAX", prefetcher="tree", scale=0.25)
    vec = simulate_cell(SweepCell(backend="numpy", **base))
    legacy = simulate_cell(SweepCell(backend="legacy", **base))
    for f in ("hits", "late", "faults", "pages_migrated", "prefetch_issued"):
        assert vec[f] == legacy[f]
    assert vec["cycles"] == pytest.approx(legacy["cycles"], rel=1e-6)
    # the backend that actually ran is recorded, never silent
    assert vec["backend"] == "numpy"
    assert legacy["backend"] == "legacy"


# ---------------------------------------------------------------------------
# backend scheduling: pallas lane batches + visible fallbacks
# ---------------------------------------------------------------------------

INT_ROW_FIELDS = ("n_accesses", "hits", "late", "faults", "prefetch_issued",
                  "prefetch_used", "pages_migrated", "pages_evicted")


def _backend_grid(backend):
    return expand_grid(BENCHES, ["none", "block"], scales=[0.25],
                       device_fracs=[None, 0.6], backend=backend)


def test_backend_axis_distinguishes_cells():
    keys = {c.key() for b in ("auto", "numpy", "pallas")
            for c in _backend_grid(b)}
    assert len(keys) == 3 * len(_backend_grid("auto"))


def test_sweep_pallas_grid_matches_numpy(tmp_path):
    """A >=8-cell grid replayed as ONE pallas lane batch produces rows
    identical (integer counters exact, floats to golden tolerance) to the
    NumPy backend, with the backend recorded per row."""
    from repro.uvm.replay_core import ReplayRequest, get_backend
    from repro.uvm.sweep import prepare_cell

    cells_p = _backend_grid("pallas")
    assert len(cells_p) >= 8
    # the whole grid packs into a single multi-lane kernel launch
    backend = get_backend("pallas")
    requests = []
    for cell in cells_p:
        trace, config, prefetcher, _ = prepare_cell(cell)
        requests.append(ReplayRequest(trace, prefetcher, config))
    assert all(backend.can_replay(r) for r in requests)
    assert len(backend.pack_lanes(requests)) == 1

    rows_p = run_sweep(cells_p, out_dir=str(tmp_path / "pallas"), workers=1)
    rows_n = run_sweep(_backend_grid("numpy"),
                       out_dir=str(tmp_path / "numpy"), workers=1)
    assert [r["backend"] for r in rows_p] == ["pallas"] * len(rows_p)
    assert [r["backend"] for r in rows_n] == ["numpy"] * len(rows_n)
    for got, want in zip(rows_p, rows_n):
        for f in INT_ROW_FIELDS:
            assert got[f] == want[f], f
        assert got["cycles"] == pytest.approx(want["cycles"], rel=1e-6)
        assert got["pcie_bytes"] == pytest.approx(want["pcie_bytes"],
                                                  rel=1e-6)
        assert got["hit_rate"] == pytest.approx(want["hit_rate"], rel=1e-6)


def test_sweep_mixed_family_grid_runs_on_lanes(tmp_path):
    """A grid interleaving every non-learned prefetcher family under
    --backend pallas replays every cell on the lanes (family-homogeneous
    batches), with rows matching the NumPy backend."""
    cells_p = expand_grid(BENCHES, ["none", "tree", "oracle", "block"],
                          scales=[0.25], device_fracs=[None, 0.6],
                          backend="pallas")
    rows_p = run_sweep(cells_p, out_dir=str(tmp_path / "pallas"), workers=1)
    assert [r["backend"] for r in rows_p] == ["pallas"] * len(rows_p)
    cells_n = expand_grid(BENCHES, ["none", "tree", "oracle", "block"],
                          scales=[0.25], device_fracs=[None, 0.6],
                          backend="numpy")
    rows_n = run_sweep(cells_n, out_dir=str(tmp_path / "numpy"), workers=1)
    for got, want in zip(rows_p, rows_n):
        for f in INT_ROW_FIELDS:
            assert got[f] == want[f], (got["bench"], got["prefetcher"], f)
        assert got["cycles"] == pytest.approx(want["cycles"], rel=1e-6)


def test_sweep_policy_grid_runs_on_lanes(tmp_path):
    """A grid crossing every eviction policy under --backend pallas
    replays every cell on the lanes (one batch of a family holds lanes of
    every policy), rows record the policy, and the numpy backend agrees
    per cell."""
    kw = dict(scales=[0.25], device_fracs=[0.5],
              evictions=["lru", "random", "hotcold"])
    cells_p = expand_grid(BENCHES, ["none", "tree"], backend="pallas", **kw)
    rows_p = run_sweep(cells_p, out_dir=str(tmp_path / "pallas"), workers=1)
    assert [r["backend"] for r in rows_p] == ["pallas"] * len(rows_p)
    assert [r["eviction"] for r in rows_p] == \
        [c.eviction for c in cells_p]
    assert {r["eviction"] for r in rows_p} == {"lru", "random", "hotcold"}
    rows_n = run_sweep(expand_grid(BENCHES, ["none", "tree"],
                                   backend="numpy", **kw),
                       out_dir=str(tmp_path / "numpy"), workers=1)
    for got, want in zip(rows_p, rows_n):
        for f in INT_ROW_FIELDS:
            assert got[f] == want[f], (got["bench"], got["prefetcher"],
                                       got["eviction"], f)
        assert got["cycles"] == pytest.approx(want["cycles"], rel=1e-6)


def test_sweep_pallas_fallback_is_recorded(tmp_path, monkeypatch):
    """Cells the lanes decline under --backend pallas fall back per cell
    to the NumPy path and the row says so instead of reading as
    covered."""
    from repro.uvm.backends.pallas_backend import PallasReplayBackend

    monkeypatch.setattr(PallasReplayBackend, "can_replay",
                        lambda self, request: False)
    cells = expand_grid(["ATAX"], ["tree"], scales=[0.25], backend="pallas")
    rows = run_sweep(cells, out_dir=str(tmp_path / "out"), workers=1)
    assert rows[0]["backend"] == "numpy"


def test_sweep_pallas_runtime_failure_degrades_per_cell(tmp_path,
                                                        monkeypatch):
    """A lane batch that dies at runtime (not structurally) raises out of
    the sweep: its cells are not replayed on the NumPy path in its place,
    so a device failure can never read as a host run, and no row of the
    failed batch is persisted."""
    import warnings

    from repro.uvm.backends.pallas_backend import PallasReplayBackend

    def _boom(self, requests):
        raise RuntimeError("synthetic kernel failure")

    monkeypatch.setattr(PallasReplayBackend, "replay", _boom)
    cells = _backend_grid("pallas")[:4]
    out = str(tmp_path / "out")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(RuntimeError, match="synthetic kernel failure"):
            run_sweep(cells, out_dir=out, workers=1)
    for cell in cells:
        assert load_cell_row(os.path.join(out, "cells",
                                          f"{cell.key()}.json"))[0] is None


def test_sweep_pallas_resume_skips_lane_batches(tmp_path, monkeypatch):
    """Resumed pallas grids read persisted cells — no kernel relaunch."""
    import repro.uvm.sweep as sweep_mod

    out = str(tmp_path / "out")
    cells = _backend_grid("pallas")[:4]
    first = run_sweep(cells, out_dir=out, workers=1)

    def _boom(*a, **k):
        raise AssertionError("resume must not replay any lane batch")

    monkeypatch.setattr(sweep_mod, "_run_lane_batches", _boom)
    monkeypatch.setattr(sweep_mod, "simulate_cell", _boom)
    resumed = run_sweep(cells, out_dir=out, workers=1)
    assert _strip_timing(resumed) == _strip_timing(first)


# ---------------------------------------------------------------------------
# train-once learned cells
# ---------------------------------------------------------------------------

LEARNED_STEPS = 20


def _learned_grid():
    """1 trace x 3 prediction_us x 2 device_frac — the Fig 10-style
    sensitivity grid whose learned variants must share one training run."""
    return expand_grid(["ATAX"], ["learned"], scales=[0.25],
                       prediction_us=[1.0, 2.0, 5.0],
                       device_fracs=[None, 0.5],
                       service_steps=LEARNED_STEPS)


def test_learned_grid_trains_once_and_beats_retrain(tmp_path, monkeypatch):
    """A (trace x prediction_us x device_frac) learned grid invokes
    PredictorService.fit exactly once, and the cached grid is >=3x faster
    end to end than the retrain-per-cell baseline."""
    from repro.core.service import PredictorService

    fit_calls = []
    orig_fit = PredictorService.fit

    def counting_fit(self, *args, **kwargs):
        fit_calls.append(1)
        return orig_fit(self, *args, **kwargs)

    monkeypatch.setattr(PredictorService, "fit", counting_fit)
    cells = _learned_grid()
    assert len(cells) == 6

    # warm jit (train.step_fn recompiles per fit; the apply cache persists)
    predcache.clear_memo()
    monkeypatch.setenv("REPRO_PREDCACHE", "0")
    simulate_cell(cells[0])
    fit_calls.clear()

    # retrain-per-cell baseline: cache disabled, one training run per cell
    t0 = time.monotonic()
    base_rows = run_sweep(cells, out_dir=str(tmp_path / "base"), workers=1)
    t_base = time.monotonic() - t0
    assert len(fit_calls) == len(cells)

    # train-once grid: one fit, every variant reuses the cached array
    monkeypatch.setenv("REPRO_PREDCACHE", "1")
    predcache.clear_memo()
    fit_calls.clear()
    t0 = time.monotonic()
    rows = run_sweep(cells, out_dir=str(tmp_path / "cached"), workers=1)
    t_cached = time.monotonic() - t0
    assert len(fit_calls) == 1

    # identical replay knobs per cell -> identical rows (training is
    # deterministic, so sharing the array cannot change any result)
    assert _strip_timing(rows) == _strip_timing(base_rows)
    assert t_base >= 3.0 * t_cached, (
        f"train-once grid not >=3x faster: baseline {t_base:.2f}s "
        f"vs cached {t_cached:.2f}s")

    # the shared array landed in the on-disk cache next to the traces
    pred_dir = os.path.join(str(tmp_path / "cached"), "trace_cache",
                            predcache.DEFAULT_SUBDIR)
    assert [f for f in os.listdir(pred_dir) if f.startswith("preds_")]
    predcache.clear_memo()


def test_learned_resume_needs_no_training(tmp_path, monkeypatch):
    """Resuming a completed learned grid reads persisted cells — nothing is
    re-simulated, so in particular nothing retrains."""
    import repro.uvm.sweep as sweep_mod

    predcache.clear_memo()
    out = str(tmp_path / "out")
    cells = _learned_grid()[:2]
    first = run_sweep(cells, out_dir=out, workers=1)

    def _boom(*a, **k):
        raise AssertionError("resume must not re-simulate any cell")

    # guard the whole cell path: a memo/disk prediction hit could mask a
    # broken resume if we only watched PredictorService.fit
    monkeypatch.setattr(sweep_mod, "simulate_cell", _boom)
    predcache.clear_memo()
    resumed = run_sweep(cells, out_dir=out, workers=1)
    assert _strip_timing(resumed) == _strip_timing(first)
    predcache.clear_memo()


def test_learned_cells_train_in_the_parent_before_fan_out(tmp_path,
                                                         monkeypatch):
    """One process holds the chip: with ``workers > 1`` the learned cells
    (predictor training + prediction) run in the sweep's own process
    before the fan-out, so the workers — which refuse device work —
    replay only the rest, and the grid matches a serial run."""
    from repro.core.service import PredictorService

    fits = []
    orig_fit = PredictorService.fit

    def counting_fit(self, *args, **kwargs):
        fits.append(1)                  # counts calls in THIS process only
        return orig_fit(self, *args, **kwargs)

    monkeypatch.setattr(PredictorService, "fit", counting_fit)
    cells = (_learned_grid()[:2]
             + expand_grid(["ATAX"], ["none", "tree"], scales=[0.25]))
    predcache.clear_memo()
    rows = run_sweep(cells, out_dir=str(tmp_path / "par"), workers=2)
    assert len(fits) == 1
    assert not any(r["quarantined"] for r in rows)
    predcache.clear_memo()
    serial = run_sweep(cells, out_dir=str(tmp_path / "ser"), workers=1)
    assert _strip_timing(rows) == _strip_timing(serial)
    predcache.clear_memo()


def test_cli_exits_nonzero_on_quarantine(tmp_path, monkeypatch, capsys):
    """``python -m repro.uvm.sweep`` fails when any row is quarantined."""
    from repro.uvm import faults
    from repro.uvm.sweep import main

    cell = expand_grid(["ATAX"], ["none"], scales=[0.25], backend="numpy")[0]
    plan = faults.FaultPlan(seed=0, specs=(
        faults.FaultSpec("cell.start", "raise", prob=1.0, max_count=None,
                         match=cell.key()),))
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, plan.to_json())
    monkeypatch.setenv("REPRO_SWEEP_MAX_ATTEMPTS", "1")
    monkeypatch.setenv("REPRO_SWEEP_BACKOFF", "0")
    faults.reset()
    try:
        with pytest.warns(RuntimeWarning, match="quarantined"):
            with pytest.raises(SystemExit) as exc:
                main(["--benches", "ATAX", "--prefetchers", "none",
                      "--scales", "0.25", "--backend", "numpy",
                      "--out", str(tmp_path / "out")])
    finally:
        monkeypatch.delenv(faults.FAULT_PLAN_ENV)
        faults.reset()
    assert exc.value.code not in (0, None)
    assert "1 of 1 cells quarantined" in str(exc.value.code)


def test_cli_backend_legacy_runs_reference_loop(tmp_path):
    """``--backend legacy`` on the sweep CLI replays every cell on the
    reference per-access loop, and the rows say so."""
    from repro.uvm.sweep import main

    out = str(tmp_path / "out")
    main(["--benches", "ATAX", "--prefetchers", "none,tree",
          "--scales", "0.25", "--backend", "legacy", "--out", out])
    rows = read_results(out)
    assert [(r["prefetcher"], r["backend"]) for r in rows] == [
        ("none", "legacy"), ("tree", "legacy")]


# ---------------------------------------------------------------------------
# crash safety: checksummed cell store, leases, retries, quarantine
# ---------------------------------------------------------------------------

def _strip_volatile(rows):
    from repro.uvm.faults import VOLATILE_ROW_FIELDS
    return [{k: v for k, v in r.items() if k not in VOLATILE_ROW_FIELDS}
            for r in rows]


def test_cell_row_envelope_rejects_corruption_and_versions(tmp_path):
    path = str(tmp_path / "cell.json")
    row = {"bench": "ATAX", "hit_rate": 0.5}
    write_cell_row(path, row)
    assert load_cell_row(path) == (row, "ok")

    # payload edited without the checksum: corrupt, never served
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace("0.5", "0.9"))
    assert load_cell_row(path) == (None, "corrupt")

    # truncation (torn write surviving a crashed rename-less writer)
    write_cell_row(path, row)
    with open(path, "r+") as f:
        f.truncate(os.path.getsize(path) // 2)
    assert load_cell_row(path) == (None, "corrupt")

    # foreign SWEEP_VERSION envelopes and pre-envelope flat rows are
    # "version", not "ok" — a version bump invalidates old grids
    write_cell_row(path, row)
    with open(path) as f:
        doc = json.load(f)
    doc["_v"] = 99
    with open(path, "w") as f:
        json.dump(doc, f)
    assert load_cell_row(path) == (None, "version")
    with open(path, "w") as f:
        json.dump(row, f)                  # legacy flat row, no envelope
    assert load_cell_row(path) == (None, "version")

    assert load_cell_row(str(tmp_path / "nope.json")) == (None, "missing")


def test_resume_requeues_invalid_cell_files(tmp_path):
    """Satellite: a truncated/corrupt/cross-version cell file warns, is
    quarantined aside, and its cell recomputes — resume never raises and
    never trusts bad bytes."""
    out = str(tmp_path / "out")
    cells = _small_cells()
    full = run_sweep(cells, out_dir=out, workers=1)
    paths = [os.path.join(out, "cells", f"{c.key()}.json") for c in cells]

    with open(paths[0], "r+") as f:        # torn write
        f.truncate(os.path.getsize(paths[0]) // 2)
    with open(paths[1], "w") as f:         # garbage bytes
        f.write("not json{{{")
    with open(paths[2], "w") as f:         # pre-envelope flat row
        json.dump(full[2], f)

    with pytest.warns(RuntimeWarning, match="quarantining"):
        resumed = run_sweep(cells, out_dir=out, workers=1)
    assert _strip_volatile(resumed) == _strip_volatile(full)
    for p in paths[:3]:
        assert os.path.exists(p + ".corrupt")     # evidence kept aside
        assert load_cell_row(p) == (load_cell_row(p)[0], "ok")


def test_worker_sigkill_mid_cell_and_mid_write_converges(tmp_path,
                                                         monkeypatch):
    """Satellite: SIGKILL a lease worker mid-cell and another mid
    cell-file write; the pool restarts workers, reclaims the dead pids'
    leases, and the grid is byte-identical to a fault-free run."""
    from repro.uvm import faults

    cells = _small_cells(backend="numpy")
    base = run_sweep(cells, out_dir=str(tmp_path / "base"), workers=1)

    plan = faults.FaultPlan(
        seed=3, ledger_dir=str(tmp_path / "ledger"), specs=(
            faults.FaultSpec("cell.start", "kill", prob=1.0, max_count=1,
                             match=cells[1].key()),
            faults.FaultSpec("cell.result.write", "kill", prob=1.0,
                             max_count=1, match=cells[2].key()),
        ))
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, plan.to_json())
    faults.reset()
    try:
        rows = run_sweep(cells, out_dir=str(tmp_path / "chaos"), workers=2)
    finally:
        monkeypatch.delenv(faults.FAULT_PLAN_ENV)
        faults.reset()

    assert _strip_volatile(rows) == _strip_volatile(base)
    assert all(r["quarantined"] is False for r in rows)
    assert all(isinstance(r["retries"], int) for r in rows)
    # both sabotaged cells needed a second lease claim
    assert rows[1]["retries"] >= 1
    assert rows[2]["retries"] >= 1
    assert faults.rows_digest(rows) == faults.rows_digest(base)


def test_unrecoverable_cell_quarantines_instead_of_aborting(tmp_path,
                                                            monkeypatch):
    """A cell that fails every attempt lands in the quarantine manifest
    as a stub row after capped retries — the rest of the grid completes,
    and a resumed sweep reloads the verdict without recomputing."""
    from repro.uvm import faults

    cells = _small_cells(backend="numpy")
    victim = cells[0].key()
    plan = faults.FaultPlan(seed=0, specs=(
        faults.FaultSpec("cell.start", "raise", prob=1.0, max_count=None,
                         match=victim),))
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, plan.to_json())
    faults.reset()
    out = str(tmp_path / "out")
    try:
        with pytest.warns(RuntimeWarning, match="quarantined"):
            rows = run_sweep(cells, out_dir=out, workers=1, max_attempts=2)

        assert rows[0]["quarantined"] is True
        assert rows[0]["hit_rate"] is None and rows[0]["ipc"] is None
        assert rows[0]["retries"] == 1            # 2 attempts = 1 retry
        assert rows[0]["bench"] == cells[0].bench
        assert all(r["quarantined"] is False for r in rows[1:])
        assert all(r["hit_rate"] is not None for r in rows[1:])

        with open(os.path.join(out, "quarantine.json")) as f:
            manifest = json.load(f)
        assert len(manifest["cells"]) == 1
        assert manifest["cells"][0]["key"] == victim
        assert manifest["cells"][0]["errors"]     # the injected raises

        # resume: the verdict is loaded, not recomputed
        resumed = run_sweep(cells, out_dir=out, workers=1, max_attempts=2)
        assert _strip_volatile(resumed) == _strip_volatile(rows)

        # CSV round-trip keeps the new bool/int columns typed
        csv_rows = read_results_csv(os.path.join(out, "results.csv"))
        assert csv_rows[0]["quarantined"] is True
        assert csv_rows[1]["quarantined"] is False
        assert csv_rows[0]["hit_rate"] is None
    finally:
        monkeypatch.delenv(faults.FAULT_PLAN_ENV)
        faults.reset()

    # resume=False clears the verdict and the cell recovers (the plan is
    # gone): the quarantine is a judgment about past attempts, not fate
    fresh = run_sweep(cells, out_dir=out, workers=1, resume=False)
    assert all(r["quarantined"] is False for r in fresh)
    assert fresh[0]["hit_rate"] is not None


def test_aggregate_results_rebuild_from_cell_store(tmp_path):
    """A torn results.json falls back to the checksummed per-cell store."""
    out = str(tmp_path / "out")
    cells = _small_cells()
    rows = run_sweep(cells, out_dir=out, workers=1)
    agg = os.path.join(out, "results.json")
    with open(agg, "r+") as f:
        f.truncate(os.path.getsize(agg) // 3)
    with pytest.warns(RuntimeWarning, match="rebuilding"):
        back = read_results(out)
    key = lambda r: (r["bench"], r["prefetcher"], str(r["device_frac"]))
    assert sorted(map(key, back)) == sorted(map(key, rows))
    assert {json.dumps(r, sort_keys=True) for r in back} \
        == {json.dumps(r, sort_keys=True) for r in rows}


# ---------------------------------------------------------------------------
# trace memo: checksum once per (path, sha); cold-read quarantine unchanged
# ---------------------------------------------------------------------------

def _trace_cache_file(cache):
    names = [f for f in os.listdir(cache)
             if f.startswith("trace_") and not f.endswith(".corrupt")]
    assert len(names) == 1, names
    return os.path.join(cache, names[0])


def _clobber_middle(path):
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        f.write(b"\xde\xad\xbe\xef" * 16)


def test_trace_memo_checksums_once_per_path(tmp_path):
    """Within a process the npz cache is opened and hashed once per
    (path, sha): a file corrupted *after* the first verified read is never
    re-read, so memoized loads serve the verified trace with no
    quarantine.  A cold reader (fresh memo) still quarantines and
    regenerates — the PR 7 crash-safety path is unchanged."""
    from repro.uvm.sweep import _trace_memo
    cache = str(tmp_path / "cache")
    t1 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)
    path = _trace_cache_file(cache)
    _trace_memo.clear()
    t2 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)  # disk, verified
    np.testing.assert_array_equal(t1.accesses, t2.accesses)

    _clobber_middle(path)
    t3 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)
    assert t3 is t2                      # memo hit: no re-open, no re-hash
    assert not os.path.exists(path + ".corrupt")

    _trace_memo.clear()                  # simulate a fresh process
    with pytest.warns(RuntimeWarning, match="quarantining"):
        t4 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)
    assert os.path.exists(path + ".corrupt")
    np.testing.assert_array_equal(t4.accesses, t1.accesses)


def test_trace_memo_disabled_rereads_disk(tmp_path, monkeypatch):
    """REPRO_TRACE_MEMO=0 restores the read-per-call behavior: disk
    corruption is caught on the very next load."""
    from repro.uvm.sweep import _trace_memo
    monkeypatch.setenv("REPRO_TRACE_MEMO", "0")
    _trace_memo.clear()
    cache = str(tmp_path / "cache")
    t1 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)
    path = _trace_cache_file(cache)
    _clobber_middle(path)
    with pytest.warns(RuntimeWarning, match="quarantining"):
        t2 = load_trace("ATAX", 0.25, 0, 0.6, cache_dir=cache)
    np.testing.assert_array_equal(t2.accesses, t1.accesses)


# ---------------------------------------------------------------------------
# model-family axis + adaptive eviction resolution
# ---------------------------------------------------------------------------

def test_grid_model_family_axis():
    """model_families is a first-class grid axis: cells carry it, key on
    it, and rows record it."""
    cells = expand_grid(["ATAX"], ["learned"], scales=[0.25],
                        model_families=["simplified", "transformer"])
    assert len(cells) == 2
    assert [c.model_family for c in cells] == ["simplified", "transformer"]
    assert len({c.key() for c in cells}) == 2
    assert "model_family" in ROW_FIELDS


def test_row_records_model_family(monkeypatch):
    """The learned cell hands its family to predcache (so training keys
    on the model identity) and the row records which family replayed."""
    from repro.uvm import predcache as predcache_mod

    seen = []

    def fake_get_or_train(trace, *, steps, cache_dir=None,
                          service_kwargs=None, **kw):
        seen.append(dict(service_kwargs or {}, steps=steps))
        return np.full(len(trace.accesses), -1, dtype=np.int64)

    monkeypatch.setattr(predcache_mod, "get_or_train", fake_get_or_train)
    row = simulate_cell(SweepCell("ATAX", "learned", scale=0.25,
                                  model_family="transformer",
                                  service_steps=5))
    assert seen == [{"model_family": "transformer", "steps": 5}]
    assert row["model_family"] == "transformer"
    # non-learned cells default to (and record) the simplified family
    base = simulate_cell(SweepCell("ATAX", "none", scale=0.25))
    assert base["model_family"] == "simplified"


def test_adaptive_cell_resolves_to_concrete_policy(tmp_path, monkeypatch):
    """An adaptive cell resolves at prepare time — the row's eviction
    column records the concrete policy that replayed, never the
    ``adaptive`` literal, and a selector table pins the choice."""
    from repro.uvm import adaptive

    adaptive.clear_memo()
    row = simulate_cell(SweepCell("ATAX", "none", scale=0.25,
                                  device_frac=0.5, eviction="adaptive"))
    from repro.uvm.eviction import EVICTION_POLICIES
    assert row["eviction"] in EVICTION_POLICIES

    table = tmp_path / "table.json"
    table.write_text(json.dumps({"ATAX": "hotcold"}))
    monkeypatch.setenv("REPRO_ADAPTIVE_TABLE", str(table))
    pinned = simulate_cell(SweepCell("ATAX", "none", scale=0.25,
                                     device_frac=0.5, eviction="adaptive"))
    assert pinned["eviction"] == "hotcold"
    # no pressure -> every policy is a no-op -> canonical lru
    monkeypatch.delenv("REPRO_ADAPTIVE_TABLE")
    free = simulate_cell(SweepCell("Pathfinder", "none", scale=0.25,
                                   eviction="adaptive"))
    assert free["eviction"] == "lru"
    adaptive.clear_memo()


def test_selector_from_rows_picks_cheapest_per_bench():
    from repro.uvm.adaptive import selector_from_rows

    rows = [
        {"bench": "A", "eviction": "lru", "cycles": 300},
        {"bench": "A", "eviction": "random", "cycles": 100},
        {"bench": "A", "eviction": "hotcold", "cycles": 200},
        # bench B: two rows per policy -> mean decides
        {"bench": "B", "eviction": "lru", "cycles": 100},
        {"bench": "B", "eviction": "lru", "cycles": 300},
        {"bench": "B", "eviction": "hotcold", "cycles": 150},
        {"bench": "B", "eviction": "hotcold", "cycles": 150},
        # ties break in EVICTION_POLICIES order (lru first)
        {"bench": "C", "eviction": "random", "cycles": 50},
        {"bench": "C", "eviction": "lru", "cycles": 50},
        # quarantined rows (no cycles) and adaptive literals are ignored
        {"bench": "D", "eviction": "lru", "cycles": None},
        {"bench": "D", "eviction": "adaptive", "cycles": 10},
    ]
    assert selector_from_rows(rows) == {"A": "random", "B": "hotcold",
                                        "C": "lru"}


def test_adaptive_table_parsed_once_per_mtime(tmp_path, monkeypatch):
    """The selector table is parsed once per (path, mtime): prepare-stage
    threads resolving thousands of cells must not re-read + re-parse the
    JSON per cell.  Editing the file (new mtime) invalidates the cache;
    an unreadable path fails loudly with the env var named."""
    import repro.uvm.adaptive as adaptive

    adaptive.clear_memo()
    table = tmp_path / "table.json"
    table.write_text(json.dumps({"ATAX": "hotcold"}))
    monkeypatch.setenv("REPRO_ADAPTIVE_TABLE", str(table))

    opens = []
    real_open = open

    def counting_open(path, *a, **kw):
        if str(path) == str(table):
            opens.append(path)
        return real_open(path, *a, **kw)

    # adaptive._table reads via the open builtin resolved in its module
    monkeypatch.setattr(adaptive, "open", counting_open, raising=False)
    for _ in range(5):
        assert adaptive.resolve_eviction("adaptive", "ATAX") == "hotcold"
    assert len(opens) == 1                 # parsed once, served 5x

    # content change (bump mtime explicitly: coarse filesystem
    # timestamps could otherwise collide) -> one re-parse
    table.write_text(json.dumps({"ATAX": "random"}))
    st = os.stat(table)
    os.utime(table, ns=(st.st_atime_ns, st.st_mtime_ns + 1))
    assert adaptive.resolve_eviction("adaptive", "ATAX") == "random"
    assert adaptive.resolve_eviction("adaptive", "ATAX") == "random"
    assert len(opens) == 2

    monkeypatch.setenv("REPRO_ADAPTIVE_TABLE", str(tmp_path / "gone.json"))
    with pytest.raises(FileNotFoundError, match="REPRO_ADAPTIVE_TABLE"):
        adaptive.resolve_eviction("adaptive", "ATAX")
    adaptive.clear_memo()


def test_adaptive_probe_keyed_by_prefetcher_family(monkeypatch):
    """The probe replays under the cell's prefetcher-family proxy and the
    memo keys on it: a tree cell must not be resolved from demand-paging
    behavior, while oracle and learned cells share one oracle probe."""
    from repro.uvm import adaptive
    from repro.uvm.eviction import EVICTION_POLICIES

    assert adaptive.probe_proxy(None) == "none"
    assert adaptive.probe_proxy("none") == "none"
    assert adaptive.probe_proxy("block") == "block"
    assert adaptive.probe_proxy("tree") == "tree"
    assert adaptive.probe_proxy("oracle") == "oracle"
    assert adaptive.probe_proxy("learned") == "oracle"

    trace = load_trace("ATAX", 0.25, 0, 0.6)
    cap = trace.working_set_pages // 2
    probes = []
    orig_probe = adaptive._probe

    def counting_probe(tr, device_pages, probe_accesses, proxy="none"):
        probes.append(proxy)
        return orig_probe(tr, device_pages, probe_accesses, proxy)

    monkeypatch.setattr(adaptive, "_probe", counting_probe)
    monkeypatch.delenv("REPRO_ADAPTIVE_TABLE", raising=False)
    adaptive.clear_memo()
    kw = dict(trace=trace, device_pages=cap, probe_accesses=2000)
    for pf in ("none", "tree", "oracle", "learned", "tree", "none"):
        got = adaptive.resolve_eviction("adaptive", "ATAX", prefetcher=pf,
                                        **kw)
        assert got in EVICTION_POLICIES
    # one probe per distinct proxy family; learned reused oracle's and
    # the repeats hit the memo
    assert probes == ["none", "tree", "oracle"]
    adaptive.clear_memo()


# ---------------------------------------------------------------------------
# serve rows: SLO columns come from in-band step clocks (slo_source)
# ---------------------------------------------------------------------------

def test_serve_rows_slo_source_kernel(tmp_path):
    """Serve rows derive their SLO columns from the step clocks the
    primary replay already produced (``slo_source="kernel"`` — in-kernel
    on the pallas lanes, host-side on numpy); the PR 6 double-replay
    side pass only fires when a row arrives without clocks.  Both
    backends must emit bit-identical latency columns."""
    cells = [SweepCell(bench="ServeDecode", prefetcher="none", scale=0.1,
                       window=None, device_frac=0.5, backend=be)
             for be in ("numpy", "pallas")]
    rows = run_sweep(cells, out_dir=str(tmp_path / "out"), workers=1)
    assert [r["backend"] for r in rows] == ["numpy", "pallas"]
    lat = ("decode_lat_p50_us", "decode_lat_p95_us", "decode_lat_p99_us",
           "ttft_p50_us", "ttft_p95_us", "ttft_p99_us")
    for r in rows:
        assert r["slo_source"] == "kernel"
        for f in lat:
            assert isinstance(r[f], float) and r[f] > 0.0, f
        assert (r["decode_lat_p50_us"] <= r["decode_lat_p95_us"]
                <= r["decode_lat_p99_us"])
    for f in lat:                       # lanes == host math, bitwise
        assert rows[0][f] == rows[1][f], f
