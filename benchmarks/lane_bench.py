"""Lane-executor throughput bench: the perf trajectory behind
``BENCH_lanes.json``.

Two measurements, both on the serve trace family:

* **Per-family lane throughput** — one 8-lane pallas batch per prefetcher
  family (demand/tree/learned/oracle) on ``ServeDecode``: cold replay
  (kernel build or executable-cache deserialize + run), warm replay
  (packed arrays + kernel run), and the numpy reference replay of the
  same lanes.  Every lane is cross-checked against the numpy backend on
  all replay counters — **any drift aborts the bench** (exit 1), the same
  contract as ``sim_throughput``.
* **End-to-end serve-smoke sweep** — ``run_sweep`` over the
  ``serve-smoke`` scenario on ``--backend pallas`` with a throwaway
  results dir, in this process (one process holds the chip), measured
  after one warmup sweep so every lane program is compiled: the
  steady-state wall time of a sweep.

CLI::

    PYTHONPATH=src python -m benchmarks.lane_bench
    PYTHONPATH=src python -m benchmarks.lane_bench \
        --emit-json BENCH_lanes.json      # trajectory point
    ... --skip-e2e                        # micro rows only (fast)

``scripts/check_bench.py`` diffs a fresh emission against the committed
baseline: row names and per-row key sets must match exactly, ``counter_*``
fields must be bit-identical, and timing fields are gated by
``REPRO_BENCH_TOL`` (fractional slack; 0 disables the timing gate).
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

#: replay counters cross-checked lane-by-lane against the numpy backend
COUNTER_FIELDS = ("cycles", "hits", "late", "faults", "prefetch_issued",
                  "prefetch_used", "pages_migrated", "pages_evicted",
                  "pcie_bytes")
#: one representative prefetcher per lane-kernel family
FAMILIES = (("demand", "none"), ("tree", "tree"),
            ("learned", "learned"), ("oracle", "oracle"))
N_LANES = 8
SCALE = 0.25
RATIO = 0.5


def _mk_prefetcher(name: str, trace):
    from repro.uvm.prefetchers import (BlockPrefetcher, LearnedPrefetcher,
                                       NoPrefetcher, OraclePrefetcher,
                                       TreePrefetcher)
    if name == "none":
        return NoPrefetcher()
    if name == "block":
        return BlockPrefetcher()
    if name == "tree":
        return TreePrefetcher()
    if name == "learned":
        # deterministic 30%-masked oracle predictions: exercises the
        # learned lane kernel without training a predictor
        rng = np.random.default_rng(0)
        preds = np.asarray(trace.pages, dtype=np.int64).copy()
        preds[rng.random(preds.size) < 0.3] = -1
        return LearnedPrefetcher(predicted_pages=preds)
    if name == "oracle":
        return OraclePrefetcher(np.asarray(trace.pages), lookahead=8)
    raise ValueError(name)


def _mk_requests(trace, pf_name: str, config, bounds):
    from repro.uvm.replay_core import ReplayRequest
    return [ReplayRequest(trace, _mk_prefetcher(pf_name, trace), config,
                          step_bounds=bounds) for _ in range(N_LANES)]


def family_rows() -> List[Dict]:
    """Per-family 8-lane batch timings + fatal numpy counter cross-check."""
    from repro.offload.serve_trace import build_serve_trace, trace_step_bounds
    from repro.uvm.config import UVMConfig
    from repro.uvm.replay_core import dispatch, get_backend

    trace = build_serve_trace("ServeDecode", scale=SCALE, seed=0)
    bounds = trace_step_bounds(trace)
    config = UVMConfig(device_pages=int(trace.working_set_pages * RATIO))
    backend = get_backend("pallas")
    rows = []
    for family, pf_name in FAMILIES:
        t0 = time.perf_counter()
        cold = backend.replay(_mk_requests(trace, pf_name, config, bounds))
        t1 = time.perf_counter()
        warm = backend.replay(_mk_requests(trace, pf_name, config, bounds))
        t2 = time.perf_counter()
        refs = [dispatch(r, backend="numpy")
                for r in _mk_requests(trace, pf_name, config, bounds)]
        t3 = time.perf_counter()

        row = {"name": f"family:{family}", "prefetcher": pf_name,
               "lanes": N_LANES, "accesses": len(trace) * N_LANES,
               "cold_s": t1 - t0, "warm_s": t2 - t1, "numpy_s": t3 - t2}
        for lane, (got, want) in enumerate(zip(warm, refs)):
            if got.backend != "pallas":
                raise SystemExit(f"lane_bench: {family} lane {lane} fell "
                                 f"off the pallas lanes ({got.backend})")
            for f in COUNTER_FIELDS:
                if getattr(got, f) != getattr(want, f):
                    raise SystemExit(
                        f"lane_bench: counter drift on {family} lane "
                        f"{lane}: {f} pallas={getattr(got, f)} "
                        f"numpy={getattr(want, f)}")
            if not np.array_equal(got.step_clocks, want.step_clocks):
                raise SystemExit(f"lane_bench: step-clock drift on "
                                 f"{family} lane {lane}")
        for f in ("cycles", "hits", "faults", "pcie_bytes"):
            row[f"counter_{f}"] = float(sum(getattr(s, f) for s in warm))
        rows.append(row)
        print(f"  {row['name']:16s} cold {row['cold_s']:.3f}s  "
              f"warm {row['warm_s']:.3f}s  numpy {row['numpy_s']:.3f}s")
    return rows


def _sweep_once(out_dir: str) -> tuple:
    """One serve-smoke sweep in this process; returns (seconds, rows)."""
    from repro.uvm.scenarios import expand_scenario
    from repro.uvm.sweep import run_sweep

    cells = expand_scenario("serve-smoke", backend="pallas")
    t0 = time.perf_counter()
    rows = run_sweep(cells, out_dir=out_dir)
    return time.perf_counter() - t0, rows


def e2e_row() -> Dict:
    """Serve-smoke wall time with every lane program compiled.

    The warmup sweep hides one-time costs this bench does not track
    (compilation, trace generation into a fresh cache), so the timed
    sweep measures the steady state."""
    with tempfile.TemporaryDirectory(prefix="lane_bench_warm_") as d:
        warmup_s, _ = _sweep_once(d)
    with tempfile.TemporaryDirectory(prefix="lane_bench_e2e_") as d:
        seconds, rows = _sweep_once(d)
    if len(rows) != 24:
        raise SystemExit(f"lane_bench: serve-smoke produced {len(rows)} "
                         "rows, not 24")
    off_lane = [r for r in rows if r["backend"] != "pallas"]
    if off_lane:
        raise SystemExit(f"lane_bench: {len(off_lane)} serve cells fell "
                         "off the pallas lanes")
    bad_src = [r for r in rows if r["slo_source"] != "kernel"]
    if bad_src:
        raise SystemExit(f"lane_bench: {len(bad_src)} lane rows took the "
                         "side-pass SLO path instead of in-kernel clocks")
    print(f"  e2e:serve-smoke  warmup {warmup_s:.3f}s  timed {seconds:.3f}s")
    return {"name": "e2e:serve-smoke", "rows": len(rows),
            "warmup_s": warmup_s, "seconds": seconds}


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="pallas lane throughput: per-family batches + "
                    "end-to-end serve-smoke sweep")
    ap.add_argument("--emit-json", default=None, metavar="PATH",
                    help="write the trajectory point (BENCH_lanes.json)")
    ap.add_argument("--skip-e2e", action="store_true",
                    help="micro rows only; skip the end-to-end sweeps")
    args = ap.parse_args(argv)

    from repro.uvm.sweep import SWEEP_VERSION

    print("== lane_bench: per-family 8-lane batches (ServeDecode@0.25) ==")
    rows = family_rows()
    if not args.skip_e2e:
        print("== lane_bench: end-to-end serve-smoke sweep ==")
        rows.append(e2e_row())
    if args.emit_json:
        doc = {"version": 1, "sweep_version": SWEEP_VERSION,
               "scale": SCALE, "ratio": RATIO, "rows": rows}
        with open(args.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=float)
            f.write("\n")
        print(f"wrote {args.emit_json}")


if __name__ == "__main__":
    main()
