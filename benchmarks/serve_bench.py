"""Serving-traffic SLO study: request-rate sweep over PagedKVStore-derived
replay traces (beyond paper).

The paper measures throughput-style UVM metrics on HPC kernels; serving
workloads care about *tail latency*.  This suite replays the serve trace
family (``repro.offload.serve_trace``: continuous-batching decode,
multi-tenant mixes, bursty open-loop arrivals) through the UVM replay
backends, sweeping request rate (``ServeBursty@r<rate>``) against
capacity ratio, eviction policy and prefetcher, and reports
p50/p95/p99 per-decode-step latency plus TTFT for every cell — the
latency columns the sweep derives from per-step replay clocks
(``ReplayRequest.step_bounds``).

CLI::

    PYTHONPATH=src python -m benchmarks.serve_bench
    PYTHONPATH=src python -m benchmarks.serve_bench \
        --emit-json serve_rows.json             # SLO rows as JSON
    PYTHONPATH=src python -m benchmarks.serve_bench \
        --scenario serve-smoke                  # registry-routed matrix

``--scenario`` routes a named ``repro.uvm.scenarios`` matrix through the
same sweep engine (shared trace caches, resume, ``--workers`` via
``benchmarks.run``) instead of the local rate grid.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List, Optional

from benchmarks import common
from benchmarks.common import QUICK, print_table, uvm_sweep
from repro.uvm.eviction import EVICTION_POLICIES
from repro.uvm.sweep import SWEEP_VERSION, SweepCell

#: rate-independent baselines + the open-loop rate sweep
BENCHES = (["ServeDecode", "ServeBursty@r64"] if QUICK else
           ["ServeDecode", "ServeTenantMix",
            "ServeBursty@r32", "ServeBursty@r64", "ServeBursty@r128"])
RATIOS = [0.5] if QUICK else [0.75, 0.5]
EVICTIONS = ("lru",) if QUICK else EVICTION_POLICIES
PREFETCHERS = ("none", "block") if QUICK else ("none", "block", "tree")
#: decode lengths scale; arrivals don't — rate pressure is preserved
SCALE = 0.25

COLS = ["bench", "rate_rps", "capacity_x", "eviction", "prefetcher",
        "backend", "hit_rate", "decode_lat_p50_us", "decode_lat_p95_us",
        "decode_lat_p99_us", "ttft_p50_us", "ttft_p95_us", "ttft_p99_us"]


def _rate(bench: str) -> Optional[float]:
    """The open-loop request rate of a bench name, None for closed-loop."""
    from repro.offload.serve_trace import get_serve_workload
    wl = get_serve_workload(bench)
    return wl.rate_rps if wl.arrival == "open" else None


def run() -> List[Dict]:
    cells, tags = [], []
    for bench in BENCHES:
        for ratio in RATIOS:
            for ev in EVICTIONS:
                for pf in PREFETCHERS:
                    # serve traces are never window-split: the decode-step
                    # bounds behind the latency columns must stay aligned
                    # common.SWEEP_BACKEND read at call time, not import
                    # time, so run.py --backend reaches scenario suites
                    cells.append(SweepCell(
                        bench=bench, prefetcher=pf, scale=SCALE,
                        window=None, device_frac=ratio, eviction=ev,
                        backend=common.SWEEP_BACKEND))
                    tags.append((bench, ratio, ev, pf))
    rows = []
    for (bench, ratio, ev, pf), r in zip(tags, uvm_sweep(cells)):
        rows.append({
            "bench": bench, "rate_rps": _rate(bench), "capacity_x": ratio,
            "eviction": ev, "prefetcher": pf, "backend": r.get("backend"),
            "hit_rate": r["hit_rate"],
            "decode_lat_p50_us": r["decode_lat_p50_us"],
            "decode_lat_p95_us": r["decode_lat_p95_us"],
            "decode_lat_p99_us": r["decode_lat_p99_us"],
            "ttft_p50_us": r["ttft_p50_us"],
            "ttft_p95_us": r["ttft_p95_us"],
            "ttft_p99_us": r["ttft_p99_us"],
        })
    return rows


def run_scenario(name: str) -> List[Dict]:
    """Replay a registry scenario through the shared benchmark sweep
    caches; returns the raw sweep rows (scenario/eviction/backend and the
    serve latency columns included)."""
    from repro.uvm.scenarios import expand_scenario

    cells = expand_scenario(name, backend=common.SWEEP_BACKEND)
    return uvm_sweep(cells)


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Serving-traffic SLO sweep: request rate x capacity "
                    "x eviction x prefetcher")
    ap.add_argument("--emit-json", default=None, metavar="PATH",
                    help="write result rows (latency percentile columns "
                         "included) as JSON for BENCH_* tracking")
    ap.add_argument("--scenario", default=None,
                    help="route a named repro.uvm.scenarios matrix "
                         "through the sweep instead of the local grid")
    args = ap.parse_args(argv)

    if args.scenario:
        rows = run_scenario(args.scenario)
        print_table(f"Scenario matrix: {args.scenario}", rows,
                    ["bench", "device_frac", "eviction", "prefetcher",
                     "backend", "hit_rate", "decode_lat_p99_us",
                     "ttft_p99_us"])
    else:
        rows = run()
        print_table("Serving traffic: request rate x capacity x "
                    "eviction x prefetcher (beyond paper)", rows, COLS)
    if args.emit_json:
        doc = {"version": 1, "sweep_version": SWEEP_VERSION,
               "scenario": args.scenario, "scale": SCALE, "rows": rows}
        with open(args.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=float)
            f.write("\n")
        print(f"wrote {args.emit_json}")


if __name__ == "__main__":
    main()
