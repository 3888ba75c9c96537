"""Multi-tenant interference study: interleaved bench pairs contending
for one device (beyond paper).

The paper evaluates one benchmark at a time; shared-virtual-memory
studies (arXiv 2405.06811) show co-resident applications interfering
through the paging layer is what deployments actually see.  This suite
replays interleaved bench-pair traces (``repro.traces.interleave``)
through the UVM replay backends, sweeping capacity ratio x capacity
split (shared contention vs. hard per-tenant quotas with a spill pool) x
eviction policy x prefetcher, and reports per-tenant hit rates plus the
interference slowdown — each tenant's completion cycles in the mix over
its solo replay — for every cell.

CLI::

    PYTHONPATH=src python -m benchmarks.mt_bench
    PYTHONPATH=src python -m benchmarks.mt_bench \
        --emit-json BENCH_mt.json               # trajectory rows
    PYTHONPATH=src python -m benchmarks.mt_bench --scenario mt-smoke

Counter-class row fields (``counter_*``) are deterministic pure
functions of the cell, so ``scripts/check_bench.py`` gates them exactly:
any drift in per-tenant accounting or the interference columns fails CI.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

from benchmarks import common
from benchmarks.common import QUICK, print_table, uvm_sweep
from repro.uvm.eviction import EVICTION_POLICIES
from repro.uvm.sweep import SWEEP_VERSION, SweepCell

BENCHES = ["ATAX+Pathfinder"] if QUICK else ["ATAX+Pathfinder",
                                             "BICG+Hotspot"]
RATIOS = [0.5] if QUICK else [0.75, 0.5]
EVICTIONS = ("lru",) if QUICK else EVICTION_POLICIES
SPLITS = ("shared", "0.5/0.5") if QUICK else ("shared", "0.5/0.5",
                                              "0.4/0.4")
PREFETCHERS = ("none", "tree")
SCALE = 0.25

COLS = ["bench", "capacity_x", "capacity_split", "eviction", "prefetcher",
        "backend", "hit_rate", "counter_hit_rate_t0", "counter_hit_rate_t1",
        "counter_interference_slowdown"]


def run() -> List[Dict]:
    cells, tags = [], []
    for bench in BENCHES:
        for ratio in RATIOS:
            for ev in EVICTIONS:
                for split in SPLITS:
                    for pf in PREFETCHERS:
                        # common.SWEEP_BACKEND read at call time, not
                        # import time, so run.py --backend reaches here
                        cells.append(SweepCell(
                            bench=bench, prefetcher=pf, scale=SCALE,
                            device_frac=ratio, eviction=ev,
                            capacity_split=split,
                            backend=common.SWEEP_BACKEND))
                        tags.append((bench, ratio, ev, split, pf))
    rows = []
    for (bench, ratio, ev, split, pf), r in zip(tags, uvm_sweep(cells)):
        rows.append({
            "name": f"{bench}/{ratio}/{ev}/{split}/{pf}",
            "bench": bench, "capacity_x": ratio, "capacity_split": split,
            "eviction": ev, "prefetcher": pf, "backend": r.get("backend"),
            "tenants": r["tenants"],
            "hit_rate": r["hit_rate"],
            "counter_hits": r["hits"],
            "counter_faults": r["faults"],
            "counter_pages_evicted": r["pages_evicted"],
            "counter_hit_rate_t0": r["hit_rate_t0"],
            "counter_hit_rate_t1": r["hit_rate_t1"],
            "counter_slowdown_t0": r["slowdown_t0"],
            "counter_slowdown_t1": r["slowdown_t1"],
            "counter_interference_slowdown": r["interference_slowdown"],
        })
    return rows


def run_scenario(name: str) -> List[Dict]:
    """Replay a registry scenario (e.g. ``mt-smoke`` / ``mt-full``)
    through the shared benchmark sweep caches; returns the raw sweep
    rows (per-tenant and interference columns included)."""
    from repro.uvm.scenarios import expand_scenario

    cells = expand_scenario(name, backend=common.SWEEP_BACKEND)
    return uvm_sweep(cells)


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Multi-tenant interference sweep: interleaved bench "
                    "pairs x capacity split x eviction x prefetcher")
    ap.add_argument("--emit-json", default=None, metavar="PATH",
                    help="write result rows (per-tenant hit rates + "
                         "interference slowdown) as JSON for BENCH_* "
                         "tracking")
    ap.add_argument("--scenario", default=None,
                    help="route a named repro.uvm.scenarios matrix "
                         "through the sweep instead of the local grid")
    args = ap.parse_args(argv)

    t0 = time.time()
    if args.scenario:
        rows = run_scenario(args.scenario)
        print_table(f"Scenario matrix: {args.scenario}", rows,
                    ["bench", "device_frac", "capacity_split", "eviction",
                     "prefetcher", "backend", "hit_rate", "hit_rate_t0",
                     "hit_rate_t1", "interference_slowdown"])
    else:
        rows = run()
        print_table("Multi-tenant interference: pair x capacity split x "
                    "eviction x prefetcher (beyond paper)", rows, COLS)
    if args.emit_json:
        doc = {"version": 1, "sweep_version": SWEEP_VERSION,
               "scenario": args.scenario, "scale": SCALE, "quick": QUICK,
               "total_seconds": time.time() - t0, "rows": rows}
        with open(args.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=float)
            f.write("\n")
        print(f"wrote {args.emit_json}")


if __name__ == "__main__":
    main()
