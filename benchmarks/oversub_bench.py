"""Beyond-paper study: prefetching under GPU memory *oversubscription*.

The paper evaluates without oversubscription (§7.1) and warns that
aggressive prefetching risks thrashing when memory is scarce (§2.3).  This
suite measures exactly that: device capacity swept from 1.5x down to 0.5x
the working set, for on-demand / tree / learned prefetching — and, per
arXiv 2204.02974, across every eviction policy (lru / random / hotcold),
since policy choice swings oversubscribed results by double digits.

CLI::

    PYTHONPATH=src python -m benchmarks.oversub_bench
    PYTHONPATH=src python -m benchmarks.oversub_bench \
        --emit-json BENCH_oversub.json          # rows carry the policy
    PYTHONPATH=src python -m benchmarks.oversub_bench \
        --scenario oversub-smoke                # registry-routed matrix

``--scenario`` routes through the declarative registry in
``repro.uvm.scenarios`` instead of the local grid, with the same sweep
engine (shared trace/prediction caches, resume, ``--workers`` via
``benchmarks.run``).
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List, Optional

from benchmarks.common import (QUICK, _eval_cell, get_eval_trace,
                               print_table, uvm_sweep)
from repro.uvm.eviction import EVICTION_POLICIES

BENCHES = ["Hotspot", "Backprop"]
FRACTIONS = [1.5, 0.75, 0.5]
PREFETCHERS = ("none", "tree", "learned")
#: the quick pass keeps the historical single-policy grid; the full run
#: sweeps every eviction policy (3x the cells, same traces/predictions)
EVICTIONS = ("lru",) if QUICK else EVICTION_POLICIES

COLS = ["bench", "capacity_x", "eviction", "prefetcher", "hit_rate",
        "pcie_mb", "ipc_vs_tree"]


def run(evictions=EVICTIONS) -> List[Dict]:
    # one batched (bench × capacity × eviction × prefetcher) grid through
    # the sweep API
    cells, tags = [], []
    for b in BENCHES:
        ws = get_eval_trace(b).working_set_pages
        for frac in FRACTIONS:
            for ev in evictions:
                for pf in PREFETCHERS:
                    cells.append(_eval_cell(b, pf,
                                            device_pages=int(ws * frac),
                                            eviction=ev))
                    tags.append((b, frac, ev, pf))
    rows = []
    for (b, frac, ev, pf), r in zip(tags, uvm_sweep(cells)):
        rows.append({
            "bench": b, "capacity_x": frac, "eviction": ev,
            "prefetcher": pf, "backend": r.get("backend"),
            "hit_rate": r["hit_rate"],
            "pcie_mb": r["pcie_bytes"] / 1e6,
            "ipc": r["ipc"],
        })
    _normalize_ipc(rows)
    return rows


def _normalize_ipc(rows: List[Dict]) -> None:
    """Normalize IPC within (bench, fraction, eviction) to tree runtime."""
    by = {}
    for r in rows:
        by.setdefault((r["bench"], r["capacity_x"], r["eviction"]),
                      {})[r["prefetcher"]] = r
    for d in by.values():
        tree_ipc = d.get("tree", {}).get("ipc", 1.0)
        for r in d.values():
            r["ipc_vs_tree"] = r["ipc"] / max(tree_ipc, 1e-9)


def run_scenario(name: str) -> List[Dict]:
    """Replay a registry scenario (``repro.uvm.scenarios``) through the
    shared benchmark sweep caches; returns the raw sweep rows (each one
    carries ``scenario``/``eviction``/``backend`` columns)."""
    from benchmarks import common
    from repro.uvm.scenarios import expand_scenario

    cells = expand_scenario(name, backend=common.SWEEP_BACKEND)
    return uvm_sweep(cells)


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Oversubscription capacity x eviction-policy sweep")
    ap.add_argument("--emit-json", default=None, metavar="PATH",
                    help="write result rows (policy column included) as "
                         "JSON for BENCH_* trajectory tracking")
    ap.add_argument("--scenario", default=None,
                    help="route a named repro.uvm.scenarios matrix "
                         "through the sweep instead of the local grid")
    args = ap.parse_args(argv)

    if args.scenario:
        rows = run_scenario(args.scenario)
        print_table(f"Scenario matrix: {args.scenario}", rows,
                    ["bench", "device_frac", "eviction", "prefetcher",
                     "backend", "hit_rate", "ipc", "unity"])
    else:
        rows = run()
        print_table("Oversubscription: capacity x eviction-policy sweep "
                    "(beyond paper)", rows, COLS)
    if args.emit_json:
        # derive the policy list from the rows themselves: on the
        # --scenario path the module-level grid does not describe them
        doc = {"version": 2, "scenario": args.scenario,
               "evictions": sorted({r["eviction"] for r in rows}),
               "rows": rows}
        with open(args.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=float)
            f.write("\n")
        print(f"wrote {args.emit_json}")


if __name__ == "__main__":
    main()
