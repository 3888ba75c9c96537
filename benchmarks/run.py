"""Run every benchmark table/figure.  Prints ``name,us_per_call,derived``
summary CSV at the end (per-table CSVs above it).

    PYTHONPATH=src python -m benchmarks.run            # full
    REPRO_BENCH_QUICK=1 PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --only table1,perf
    PYTHONPATH=src python -m benchmarks.run --only table10,table11,oversub \
        --workers 8                                    # parallel UVM sweeps
    PYTHONPATH=src python -m benchmarks.run --scenario oversub-full \
        --workers 8     # full 11-bench x ratio x eviction-policy matrix

The UVM suites (table10/table11/perf/oversub/fig10/fig12) all route through
``repro.uvm.sweep``: simulations run on the backend-pluggable replay core
(``--backend {auto,numpy,pallas,legacy}``; pallas packs compatible cells into
multi-lane kernel batches), non-learned cells fan out over ``--workers``
processes, and completed cells persist under ``benchmarks/cache/sweep/``
for resume.  Every sweep row records the backend that actually ran.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (common, family_accuracy, fig5_features,
                        fig6_convergence,
                        fig9_predictors, mt_bench, oversub_bench,
                        fig10_latency, fig12_pcie, kernels_bench,
                        offload_bench, perf_ipc, serve_bench,
                        table1_transformer,
                        table2_clustering, table3_distance, table4_fc,
                        table5_hlsh, table67_memory, table8_revised,
                        table10_hitrate, table11_unity)
from repro.uvm import BACKENDS

SUITES = [
    ("table1", table1_transformer.main),
    # predictor-family comparison (simplified vs reference Transformer);
    # explicit empty argv: it has its own CLI like oversub_bench
    ("families", lambda: family_accuracy.main([])),
    ("table2", table2_clustering.main),
    ("table3", table3_distance.main),
    ("table4", table4_fc.main),
    ("table5", table5_hlsh.main),
    ("table67", table67_memory.main),
    ("table8", table8_revised.main),
    ("fig5", fig5_features.main),
    ("fig6", fig6_convergence.main),
    ("fig9", fig9_predictors.main),
    ("fig10", fig10_latency.main),
    ("table10", table10_hitrate.main),
    ("table11", table11_unity.main),
    ("fig12", fig12_pcie.main),
    ("perf", perf_ipc.main),
    ("kernels", kernels_bench.main),
    ("offload", offload_bench.main),
    # explicit empty argv: oversub_bench has its own CLI and must not
    # re-parse run.py's flags when invoked as a suite
    ("oversub", lambda: oversub_bench.main([])),
    # serving-traffic SLO sweep (rate x capacity x eviction x prefetcher)
    ("serve", lambda: serve_bench.main([])),
    # multi-tenant interference sweep (pair x capacity split x eviction)
    ("mt", lambda: mt_bench.main([])),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--workers", type=int, default=None,
                    help="process fan-out for the UVM sweep suites")
    ap.add_argument("--backend", default=None, choices=list(BACKENDS),
                    help="replay backend for the UVM sweep suites "
                         "(pallas = multi-lane kernel batches; auto "
                         "picks pallas on a TPU and numpy elsewhere; "
                         "every result row records "
                         "the backend that actually ran, so per-cell "
                         "fallbacks are visible)")
    ap.add_argument("--scenario", default=None,
                    help="run a named repro.uvm.scenarios oversubscription "
                         "matrix (e.g. oversub-full) as the only suite, "
                         "through the shared sweep caches; honors "
                         "--workers/--backend")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.workers is not None:
        common.SWEEP_WORKERS = args.workers
    if args.backend is not None:
        common.SWEEP_BACKEND = args.backend
    suites = SUITES
    if args.scenario and args.only:
        ap.error("--scenario replaces the suite list; it cannot be "
                 "combined with --only")
    if args.scenario:
        # scenario routing replaces the suite list: each name is a
        # registry-defined (bench x ratio x eviction x prefetcher) matrix,
        # resumable.  Comma lists run several matrices as separate
        # suites — module/argv are bound per iteration via default args
        # so the closures don't all collapse onto the last scenario.
        suites = []
        for scen in args.scenario.split(","):
            scenario_argv = ["--scenario", scen]
            # serve-* scenarios route through serve_bench so the printed
            # table carries the SLO latency columns; mt-* through
            # mt_bench for the per-tenant/interference columns
            module = (serve_bench if scen.startswith("serve")
                      else mt_bench if scen.startswith("mt")
                      else oversub_bench)
            suites.append((f"scenario:{scen}",
                           lambda m=module, a=scenario_argv: m.main(a)))
        only = None

    summary = []
    failed = []
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
            status = "ok"
        except Exception:
            traceback.print_exc()
            status = "FAILED"
            failed.append(name)
        summary.append((name, (time.time() - t0) * 1e6, status))

    print("\n== summary ==")
    print("name,us_per_call,derived")
    for name, us, status in summary:
        print(f"{name},{us:.0f},{status}")
    if failed:
        raise SystemExit(f"failed suites: {failed}")


if __name__ == "__main__":
    main()
