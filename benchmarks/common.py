"""Shared benchmark infrastructure: cached traces, cached training cells,
cached UVM simulations."""
from __future__ import annotations

import functools
import hashlib
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core import (
    DeltaVocab, PredictorConfig, build_dataset, cluster_trace,
    delta_convergence, revised_config, train_predictor,
)
from repro.traces import GPUModel, generate_benchmark
from repro.uvm import BACKENDS, LearnedPrefetcher, UVMConfig
from repro.uvm.sweep import (SWEEP_VERSION, SweepCell, run_sweep,
                             simulate_cell)

CACHE_DIR = os.path.join(os.path.dirname(__file__), "cache")
# one trace/prediction cache for every suite: sweep workers and in-process
# uvm_cell paths hit the same content-addressed prediction arrays, so a
# benchmark's predictor trains exactly once per (trace, model) pair across
# the whole `benchmarks.run` session (and across sessions).
# REPRO_SWEEP_CACHE_DIR redirects the sweep-cell store — the perf gate
# points it at a throwaway dir so timed runs measure real work, never
# resume hits
SWEEP_DIR = os.environ.get("REPRO_SWEEP_CACHE_DIR",
                           os.path.join(CACHE_DIR, "sweep"))
TRACE_CACHE_DIR = os.path.join(SWEEP_DIR, "trace_cache")
QUICK = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"

# process fan-out for every sweep cell, learned included: each worker
# imports jax and either trains a benchmark's predictor or reuses it from
# the shared prediction cache.  Two in-flight cells sharing one cache key
# make the later worker wait on the training lock rather than retrain;
# grids order variants of the same benchmark far apart so that rarely
# costs a busy slot.  (run.py --workers overrides.)
SWEEP_WORKERS = int(os.environ.get("REPRO_SWEEP_WORKERS", "1"))

# replay backend for every UVM sweep cell (run.py --backend overrides):
# "auto" = pallas multi-lane kernels on a TPU, the NumPy engine
# everywhere else (the names are replay_core.BACKENDS); cells record the
# backend that actually ran in their rows, so fallbacks stay visible in
# the emitted results.  Validated here so a typo fails at import, not
# mid-sweep after the training suites already burned their wall-clock.
SWEEP_BACKEND = os.environ.get("REPRO_SWEEP_BACKEND", "auto")
if SWEEP_BACKEND not in BACKENDS:
    raise ValueError(
        f"REPRO_SWEEP_BACKEND={SWEEP_BACKEND!r}: choose from "
        f"{','.join(BACKENDS)}")

ALL_BENCHMARKS = ["AddVectors", "ATAX", "Backprop", "BICG", "Hotspot", "MVT",
                  "NW", "Pathfinder", "Srad-v2", "StreamTriad", "2DCONV"]
PREDICTOR_BENCHMARKS = ["AddVectors", "ATAX", "Backprop", "BICG", "Hotspot",
                        "MVT", "NW", "Pathfinder", "Srad-v2"]

STEPS = 60 if QUICK else 150
SERVICE_STEPS = 60 if QUICK else 150


def _cache_path(key: str) -> str:
    os.makedirs(CACHE_DIR, exist_ok=True)
    h = hashlib.sha256(key.encode()).hexdigest()[:20]
    return os.path.join(CACHE_DIR, f"{h}.json")


def cached(key: str, fn):
    path = _cache_path(key)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    result = fn()
    result["_seconds"] = time.time() - t0
    result["_key"] = key
    with open(path, "w") as f:
        json.dump(result, f, default=float)
    return result


@functools.lru_cache(maxsize=16)
def get_trace(name: str):
    return GPUModel().run(generate_benchmark(name))


# The paper simulates a fixed instruction budget per benchmark (Table 10),
# not whole-workload completion: arrays are only partially touched within
# the window, which is exactly what exposes the tree prefetcher's
# over-fetching (its accuracy is 0.79 there, not ~1.0).  UVM evaluation
# therefore runs on the leading 60% window of each trace.
EVAL_WINDOW = 0.6


@functools.lru_cache(maxsize=16)
def get_eval_trace(name: str):
    tr, _ = get_trace(name).split(EVAL_WINDOW)
    return tr


def train_cell(bench: str, *, cluster: str = "sm", distance: int = 1,
               arch: str = "transformer", attention: str = "full",
               revised: bool = False, quantize: bool = False,
               shuffle: bool = False, features: Optional[tuple] = None,
               n_layers: int = 2, n_heads: int = 4, steps: int = None,
               drop_feature: Optional[str] = None,
               single_feature: Optional[str] = None) -> Dict:
    """Train one predictor configuration on one benchmark; cached."""
    steps = steps or STEPS
    if revised:
        # the 12-dim revised model is ~100x cheaper per step than the
        # 200-dim transformer but needs more steps to converge
        steps = max(steps, 400)
    # v bumped 8 -> 9 with the deterministic (crc32) trace seeding: cached
    # rows trained on old salted-hash traces must not be served
    key = json.dumps(dict(
        v=9, bench=bench, cluster=cluster, distance=distance, arch=arch,
        attention=attention, revised=revised, quantize=quantize,
        shuffle=shuffle, features=features, n_layers=n_layers,
        n_heads=n_heads, steps=steps, drop=drop_feature,
        single=single_feature), sort_keys=True)

    def compute():
        from repro.core.model import EMB_DIMS, REVISED_FEATURES
        trace = get_trace(bench)
        ct = cluster_trace(trace, cluster)
        vocab = DeltaVocab.build(ct, distance=distance)
        conv = delta_convergence(ct)
        feats = features
        if feats is None:
            feats = REVISED_FEATURES if revised else tuple(EMB_DIMS)
        if drop_feature:
            feats = tuple(f for f in feats if f != drop_feature)
        if single_feature:
            feats = (single_feature,)
        if revised:
            import dataclasses as _dc
            cfg = revised_config(vocab.n_classes, conv, quantize=quantize)
            if attention != "hlsh":
                # explicit attention override (ablations)
                cfg = _dc.replace(cfg, attention=attention)
        else:
            cfg = PredictorConfig(
                n_classes=vocab.n_classes, arch=arch, attention=attention,
                features=feats, n_layers=n_layers, n_heads=n_heads,
                quantize=quantize)
        data = build_dataset(ct, vocab, features=list(cfg.features),
                             distance=distance, shuffle_tokens=shuffle,
                             max_train=10000, max_eval=3000)
        res = train_predictor(cfg, data, steps=steps)
        return {"bench": bench, "convergence": conv,
                "n_classes": vocab.n_classes,
                "f1": res.metrics["f1"], "top1": res.metrics["top1"],
                "top10": res.metrics.get("top10"),
                "train_seconds": res.train_seconds,
                "d_model": cfg.d_model}

    return cached(key, compute)


@functools.lru_cache(maxsize=32)
def _service_predictions(bench: str, steps: int):
    """Predictions for one benchmark's eval trace via the content-addressed
    prediction cache — trains at most once per (trace, model) pair, shared
    with the sweep workers through ``TRACE_CACHE_DIR``."""
    from repro.uvm import predcache
    trace = get_eval_trace(bench)
    preds = predcache.get_or_train(
        trace, steps=steps,
        cache_dir=os.path.join(TRACE_CACHE_DIR, predcache.DEFAULT_SUBDIR))
    return trace, preds


def _eval_cell(bench: str, prefetcher: str, *, prediction_us: float = 1.0,
               device_pages: Optional[int] = None,
               eviction: str = "lru") -> SweepCell:
    """The sweep-grid point matching the paper's evaluation setup."""
    return SweepCell(bench=bench, prefetcher=prefetcher,
                     prediction_us=prediction_us, device_pages=device_pages,
                     eviction=eviction,
                     window=EVAL_WINDOW, backend=SWEEP_BACKEND,
                     service_steps=SERVICE_STEPS)


def _run_cell(cell: SweepCell, timeline: bool = False) -> Dict:
    """One sweep cell on the in-process trace/predictor caches.  On the
    paper's default grid point the learned prefetcher shares a single
    trained service across every prediction_us and capacity point of a
    benchmark; off-default cells train their own (sweep.make_prefetcher)."""
    default_point = (cell.scale == 1.0 and cell.seed == 0
                     and cell.window == EVAL_WINDOW)
    trace = get_eval_trace(cell.bench) if default_point else None
    pf = None
    if (cell.prefetcher == "learned" and default_point
            and cell.service_steps == SERVICE_STEPS):
        _, preds = _service_predictions(cell.bench, cell.service_steps)
        pf = LearnedPrefetcher(
            preds,
            extra_latency_cycles=(cell.prediction_us
                                  * UVMConfig().cycles_per_us))
    row = simulate_cell(cell, trace=trace, prefetcher=pf,
                        record_timeline=timeline)
    row["simulated_instructions"] = row["n_instructions"]
    return row


def _cached_cell(cell: SweepCell) -> Dict:
    # keyed on SWEEP_VERSION too, so one knob invalidates both this JSON
    # cache and the sweep-cell store after a timing-model change
    key = json.dumps(dict(v=9, sweep_v=SWEEP_VERSION, **cell.to_dict()),
                     sort_keys=True)
    return cached(key, lambda: _run_cell(cell))


def uvm_cell(bench: str, prefetcher: str, *,
             prediction_us: float = 1.0,
             device_pages: Optional[int] = None,
             timeline: bool = False) -> Dict:
    """Run one UVM cell through the sweep engine; cached (except when a
    timeline is requested)."""
    cell = _eval_cell(bench, prefetcher, prediction_us=prediction_us,
                      device_pages=device_pages)
    if timeline:
        return _run_cell(cell, timeline=True)
    return _cached_cell(cell)


def uvm_sweep(cells: List[SweepCell]) -> List[Dict]:
    """Run a (bench × prefetcher × config) grid via the sweep orchestrator.

    Every cell — learned included — fans out across ``SWEEP_WORKERS``
    processes with on-disk resume state: the prediction cache under
    ``TRACE_CACHE_DIR`` gives learned cells train-once semantics, so a
    worker either reuses an existing predictions array or trains it for
    every other cell (and future run) of the same (trace, model) pair.
    """
    # several suites share this out_dir: skip the aggregate files so
    # they never reflect just the last suite's grid
    rows = run_sweep(cells, out_dir=SWEEP_DIR, cache_dir=TRACE_CACHE_DIR,
                     workers=SWEEP_WORKERS, write_aggregate=False)
    for row in rows:
        row["simulated_instructions"] = row["n_instructions"]
    return rows


def geomean(xs: List[float]) -> float:
    xs = np.asarray(xs, dtype=np.float64)
    return float(np.exp(np.mean(np.log(np.maximum(xs, 1e-12)))))


def print_table(title: str, rows: List[Dict], cols: List[str]) -> None:
    print(f"\n== {title} ==")
    print(",".join(cols))
    for r in rows:
        print(",".join(_fmt(r.get(c)) for c in cols))


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4f}"
    return str(v)
