"""Batched UVM sweep orchestrator + backend scheduler.

Runs (trace × prefetcher × config) grids through the backend-pluggable
replay core: cached trace generation, optional process fan-out, structured
JSON/CSV results, and resumability (each completed cell is persisted, so an
interrupted sweep picks up where it stopped).

Programmatic use::

    from repro.uvm.sweep import expand_grid, run_sweep
    cells = expand_grid(["ATAX", "BICG"], ["none", "tree", "oracle"],
                        device_fracs=[None, 0.5])
    rows = run_sweep(cells, out_dir="results/", workers=8)

CLI::

    PYTHONPATH=src python -m repro.uvm.sweep \
        --benches ATAX,BICG,Pathfinder,Hotspot \
        --prefetchers none,tree,learned,oracle \
        --evictions lru,random,hotcold \
        --backend pallas --out results/ --workers 8

    # the full oversubscription scenario matrix (11 benchmarks x ratio x
    # eviction policy x prefetcher, see repro.uvm.scenarios), resumable:
    PYTHONPATH=src python -m repro.uvm.sweep --scenario oversub-full \
        --out results/oversub/ --workers 8

Backend scheduling
------------------

Each cell names a replay backend, the one selector of its replay path
(``--backend {auto,numpy,pallas,legacy}``, the names of
``repro.uvm.replay_core.BACKENDS``; also the ``REPRO_SWEEP_BACKEND`` env
var).  The scheduler groups pending pallas-eligible cells — every
paper-facing prefetcher (none/block/tree/learned/oracle) whose page span
fits a lane — into multi-lane batches bucketed by *prefetcher family* in
addition to span/length (a lane batch is always family-homogeneous:
demand, tree, learned, and oracle lanes are different kernels with
different per-lane state) and replays each batch in ONE ``jax_pallas``
kernel launch (one lane per cell, padded to the longest trace; see
``repro.uvm.backends.pallas_backend``).  Everything unpackable falls back
*per cell* down the ``pallas → numpy → legacy`` chain, and every result
row records the backend that actually ran in its ``backend`` column, so
fallbacks are visible instead of silently reading as covered.  That
per-cell fallback covers only what the lanes decline by contract (span or
length caps, timelines); a lane batch that fails at runtime raises.
``auto`` resolves to the pallas lanes on a TPU, where they are a compiled
device program, and to the NumPy engine everywhere else.

One process holds the chip
--------------------------

All device work — lane batches, and predictor training and prediction
for ``learned`` cells — runs in the process that called
:func:`run_sweep`, before any ``--workers`` fan-out.  Worker processes
replay the remaining cells on the host and never initialise the
accelerator: device work asked of one raises
(``repro.uvm.replay_core.require_device``).

Train-once learned cells
------------------------

The ``learned`` prefetcher needs the paper's predictor service (jax;
expensive to train), but its predictions depend only on the *trace content*
and the *predictor config* — not on the replay knobs (``prediction_us``,
``device_frac``/``device_pages``, backend) a sensitivity grid
varies.
:func:`make_prefetcher` therefore routes predictions through
``repro.uvm.predcache``: a grid trains **once per (trace, model) pair** and
every other learned cell of the grid reuses the cached array, in-process
(memo) and across runs (content-addressed ``.npy`` files under
``<trace cache>/pred_cache/``, written with atomic rename).

Learned cells always run in the sweep's own process (see "One process
holds the chip"); across concurrent sweeps the disk cache is shared
through the filesystem: the first process to miss a key takes a lockfile
and trains, others hitting the same key wait for the array instead of
training again — a (trace × prediction_us × device_frac) grid costs one
training run per trace no matter how many variants ride on it.
``REPRO_PREDCACHE=0`` restores the retrain-per-cell behavior.

A prebuilt predictions array can still be supplied per bench via
:func:`simulate_cell`'s ``prefetcher`` override.

Workers are deterministic: a cell's row is a pure function of the cell, so
serial and parallel sweeps produce identical results (modulo the ``seconds``
timing column).

Crash safety (leases, retries, quarantine)
------------------------------------------

With an ``out_dir``, the sweep is fault-tolerant end to end (the full
protocol is documented in ``repro/uvm/backends/README.md``, "Fault
model"):

* Every persisted artifact — ``cells/<key>.json`` rows, cached trace
  ``.npz`` files, prediction-cache entries — is **checksummed** and
  written with atomic rename.  A torn or corrupted file detected on read
  is quarantined (renamed ``*.corrupt``) with a warning and the work is
  redone, so resume never mixes damaged state into results.  Cell files
  also embed ``SWEEP_VERSION``; a version mismatch requeues the cell
  instead of mixing rows across timing-model versions.
* Per-cell execution takes an expiring **lease**
  (``cells/<key>.lease``, via ``repro.distributed.fault_tolerance``):
  a SIGKILLed worker's lease is reclaimed immediately through the
  owner-pid liveness check (TTL expiry covers remote/multi-host owners),
  so crashed workers never wedge the grid.  Leases are advisory — cells
  are deterministic and their writes atomic, so the benign steal race
  can only duplicate work, not corrupt results.
* A failing cell **retries with capped exponential backoff**
  (``REPRO_SWEEP_BACKOFF``); after ``max_attempts`` lease claims
  (``REPRO_SWEEP_MAX_ATTEMPTS``) it lands in the **quarantine manifest**
  (``out_dir/quarantine.json`` + a stub row with ``quarantined=True``)
  instead of aborting the grid — visible, never silent.
* With ``--workers N`` the fan-out is a pool of lease workers supervised
  by a :class:`~repro.distributed.fault_tolerance.HeartbeatMonitor`:
  dead workers are restarted, silent-but-alive workers are terminated so
  their leases free up, and any worker can pick up any unleased cell.
* The ``repro.uvm.faults`` plane (``REPRO_FAULT_PLAN``) injects
  deterministic chaos — kills, artifact corruption, transient backend
  raises — at the sites marked throughout this module; the chaos harness
  (``python -m repro.uvm.faults``) proves a sweep under such a plan
  converges byte-identically to a fault-free run.
"""
from __future__ import annotations

import argparse
import collections
import csv
import dataclasses
import functools
import hashlib
import json
import multiprocessing
import os
import sys
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.families import MODEL_FAMILIES  # jax-free config layer
from repro.distributed import fault_tolerance as ft
from repro.traces.trace import ACCESS_DTYPE, Trace
from repro.uvm import adaptive, faults
from repro.uvm.config import UVMConfig
from repro.uvm.eviction import EVICTION_POLICIES
from repro.uvm.prefetchers import (BlockPrefetcher, LearnedPrefetcher,
                                   NoPrefetcher, OraclePrefetcher,
                                   Prefetcher, TreePrefetcher)
from repro.uvm.replay_core import (BACKENDS, ReplayRequest, backend_chain,
                                   dispatch, get_backend)
from repro.uvm.simulator import UVMStats

#: cell-spec prefetcher names to concrete types — the single source the
#: CLI vocabulary (PREFETCHERS), :func:`make_prefetcher`, and the lane
#: scheduler's packability/family maps all derive from, so a new
#: prefetcher added here flows everywhere at once
_PREFETCHER_TYPES = {"none": NoPrefetcher, "block": BlockPrefetcher,
                     "tree": TreePrefetcher, "learned": LearnedPrefetcher,
                     "oracle": OraclePrefetcher}
PREFETCHERS = tuple(_PREFETCHER_TYPES)

#: bump on any intentional change to the timing model, trace generators,
#: prediction pipeline, or row schema — invalidates persisted sweep cells
#: and cached traces so a resumed sweep never mixes pre- and post-change
#: numbers (v7: serve rows carry ``slo_source`` — ``kernel`` when the
#: replay that ran the cell emitted its step clocks in-band, including
#: the pallas lanes' in-kernel capture; ``side-pass`` when a separate
#: NumPy replay recovered them; v8: learned cells carry a
#: ``model_family`` column — simplified vs the reference Transformer
#: variants — and the ``adaptive`` pseudo-policy resolves to a concrete
#: policy at prepare time, recorded honestly in ``eviction``;
#: v9: multi-tenant interleaved rows (``repro.traces.interleave``) carry
#: ``tenants`` / ``capacity_split`` / per-tenant hit rates and the
#: interference-slowdown columns, and the adaptive probe is keyed by the
#: cell's prefetcher family instead of demand-paging only)
SWEEP_VERSION = 9

#: serving SLO columns (``repro.offload.serve_trace``): per-decode-step
#: latency and time-to-first-token percentiles, None on non-serve rows
SERVE_LATENCY_FIELDS = (
    "decode_lat_p50_us", "decode_lat_p95_us", "decode_lat_p99_us",
    "ttft_p50_us", "ttft_p95_us", "ttft_p99_us",
)

#: multi-tenant columns (``repro.traces.interleave``): tenant count,
#: the capacity split the cell replayed under (``"shared"`` or
#: ``"f0/f1"`` quota fractions), per-tenant hit rates, and the
#: interference slowdown — each tenant's completion cycles in the mix
#: over its *solo* replay (the tenant's accesses extracted and replayed
#: alone at the capacity its quota grants, or the full device when
#: shared).  None on single-tenant rows.
MT_FIELDS = (
    "tenants", "capacity_split", "hit_rate_t0", "hit_rate_t1",
    "slowdown_t0", "slowdown_t1", "interference_slowdown",
)

#: columns of the structured results, in CSV order (``backend`` is the
#: implementation that actually ran the cell: legacy / numpy / pallas;
#: ``eviction`` the policy the cell replayed under, ``scenario`` the
#: scenario-registry entry the cell expanded from — None for ad-hoc grids)
ROW_FIELDS = [
    "bench", "prefetcher", "scale", "seed", "window", "prediction_us",
    "device_pages", "device_frac", "eviction", "model_family", "scenario",
    "backend", "n_accesses", "n_instructions",
    "cycles", "ipc", "hits", "late", "faults", "hit_rate", "prefetch_issued",
    "prefetch_used", "accuracy", "coverage", "unity", "pages_migrated",
    "pages_evicted", "pcie_bytes", *SERVE_LATENCY_FIELDS, "slo_source",
    *MT_FIELDS, "retries", "quarantined", "seconds",
]


def parse_capacity_split(split: Optional[str]) -> Optional[Tuple[float,
                                                                 float]]:
    """Validate/parse a ``capacity_split`` spec.

    ``None`` or ``"shared"`` -> None (tenants contend for the whole
    device); ``"f0/f1"`` -> the two per-tenant quota *fractions* of
    ``device_pages`` (``f0 + f1 <= 1``; the remainder is the shared
    spill pool, see ``UVMConfig.tenant_pages``).  Raises ``ValueError``
    on anything else — scenario validation and cell preparation share
    this single parser.
    """
    if split is None or split == "shared":
        return None
    try:
        f0, f1 = (float(x) for x in str(split).split("/"))
    except ValueError:
        raise ValueError(
            f"bad capacity_split {split!r}: expected 'shared' or two "
            "quota fractions like '0.5/0.5'") from None
    if f0 < 0 or f1 < 0 or f0 + f1 > 1.0 + 1e-9:
        raise ValueError(
            f"bad capacity_split {split!r}: fractions must be "
            "non-negative and sum to at most 1")
    return f0, f1


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One point of a sweep grid (hashable, JSON-serializable)."""

    bench: str
    prefetcher: str
    scale: float = 1.0
    seed: int = 0
    window: Optional[float] = 0.6       # leading trace fraction (paper eval)
    prediction_us: float = 1.0          # learned-model inference overhead
    device_pages: Optional[int] = None  # absolute capacity, or ...
    device_frac: Optional[float] = None  # ... fraction of the working set
    eviction: str = "lru"               # lru | random | hotcold | adaptive
    capacity_split: Optional[str] = None  # mt cells: "shared" | "f0/f1"
    scenario: Optional[str] = None      # scenario-registry entry (if any)
    backend: str = "auto"               # replay_core.BACKENDS
    service_steps: int = 150            # learned-predictor training steps
    model_family: str = "simplified"    # predictor family for learned cells
                                        # (repro.core.families.MODEL_FAMILIES)

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def key(self) -> str:
        blob = json.dumps({"_v": SWEEP_VERSION, **self.to_dict()},
                          sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


def expand_grid(benches: Sequence[str], prefetchers: Sequence[str], *,
                scales: Sequence[float] = (1.0,),
                seeds: Sequence[int] = (0,),
                windows: Sequence[Optional[float]] = (0.6,),
                prediction_us: Sequence[float] = (1.0,),
                device_fracs: Sequence[Optional[float]] = (None,),
                evictions: Sequence[str] = ("lru",),
                model_families: Sequence[str] = ("simplified",),
                capacity_splits: Sequence[Optional[str]] = (None,),
                scenario: Optional[str] = None,
                backend: str = "auto",
                service_steps: int = 150) -> List[SweepCell]:
    """Cartesian product of the sweep axes, in deterministic order."""
    cells = []
    for bench in benches:
        for pf in prefetchers:
            for scale in scales:
                for seed in seeds:
                    for window in windows:
                        for us in prediction_us:
                            for frac in device_fracs:
                                for ev in evictions:
                                    for split in capacity_splits:
                                        for fam in model_families:
                                            cells.append(SweepCell(
                                                bench=bench, prefetcher=pf,
                                                scale=scale, seed=seed,
                                                window=window,
                                                prediction_us=us,
                                                device_frac=frac,
                                                eviction=ev,
                                                capacity_split=split,
                                                scenario=scenario,
                                                backend=backend,
                                                service_steps=service_steps,
                                                model_family=fam))
    return cells


# ---------------------------------------------------------------------------
# cached trace generation
# ---------------------------------------------------------------------------

def _trace_cache_path(cache_dir: str, bench: str, scale: float,
                      seed: int) -> str:
    tag = hashlib.sha256(
        json.dumps([SWEEP_VERSION, bench, scale, seed]).encode()
    ).hexdigest()[:16]
    return os.path.join(cache_dir, f"trace_{bench}_{tag}.npz")


def _trace_digest(accesses: np.ndarray, meta_json: str) -> str:
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(accesses).tobytes())
    h.update(meta_json.encode())
    return h.hexdigest()


def quarantine_artifact(path: str, reason: str) -> None:
    """Move a damaged persisted artifact aside (``<path>.corrupt``) with a
    warning, so the caller regenerates instead of crashing — and the
    evidence survives for inspection instead of being overwritten."""
    warnings.warn(f"{reason}: quarantining {path} -> {path}.corrupt and "
                  "regenerating", RuntimeWarning)
    try:
        os.replace(path, path + ".corrupt")
    except OSError:                   # already gone: a racer quarantined it
        pass


class _TraceMemo:
    """Bounded in-process LRU over deserialized (and checksum-verified)
    traces, keyed by the full trace identity (bench, scale, seed, window,
    cache_dir).

    Co-scheduled cells sharing a trace — 24 serve-smoke cells ride on 4
    distinct traces — hit the memo instead of re-opening and re-hashing
    the npz cache file per cell: the checksum is verified **once per
    (path, sha)** within a process, and the PR 7 quarantine path is
    untouched for cold reads (a fresh process reading a corrupted file
    still quarantines + regenerates).  Thread-safe: the lane scheduler's
    prepare stage runs in a thread pool.  ``REPRO_TRACE_MEMO`` overrides
    the entry bound (0 disables the memo entirely).
    """

    def __init__(self, maxsize: int = 32):
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._data: "collections.OrderedDict[Tuple, Trace]" = \
            collections.OrderedDict()

    def _bound(self) -> int:
        try:
            return int(os.environ.get("REPRO_TRACE_MEMO", self.maxsize))
        except ValueError:
            return self.maxsize

    def get(self, key: Tuple) -> Optional[Trace]:
        if self._bound() <= 0:
            return None
        with self._lock:
            trace = self._data.pop(key, None)
            if trace is not None:
                self._data[key] = trace       # refresh LRU position
            return trace

    def put(self, key: Tuple, trace: Trace) -> None:
        bound = self._bound()
        if bound <= 0:
            return
        with self._lock:
            self._data[key] = trace
            self._data.move_to_end(key)
            while len(self._data) > bound:
                self._data.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()


#: process-wide trace memo (worker processes each build their own)
_trace_memo = _TraceMemo()

#: single-flight guard: concurrent prepare-stage threads asking for the
#: same trace must resolve to ONE generate/deserialize/checksum, with the
#: others blocking on the winner's memo write instead of racing on the
#: cache file
_trace_flight_guard = threading.Lock()
_trace_flights: Dict[Tuple, threading.Lock] = {}


def _trace_flight(key: Tuple) -> threading.Lock:
    with _trace_flight_guard:
        return _trace_flights.setdefault(key, threading.Lock())


def load_trace(bench: str, scale: float = 1.0, seed: int = 0,
               window: Optional[float] = 0.6,
               cache_dir: Optional[str] = None) -> Trace:
    """Generate (or load from the npz disk cache) one benchmark trace and
    cut the leading evaluation window.

    Cached traces embed a content checksum; a truncated or corrupted
    cache file (killed writer on a non-atomic filesystem, disk rot, an
    injected ``trace.artifact`` fault) is quarantined with a warning and
    the trace is regenerated deterministically — never replayed from
    damaged bytes.

    Serve bench names (``repro.offload.serve_trace.SERVE_WORKLOADS``,
    including ``@r<rate>`` variants) route through the serving load
    generator instead of the GPU model; serve traces are never
    window-split (the split would desynchronize the decode-step bounds
    their latency columns derive from).

    Loads are memoized in-process (:class:`_TraceMemo`): cells sharing a
    trace deserialize and checksum it once, not once per cell, and
    concurrent prepare-stage threads single-flight on the key instead of
    generating the same trace twice.
    """
    memo_key = (bench, scale, seed, window, cache_dir)
    memoized = _trace_memo.get(memo_key)
    if memoized is not None:
        obs.count("trace.memo_hits")
        return memoized
    with _trace_flight(memo_key):
        memoized = _trace_memo.get(memo_key)    # the winner filled it
        if memoized is not None:
            obs.count("trace.memo_hits")
            return memoized
        obs.count("trace.memo_misses")
        with obs.span("trace.build", bench=bench, seed=seed):
            trace = _load_trace_uncached(bench, scale, seed, window,
                                         cache_dir)
        _trace_memo.put(memo_key, trace)
        return trace


def _load_trace_uncached(bench: str, scale: float, seed: int,
                         window: Optional[float],
                         cache_dir: Optional[str]) -> Trace:
    trace = None
    path = None
    if cache_dir:
        path = _trace_cache_path(cache_dir, bench, scale, seed)
        if os.path.exists(path):
            try:
                with np.load(path, allow_pickle=False) as z:
                    meta_json = str(z["meta"])
                    accesses = z["accesses"].astype(ACCESS_DTYPE,
                                                    copy=False)
                    stored_sha = str(z["sha"])
                if stored_sha != _trace_digest(accesses, meta_json):
                    raise ValueError("trace cache checksum mismatch")
                meta = json.loads(meta_json)
                trace = Trace(
                    name=meta["name"],
                    accesses=accesses,
                    array_bases=meta["array_bases"],
                    array_pages=meta["array_pages"],
                    n_instructions=meta["n_instructions"],
                    meta=meta.get("meta", {}),
                )
            except Exception as e:
                quarantine_artifact(
                    path, f"invalid cached trace for {bench} ({e!r})")
                trace = None
    if trace is None:
        from repro.offload.serve_trace import build_serve_trace, \
            is_serve_bench
        from repro.traces.interleave import build_mt_trace, is_mt_bench
        if is_serve_bench(bench):
            trace = build_serve_trace(bench, scale=scale, seed=seed)
        elif is_mt_bench(bench):
            trace = build_mt_trace(bench, scale=scale, seed=seed)
        else:
            from repro.traces import GPUModel, generate_benchmark
            from repro.traces.gpu_model import GPUModelConfig
            spec = generate_benchmark(bench, scale=scale, seed=seed)
            trace = GPUModel(GPUModelConfig(seed=seed)).run(spec)
        if path:
            os.makedirs(cache_dir, exist_ok=True)
            meta = json.dumps({
                "name": trace.name,
                "array_bases": trace.array_bases,
                "array_pages": trace.array_pages,
                "n_instructions": trace.n_instructions,
                "meta": trace.meta,
            })
            tmp = path + f".{os.getpid()}.{threading.get_ident()}.tmp.npz"
            np.savez(tmp, accesses=trace.accesses, meta=np.array(meta),
                     sha=np.array(_trace_digest(trace.accesses, meta)))
            os.replace(tmp, path)
            faults.corrupt("trace.artifact", path, os.path.basename(path))
    if window is not None and not (trace.meta and "serve" in trace.meta):
        trace, _ = trace.split(window)
    return trace


# ---------------------------------------------------------------------------
# per-cell simulation
# ---------------------------------------------------------------------------

def make_prefetcher(cell: SweepCell, trace: Trace, config: UVMConfig,
                    cache_dir: Optional[str] = None) -> Prefetcher:
    if cell.prefetcher == "oracle":
        return OraclePrefetcher(np.asarray(trace.pages))
    if cell.prefetcher == "learned":
        # train-once: predictions come from the content-addressed cache —
        # one training run per (trace, model) pair, shared across every
        # prediction_us / capacity variant, process, and (with cache_dir)
        # run.  See repro.uvm.predcache.
        from repro.uvm import predcache
        pred_dir = (os.path.join(cache_dir, predcache.DEFAULT_SUBDIR)
                    if cache_dir else None)
        preds = predcache.get_or_train(
            trace, steps=cell.service_steps, cache_dir=pred_dir,
            service_kwargs={"model_family": cell.model_family})
        return LearnedPrefetcher(
            preds,
            extra_latency_cycles=cell.prediction_us * config.cycles_per_us)
    cls = _PREFETCHER_TYPES.get(cell.prefetcher)
    if cls is None:
        raise ValueError(f"unknown prefetcher {cell.prefetcher!r}")
    return cls()


def prepare_cell(cell: SweepCell, *, cache_dir: Optional[str] = None,
                 trace: Optional[Trace] = None,
                 prefetcher: Optional[Prefetcher] = None):
    """Materialize one cell's (trace, config, prefetcher, device_pages).

    Shared by the per-cell path (:func:`simulate_cell`) and the lane-batch
    scheduler, so a cell resolves to the same replay inputs no matter which
    backend ends up running it.
    """
    if trace is None:
        trace = load_trace(cell.bench, cell.scale, cell.seed, cell.window,
                           cache_dir=cache_dir)
    device_pages = cell.device_pages
    if device_pages is None and cell.device_frac is not None:
        device_pages = int(trace.working_set_pages * cell.device_frac)
    # the adaptive pseudo-policy resolves to a concrete one here, before
    # the replay config exists: each lane runs under a concrete policy and
    # the row's eviction column (from stats.eviction) records what ran
    eviction = adaptive.resolve_eviction(cell.eviction, cell.bench,
                                         trace=trace,
                                         device_pages=device_pages,
                                         prefetcher=cell.prefetcher)
    fracs = parse_capacity_split(cell.capacity_split)
    tenant_pages = None
    if fracs is not None:
        if device_pages is None:
            raise ValueError(
                f"cell {cell.bench}/{cell.prefetcher}: capacity_split="
                f"{cell.capacity_split!r} needs a device capacity "
                "(device_pages or device_frac)")
        tenant_pages = (int(fracs[0] * device_pages),
                        int(fracs[1] * device_pages))
    config = UVMConfig(prediction_overhead_us=cell.prediction_us,
                       device_pages=device_pages, eviction=eviction,
                       tenant_pages=tenant_pages)
    if prefetcher is None:
        prefetcher = make_prefetcher(cell, trace, config,
                                     cache_dir=cache_dir)
    return trace, config, prefetcher, device_pages


def _finish_row(cell: SweepCell, stats: UVMStats,
                device_pages: Optional[int], seconds: float,
                record_timeline: bool = False) -> Dict:
    row = cell.to_dict()
    row.pop("service_steps", None)
    row.update(
        device_pages=device_pages,
        backend=stats.backend,
        eviction=stats.eviction,
        n_accesses=stats.n_accesses,
        n_instructions=stats.n_instructions,
        cycles=stats.cycles,
        ipc=stats.ipc,
        hits=stats.hits,
        late=stats.late,
        faults=stats.faults,
        hit_rate=stats.hit_rate,
        prefetch_issued=stats.prefetch_issued,
        prefetch_used=stats.prefetch_used,
        accuracy=stats.accuracy,
        coverage=stats.coverage,
        unity=stats.unity,
        pages_migrated=stats.pages_migrated,
        pages_evicted=stats.pages_evicted,
        pcie_bytes=stats.pcie_bytes,
        retries=0,                 # lease attempts beyond the first; the
        quarantined=False,         # retry layer overwrites on retried cells
        seconds=seconds,
    )
    for f in SERVE_LATENCY_FIELDS:
        row.setdefault(f, None)      # filled on serve rows, None otherwise
    row.setdefault("slo_source", None)
    for f in MT_FIELDS:
        row.setdefault(f, None)      # filled on multi-tenant rows
    if record_timeline and stats.timeline is not None:
        row["timeline"] = stats.timeline.tolist()
    return row


def _serve_step_bounds(trace: Trace) -> Optional[np.ndarray]:
    """Decode-step bounds of a serve trace, None for benchmark traces."""
    if trace.meta and "serve" in trace.meta:
        from repro.offload.serve_trace import trace_step_bounds
        return trace_step_bounds(trace)
    return None


def _mt_step_bounds(trace: Trace) -> Optional[np.ndarray]:
    """Step bounds marking each tenant's *last access* in an interleaved
    trace (None for single-tenant traces): the replay's step clocks at
    these bounds are the per-tenant completion cycles behind the
    interference-slowdown columns — reusing the serve-row step-clock
    machinery, in-kernel on the pallas lanes included."""
    from repro.traces.interleave import tenant_last_index
    last = tenant_last_index(trace)
    if last is None:
        return None
    bounds = sorted({i + 1 for i in last if i >= 0})
    return np.asarray(bounds, dtype=np.int64)


def _step_bounds(trace: Trace) -> Optional[np.ndarray]:
    """The step bounds a cell's replay should clock: serve decode steps,
    multi-tenant completion bounds, or None."""
    bounds = _serve_step_bounds(trace)
    return bounds if bounds is not None else _mt_step_bounds(trace)


def _serve_side_pass(cell: SweepCell, trace: Trace, config: UVMConfig,
                     stats: UVMStats, bounds: np.ndarray,
                     cache_dir: Optional[str]) -> np.ndarray:
    """NumPy side-pass replay recovering a serve row's step clocks, with
    a built-in differential check: its integer counters must match the
    primary row exactly, whatever backend produced it."""
    pf = make_prefetcher(cell, trace, config, cache_dir=cache_dir)
    req = ReplayRequest(trace, pf, config, step_bounds=bounds)
    check = get_backend("numpy").replay([req])[0]
    for f in ("hits", "late", "faults", "prefetch_issued",
              "prefetch_used", "pages_migrated", "pages_evicted"):
        if getattr(check, f) != getattr(stats, f):
            raise AssertionError(
                f"serve step-clock side pass disagrees with the "
                f"{stats.backend} row on {f}: {getattr(check, f)} != "
                f"{getattr(stats, f)} "
                f"({cell.bench}/{cell.prefetcher}/{cell.eviction})")
    return check.step_clocks


def _serve_latency_row(cell: SweepCell, trace: Trace, config: UVMConfig,
                       stats: UVMStats,
                       cache_dir: Optional[str]) -> Dict:
    """The serving SLO columns for one serve-trace row.

    Every backend now records ``step_clocks`` in-band (legacy/numpy
    host-side, the pallas lanes in-kernel), so the normal path is pure
    percentile math over the clocks the primary replay already produced
    — ``slo_source="kernel"``.  The NumPy side pass of PR 6 survives in
    two demoted roles: a fallback when a row somehow arrives without
    clocks (``slo_source="side-pass"``), and an opt-in differential
    check (``REPRO_SERVE_CHECK=1``) that re-replays the cell host-side
    and requires counters AND clocks to match bit-for-bit.
    """
    from repro.offload.serve_trace import (serve_latency_columns,
                                           trace_step_bounds)

    bounds = trace_step_bounds(trace)
    clocks = stats.step_clocks
    source = "kernel"
    if clocks is None or len(clocks) != len(bounds):
        clocks = _serve_side_pass(cell, trace, config, stats, bounds,
                                  cache_dir)
        source = "side-pass"
    elif os.environ.get("REPRO_SERVE_CHECK", "0") == "1":
        check = _serve_side_pass(cell, trace, config, stats, bounds,
                                 cache_dir)
        if not np.array_equal(np.asarray(clocks), np.asarray(check)):
            raise AssertionError(
                f"in-band step clocks of the {stats.backend} row diverge "
                f"from the NumPy side pass "
                f"({cell.bench}/{cell.prefetcher}/{cell.eviction})")
    row = serve_latency_columns(trace, clocks, config)
    row["slo_source"] = source
    return row


#: solo-replay cycles memo for the interference-slowdown columns: cells
#: of one grid share solo baselines across capacity splits and backends
#: (key: trace identity + tenant + solo capacity + replay knobs)
_solo_memo: Dict[Tuple, int] = {}
_solo_lock = threading.Lock()


def _mt_solo_cycles(cell: SweepCell, trace: Trace, tenant: int,
                    capacity: Optional[int], eviction: str,
                    cache_dir: Optional[str]) -> int:
    """Cycles of one tenant's *solo* replay: its accesses extracted from
    the interleaved trace (``mt_component_trace``) and replayed alone on
    the NumPy engine at ``capacity`` — the tenant's quota on split rows,
    the full device on shared rows.  Memoized: every cell of a grid that
    shares (trace, tenant, capacity, prefetcher, policy) reuses one
    baseline replay."""
    from repro.traces.interleave import mt_component_trace

    key = (cell.bench, cell.scale, cell.seed, cell.window, tenant,
           capacity, cell.prefetcher, eviction, cell.prediction_us,
           cell.model_family)
    with _solo_lock:
        hit = _solo_memo.get(key)
    if hit is not None:
        return hit
    solo = mt_component_trace(trace, tenant)
    cfg = UVMConfig(prediction_overhead_us=cell.prediction_us,
                    device_pages=capacity, eviction=eviction)
    pf = make_prefetcher(cell, solo, cfg, cache_dir=cache_dir)
    stats = get_backend("numpy").replay([ReplayRequest(solo, pf, cfg)])[0]
    cycles = int(stats.cycles)
    with _solo_lock:
        _solo_memo.setdefault(key, cycles)
    return cycles


def _mt_row(cell: SweepCell, trace: Trace, config: UVMConfig,
            stats: UVMStats, device_pages: Optional[int],
            cache_dir: Optional[str]) -> Dict:
    """The multi-tenant columns for one interleaved-trace row: tenant
    count, the capacity split that ran, per-tenant hit rates, and the
    interference slowdown (per-tenant completion cycles in the mix over
    the tenant's solo replay)."""
    from repro.traces.interleave import N_TENANTS, tenant_last_index

    row: Dict = {"tenants": N_TENANTS,
                 "capacity_split": cell.capacity_split or "shared"}
    th, ta = stats.tenant_hits, stats.tenant_accesses
    for t in range(N_TENANTS):
        row[f"hit_rate_t{t}"] = (th[t] / ta[t]) if ta and ta[t] else None

    last = tenant_last_index(trace)
    bounds = sorted({i + 1 for i in last if i >= 0})
    clocks = stats.step_clocks
    if clocks is None or len(clocks) != len(bounds):
        # a row without in-band clocks (or with desynchronized bounds)
        # recovers them from the NumPy side pass, counter-checked
        # against the primary replay like the serve rows
        clocks = _serve_side_pass(cell, trace, config, stats,
                                  np.asarray(bounds, dtype=np.int64),
                                  cache_dir)
    cyc_at = {b: float(c) for b, c in zip(bounds, np.asarray(clocks))}
    slowdowns = []
    for t in range(N_TENANTS):
        if last[t] < 0:
            row[f"slowdown_t{t}"] = None
            continue
        capacity = (config.tenant_pages[t] if config.tenant_pages
                    else device_pages)
        solo = _mt_solo_cycles(cell, trace, t, capacity, config.eviction,
                               cache_dir)
        sd = cyc_at[last[t] + 1] / solo if solo > 0 else None
        row[f"slowdown_t{t}"] = sd
        if sd is not None:
            slowdowns.append(sd)
    row["interference_slowdown"] = max(slowdowns) if slowdowns else None
    return row


def _is_mt_trace(trace: Trace) -> bool:
    from repro.traces.interleave import tenant_boundary
    return tenant_boundary(trace) is not None


def simulate_cell(cell: SweepCell, *, cache_dir: Optional[str] = None,
                  trace: Optional[Trace] = None,
                  prefetcher: Optional[Prefetcher] = None,
                  record_timeline: bool = False) -> Dict:
    """Run one cell and return its structured row.  ``trace`` /
    ``prefetcher`` overrides let callers inject pre-built objects (e.g. a
    LearnedPrefetcher sharing one trained service across cells)."""
    t0 = time.time()
    trace, config, prefetcher, device_pages = prepare_cell(
        cell, cache_dir=cache_dir, trace=trace, prefetcher=prefetcher)
    # serve traces carry decode-step bounds into the replay so the row
    # gets per-step clocks in one pass, whichever backend runs it (the
    # pallas lanes capture them in-kernel); multi-tenant traces reuse the
    # same machinery for per-tenant completion cycles
    serve_bounds = _serve_step_bounds(trace)
    step_bounds = serve_bounds if serve_bounds is not None \
        else _mt_step_bounds(trace)
    request = ReplayRequest(trace=trace, prefetcher=prefetcher,
                            config=config, record_timeline=record_timeline,
                            step_bounds=step_bounds)
    stats = dispatch(request, cell.backend)
    row = _finish_row(cell, stats, device_pages, time.time() - t0,
                      record_timeline)
    if serve_bounds is not None:
        row.update(_serve_latency_row(cell, trace, config, stats,
                                      cache_dir))
    elif _is_mt_trace(trace):
        row.update(_mt_row(cell, trace, config, stats, device_pages,
                           cache_dir))
    return row


def _worker(args) -> Dict:
    cell, cache_dir = args
    return simulate_cell(cell, cache_dir=cache_dir)


def _init_worker(path: List[str]) -> None:
    """Worker-process initializer: children need the parent's sys.path
    (the repo uses a src layout without installation), and the parent
    holds the accelerator."""
    from repro.uvm.replay_core import hand_device_to_parent
    hand_device_to_parent()
    for p in reversed(path):
        if p not in sys.path:
            sys.path.insert(0, p)


# ---------------------------------------------------------------------------
# crash-safe cell store: checksummed envelopes, leases, attempts, quarantine
# ---------------------------------------------------------------------------

def _cell_path(out_dir: str, cell: SweepCell) -> str:
    return os.path.join(out_dir, "cells", f"{cell.key()}.json")


def write_cell_row(path: str, row: Dict) -> None:
    """Persist one result row as a checksummed, versioned envelope
    (``{_v, sha256, row}``) with atomic write-rename.  Readers verify the
    checksum and version, so a resumed sweep can never load a torn,
    corrupted, or cross-version row as if it were a completed cell."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = json.dumps(row, sort_keys=True)
    doc = {"_v": SWEEP_VERSION,
           "sha256": hashlib.sha256(payload.encode()).hexdigest(),
           "row": row}
    key = os.path.basename(path)
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, sort_keys=True)
    faults.fire("cell.result.write", key)    # kill here = torn write
    os.replace(tmp, path)
    faults.corrupt("cell.result.artifact", path, key)


def load_cell_row(path: str) -> Tuple[Optional[Dict], str]:
    """Load a persisted cell row.  Returns ``(row, "ok")`` or ``(None,
    reason)`` with reason one of ``missing`` / ``corrupt`` (torn JSON,
    checksum mismatch, truncated file) / ``version`` (written by a
    different ``SWEEP_VERSION``, including pre-envelope flat rows)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return None, "missing"
    except (ValueError, OSError, UnicodeDecodeError):
        return None, "corrupt"
    if not isinstance(doc, dict):
        return None, "corrupt"
    if doc.get("_v") != SWEEP_VERSION:
        return None, "version"
    row = doc.get("row")
    if not isinstance(row, dict):
        return None, "corrupt"
    payload = json.dumps(row, sort_keys=True)
    if hashlib.sha256(payload.encode()).hexdigest() != doc.get("sha256"):
        return None, "corrupt"
    return row, "ok"


def _read_json(path: str) -> Optional[Dict]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def _write_json_atomic(path: str, doc: Dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, sort_keys=True)
    os.replace(tmp, path)


# -- retry / lease policy ----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ExecPolicy:
    """Knobs of the leased execution layer (env-overridable)."""

    max_attempts: int        # lease claims per cell before quarantine
    lease_ttl_s: float       # lease expiry for remote/unkillable owners
    backoff_base_s: float    # exponential backoff base between retries
    backoff_cap_s: float
    hb_timeout_s: float      # silent-worker termination threshold
    max_worker_restarts: int


def _env_num(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _exec_policy(max_attempts: Optional[int] = None,
                 lease_ttl_s: Optional[float] = None) -> _ExecPolicy:
    return _ExecPolicy(
        max_attempts=int(max_attempts if max_attempts is not None
                         else _env_num("REPRO_SWEEP_MAX_ATTEMPTS", 4)),
        lease_ttl_s=float(lease_ttl_s if lease_ttl_s is not None
                          else _env_num("REPRO_SWEEP_LEASE_TTL", 300.0)),
        backoff_base_s=_env_num("REPRO_SWEEP_BACKOFF", 0.25),
        backoff_cap_s=30.0,
        # must exceed the slowest single cell (learned training included):
        # a heartbeat is written per cell attempt, not mid-cell
        hb_timeout_s=_env_num("REPRO_SWEEP_HB_TIMEOUT", 900.0),
        max_worker_restarts=int(_env_num("REPRO_SWEEP_MAX_RESTARTS", 16)),
    )


def _backoff_s(pol: _ExecPolicy, attempt: int) -> float:
    return min(pol.backoff_cap_s,
               pol.backoff_base_s * (2 ** max(attempt - 1, 0)))


# -- attempts ledger + quarantine -------------------------------------------

def _bump_attempts(path: str, error: Optional[str] = None) -> int:
    """Record one more lease claim (or a failure message) for a cell.
    Only ever called while holding the cell's lease, so the
    read-modify-write is single-writer; the write itself is atomic."""
    apath = path + ".attempts"
    doc = _read_json(apath) or {}
    doc["attempts"] = int(doc.get("attempts", 0)) + (0 if error else 1)
    errors = doc.get("errors")
    doc["errors"] = list(errors) if isinstance(errors, list) else []
    if error:
        doc["errors"].append(error)
    _write_json_atomic(apath, doc)
    return doc["attempts"]


def _quarantine_stub(cell: SweepCell, qdoc: Dict) -> Dict:
    """The placeholder row a quarantined cell contributes: the cell's
    identity columns, every stat None, and ``quarantined=True`` — the
    grid completes, but a quarantined cell can never read as covered."""
    row = cell.to_dict()
    row.pop("service_steps", None)
    for f in ROW_FIELDS:
        row.setdefault(f, None)
    row["retries"] = max(int(qdoc.get("attempts", 0)) - 1, 0)
    row["quarantined"] = True
    return row


def _attempt_cell(cell: SweepCell, out_dir: str,
                  cache_dir: Optional[str],
                  pol: _ExecPolicy) -> Tuple[str, Optional[Dict]]:
    """One non-blocking leased attempt at a cell.

    Returns ``(status, payload)``: ``("done", row)`` (computed now or
    found persisted), ``("quarantined", stub_row)``, ``("busy", None)``
    (a live owner holds the lease), or ``("retry", attempt_no)`` after a
    failure this process should back off from.  Crash-safe at every
    point: a SIGKILL leaves at most a stale lease (reclaimed via the
    dead-pid check) and a counted attempt."""
    path = _cell_path(out_dir, cell)
    row, reason = load_cell_row(path)
    if row is not None:
        return "done", row
    if reason in ("corrupt", "version"):
        quarantine_artifact(path, f"invalid persisted cell "
                            f"{cell.bench}/{cell.prefetcher} ({reason})")
    qdoc = _read_json(path + ".quarantine")
    if qdoc is not None:
        return "quarantined", _quarantine_stub(cell, qdoc)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    lease = path + ".lease"
    if not ft.try_acquire_lease(lease, pol.lease_ttl_s,
                                extra={"cell": cell.key()}):
        return "busy", None
    att = 0
    try:
        spent = int((_read_json(path + ".attempts") or {})
                    .get("attempts", 0))
        if spent >= pol.max_attempts:
            qdoc = _read_json(path + ".attempts") or {}
            qdoc.update(key=cell.key(), cell=cell.to_dict())
            _write_json_atomic(path + ".quarantine", qdoc)
            warnings.warn(
                f"cell {cell.bench}/{cell.prefetcher} "
                f"(eviction={cell.eviction}, frac={cell.device_frac}) "
                f"quarantined after {spent} attempts: "
                f"{qdoc.get('errors') or 'worker crashes'}",
                RuntimeWarning)
            return "quarantined", _quarantine_stub(cell, qdoc)
        att = _bump_attempts(path)
        faults.fire("cell.start", cell.key())
        row = simulate_cell(cell, cache_dir=cache_dir)
        row["retries"] = att - 1
        write_cell_row(path, row)
        return "done", row
    except Exception as e:
        _bump_attempts(path, error=repr(e))
        return "retry", att
    finally:
        ft.release_lease(lease)


def _run_cell_leased(i: int, cell: SweepCell, out_dir: str,
                     cache_dir: Optional[str],
                     pol: _ExecPolicy) -> Tuple[str, Dict]:
    """Drive one cell to resolution (result or quarantine), blocking
    through retries/backoff and foreign leases."""
    while True:
        status, payload = _attempt_cell(cell, out_dir, cache_dir, pol)
        if status in ("done", "quarantined"):
            return status, payload
        if status == "retry":
            time.sleep(_backoff_s(pol, payload))
        else:                                  # busy: foreign live owner
            time.sleep(min(0.2, max(pol.lease_ttl_s / 10, 0.01)))


# -- the lease worker pool ---------------------------------------------------

def _heartbeat(hb_dir: str, wid: int, done_n: int) -> None:
    try:
        _write_json_atomic(os.path.join(hb_dir, f"w{wid}.json"),
                           {"ts": time.time(), "pid": os.getpid(),
                            "done": done_n})
    except OSError:  # pragma: no cover - hb dir vanished
        pass


def _lease_worker_main(sys_path: List[str], cells: List[SweepCell],
                       out_dir: str, cache_dir: Optional[str],
                       pol: _ExecPolicy, wid: int, hb_dir: str) -> None:
    """A lease worker: loops over the whole grid claiming unleased,
    unfinished cells until every cell is resolved.  Any worker can run
    any cell, so crashed or slow peers never strand work; the rotated
    start offset keeps workers from contending on the same cells."""
    _init_worker(sys_path)
    n = len(cells)
    done = [False] * n
    rot = wid % max(n, 1)
    order = list(range(rot, n)) + list(range(rot))
    while not all(done):
        progressed = False
        for j in order:
            if done[j]:
                continue
            faults.fire("worker.loop", f"w{wid}")
            status, payload = _attempt_cell(cells[j], out_dir, cache_dir,
                                            pol)
            if status in ("done", "quarantined"):
                done[j] = True
                progressed = True
            elif status == "retry":
                progressed = True
                time.sleep(_backoff_s(pol, payload))
            _heartbeat(hb_dir, wid, sum(done))
        if not progressed:
            time.sleep(0.05)


def _mp_context():
    """fork is the cheap default, but forking a jax/XLA-initialized
    parent (e.g. benchmarks.run after training suites) inherits its
    thread/mutex state and can deadlock — use spawn in that case, unless
    __main__ is not re-importable (stdin/-c scripts), which spawn cannot
    handle.  Cells are pure functions of their spec, so results match
    the serial path either way."""
    main_file = getattr(sys.modules.get("__main__"), "__file__", None)
    spawn_ok = main_file is None or os.path.exists(main_file)
    method = "spawn" if ("jax" in sys.modules and spawn_ok) else "fork"
    try:
        return multiprocessing.get_context(method)
    except ValueError:  # pragma: no cover - non-POSIX
        return multiprocessing.get_context("spawn")


def _lease_pool(cells: Sequence[SweepCell], pending: List[int],
                out_dir: str, cache_dir: Optional[str], workers: int,
                pol: _ExecPolicy, record, verbose: bool) -> None:
    """Supervise a pool of lease workers over the pending cells.

    The parent never computes; it collects finished cell files into
    ``record`` and runs the failure-detection loop: a
    :class:`~repro.distributed.fault_tolerance.HeartbeatMonitor` tracks
    per-worker heartbeats — dead workers (SIGKILL, crash) are restarted
    up to a budget, silent-but-alive workers are terminated so their
    leases free up via the dead-pid reclaim.  If every worker exhausts
    its restart budget, the parent finishes the remainder serially
    (attempts are bounded, so that terminates — in quarantine at worst).
    """
    sub = [cells[i] for i in pending]
    ctx = _mp_context()
    hb_dir = os.path.join(out_dir, "hb")
    os.makedirs(hb_dir, exist_ok=True)
    monitor = ft.HeartbeatMonitor(timeout_s=pol.hb_timeout_s)
    n_workers = min(workers, len(sub))

    def _spawn(wid: int):
        p = ctx.Process(target=_lease_worker_main,
                        args=(list(sys.path), sub, out_dir, cache_dir,
                              pol, wid, hb_dir),
                        daemon=True)
        p.start()
        # grace window until the first beat; heartbeat files carry
        # time.time() stamps, so the monitor must live in wall-clock time
        monitor.beat(wid, 0.0, now=time.time())
        return p

    procs = {wid: _spawn(wid) for wid in range(n_workers)}
    restarts = {wid: 0 for wid in procs}
    last_hb: Dict[int, float] = {}
    unresolved = set(pending)
    try:
        while unresolved:
            for i in sorted(unresolved):
                path = _cell_path(out_dir, cells[i])
                row, _reason = load_cell_row(path)
                if row is not None:
                    record(i, row, persist=False)
                    unresolved.discard(i)
                    continue
                qdoc = _read_json(path + ".quarantine")
                if qdoc is not None:
                    record(i, _quarantine_stub(cells[i], qdoc),
                           persist=False)
                    unresolved.discard(i)
            if not unresolved:
                break
            now = time.time()
            for wid, p in procs.items():
                hb = _read_json(os.path.join(hb_dir, f"w{wid}.json"))
                if hb and isinstance(hb.get("ts"), (int, float)):
                    ts = float(hb["ts"])
                    if last_hb.get(wid) != ts:
                        monitor.beat(wid, ts - last_hb.get(wid, ts),
                                     now=ts)
                        last_hb[wid] = ts
                if p.is_alive() and wid in monitor.dead_hosts(now=now):
                    if verbose:
                        print(f"[sweep] worker {wid} silent for "
                              f">{pol.hb_timeout_s}s; terminating so its "
                              "lease frees up", flush=True)
                    p.terminate()
                    p.join(timeout=5)
                if not p.is_alive() and restarts[wid] \
                        < pol.max_worker_restarts:
                    restarts[wid] += 1
                    if verbose:
                        print(f"[sweep] worker {wid} died; restart "
                              f"{restarts[wid]}/{pol.max_worker_restarts}",
                              flush=True)
                    procs[wid] = _spawn(wid)
            if all(not p.is_alive() for p in procs.values()):
                for i in sorted(unresolved):
                    status, row = _run_cell_leased(
                        i, cells[i], out_dir, cache_dir, pol)
                    record(i, row, persist=False)
                unresolved.clear()
                break
            time.sleep(0.05)
    finally:
        for p in procs.values():
            if p.is_alive():
                p.terminate()
            p.join(timeout=10)


# ---------------------------------------------------------------------------
# orchestration: lane-batch scheduling, fan-out, persistence, resume
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _packable_prefetcher_names() -> Tuple[str, ...]:
    """Cheap pre-filter vocabulary for the lane scheduler, derived from
    the pallas backend's own packable-prefetcher set so extending the
    backend with new families automatically widens the filter."""
    from repro.uvm.backends.pallas_backend import PACKABLE_PREFETCHERS
    return tuple(n for n, t in _PREFETCHER_TYPES.items()
                 if t in PACKABLE_PREFETCHERS)


@functools.lru_cache(maxsize=1)
def _family_of_prefetcher_name() -> Dict[str, str]:
    """Lane-family kind per cell-spec prefetcher name, derived from the
    pallas backend's own type map so a new packable family automatically
    gets grouped by the scheduler (lane batches are family-homogeneous:
    processing cells family-by-family packs full batches instead of
    flushing a half-filled one at every family change)."""
    from repro.uvm.backends.pallas_backend import FAMILY_BY_TYPE
    return {n: FAMILY_BY_TYPE[t] for n, t in _PREFETCHER_TYPES.items()
            if t in FAMILY_BY_TYPE}


#: the lane scheduler's pipeline (:func:`_run_lane_batches`): threads of
#: the prepare pool, how many cells ahead of the batcher it prepares, and
#: how many lane batches replay at once on the flush pool
PREP_THREADS = 4
PREP_WINDOW = 32
FLUSH_THREADS = 2


def _wants_lanes(cell: SweepCell) -> bool:
    """True when this cell's backend chain starts at the pallas lanes (an
    explicit ``backend="pallas"`` or ``auto`` on an accelerator host) and
    its prefetcher can be packed at all — anything else skips
    trace/prefetcher preparation and goes straight to the per-cell path."""
    return (cell.prefetcher in _packable_prefetcher_names()
            and backend_chain(cell.backend)[0] == "pallas")


def _run_lane_batches(cells: Sequence[SweepCell],
                      cache_dir: Optional[str],
                      verbose: bool = False) -> Dict[int, Dict]:
    """Replay the pallas-eligible subset of ``cells`` as multi-lane batches.

    Returns ``{position: row}`` for every cell that was packed into a
    lane.  Cells are visited family-by-family (lane batches must be
    family-homogeneous — ``fits_batch`` refuses to co-bucket two
    prefetcher families, so interleaved families would flush half-empty
    batches).

    Execution is a **pipeline** of overlapping stages (diagrammed in
    ``repro/uvm/backends/README.md``, "Sweep pipeline"):

    * *prepare* — trace generation/deserialization and predcache
      inference run in a small thread pool a bounded lookahead window
      ahead of the batcher (``PREP_THREADS`` threads, ``PREP_WINDOW``
      cells ahead); the trace memo means co-scheduled
      cells sharing a trace resolve to one deserialize + one checksum.
    * *pack* — the main thread consumes prepared cells **in scheduler
      order** (results stay deterministic) and packs lanes under
      ``fits_batch``'s budgets, exactly as before.
    * *flush* — each full batch replays on a small flush pool while the
      main thread packs the next one.  At most ``FLUSH_THREADS``
      batches (2 — independent policy/family batches parallelize
      across cores, XLA releases the GIL) are in flight plus one being
      packed, so batch residency stays O(1) and the whole grid is never
      materialized — the bounded-memory property of the serial scheduler
      survives, shrunk further by the trace memo sharing Trace objects
      across lanes.

    Serve cells carry their decode-step bounds into the lane request, so
    the kernel emits per-step clocks in-band and the row's SLO columns
    are pure percentile math (``slo_source="kernel"``) — no NumPy
    side-pass replay unless ``REPRO_SERVE_CHECK=1`` asks for the
    differential check.

    Cells the backend declines (span too large, empty trace, ...) are
    left out of the result and flow back to the per-cell pool path,
    which keeps the ``--workers`` fan-out for them.  A runtime failure
    of a lane batch propagates out of the flush future and aborts the
    scheduler: its cells never replay on the host in its place.  For a
    ``TransientBackendFault`` that is the retry contract (crash the
    driver, retry on the same backend after resume).
    """
    from repro.uvm.backends.pallas_backend import _lane_shape, policy_label

    backend = get_backend("pallas")
    rows: Dict[int, Dict] = {}
    batch: List[int] = []
    requests: List[ReplayRequest] = []
    caps: List[Optional[int]] = []
    # (family, policy, length, span) per queued lane — the family element
    # makes fits_batch refuse to co-bucket families
    shapes: List[Tuple[str, str, int, int]] = []

    def _replay_batch_rows(n: int, b: List[int], reqs: List[ReplayRequest],
                           cps: List[Optional[int]],
                           shps: List[Tuple[str, str, int, int]]
                           ) -> Dict[int, Dict]:
        """Flush-stage body (runs on the flush thread): replay packed
        batch ``n`` and assemble its rows."""
        with obs.span("lane.batch", batch=n, family=shps[0][0],
                      policy=policy_label(sh[1] for sh in shps),
                      lanes=len(b),
                      accesses=sum(sh[2] for sh in shps)):
            t0 = time.time()
            stats = backend.replay(list(reqs))
            per_cell = (time.time() - t0) / len(b)
            out: Dict[int, Dict] = {}
            with obs.span("sweep.finish_rows", batch=n):
                for i, st, cap, req in zip(b, stats, cps, reqs):
                    row = _finish_row(cells[i], st, cap, per_cell)
                    if req.trace.meta and "serve" in req.trace.meta:
                        row.update(_serve_latency_row(
                            cells[i], req.trace, req.config, st, cache_dir))
                    elif _is_mt_trace(req.trace):
                        row.update(_mt_row(cells[i], req.trace, req.config,
                                           st, cap, cache_dir))
                    out[i] = row
            return out

    flush_pool = ThreadPoolExecutor(max_workers=FLUSH_THREADS)
    inflight: collections.deque = collections.deque()   # FIFO of futures
    n_flushed = 0                            # index of the batch being packed

    def _await_inflight(room: int = 0) -> None:
        """Drain flush futures (oldest first) until at most ``room`` are
        still in flight; re-raises their failures in the main thread."""
        if len(inflight) <= room:
            return
        with obs.span("sweep.await"):
            while len(inflight) > room:
                rows.update(inflight.popleft().result())

    def _flush() -> None:
        nonlocal n_flushed
        if not batch:
            return
        if verbose:
            print(f"[sweep] pallas lanes: replaying {len(batch)} cells "
                  "in one batch", flush=True)
        faults.fire("lane.flush", f"{len(batch)}:{cells[batch[0]].key()}")
        _await_inflight(room=FLUSH_THREADS - 1)  # bounded batches in flight
        inflight.append(flush_pool.submit(
            _replay_batch_rows, n_flushed, list(batch), list(requests),
            list(caps), list(shapes)))
        n_flushed += 1
        batch.clear()
        requests.clear()
        caps.clear()
        shapes.clear()

    families = _family_of_prefetcher_name()
    # family-major order: lane batches are family-homogeneous, so
    # interleaved families would flush half-filled batches; policy-major
    # within a family, so a batch holds as few eviction policies (and
    # victim keys) as its lanes allow
    order = sorted(range(len(cells)),
                   key=lambda i: (families.get(cells[i].prefetcher, "~"),
                                  cells[i].eviction, i))

    prep_pool = ThreadPoolExecutor(max_workers=PREP_THREADS)
    pending = collections.deque()            # (i, future) in scheduler order
    feed = iter(order)

    def _prepare(i: int):
        """Prepare-stage body (runs on the prep pool)."""
        with obs.span("sweep.prepare", cell=i):
            return prepare_cell(cells[i], cache_dir=cache_dir)

    def _top_up() -> None:
        while len(pending) < PREP_WINDOW:
            try:
                i = next(feed)
            except StopIteration:
                return
            pending.append((i, prep_pool.submit(_prepare, i)))

    try:
        _top_up()
        while pending:
            i, fut = pending.popleft()
            trace, config, prefetcher, pages = fut.result()
            with obs.span("sweep.pack", batch=n_flushed):
                _top_up()                    # keep the lookahead full
                req = ReplayRequest(trace, prefetcher, config,
                                    step_bounds=_step_bounds(trace))
                if not backend.can_replay(req):
                    continue                 # back to the per-cell pool path
                shape = _lane_shape(req)
                if not backend.fits_batch(shapes, shape):
                    _flush()
                batch.append(i)
                requests.append(req)
                caps.append(pages)
                shapes.append(shape)
        _flush()
        _await_inflight(room=0)
    finally:
        for _, fut in pending:
            fut.cancel()
        prep_pool.shutdown(wait=True)
        flush_pool.shutdown(wait=True)
    return rows


def run_sweep(cells: Sequence[SweepCell], *, out_dir: Optional[str] = None,
              workers: int = 1, resume: bool = True,
              cache_dir: Optional[str] = None,
              verbose: bool = False,
              write_aggregate: bool = True,
              max_attempts: Optional[int] = None,
              lease_ttl_s: Optional[float] = None) -> List[Dict]:
    """Run a grid of cells; returns rows in the order of ``cells``.

    With ``out_dir``, each completed cell is persisted under
    ``out_dir/cells/<key>.json`` as a checksummed envelope (and skipped on
    resume; a truncated/corrupt/cross-version cell file is quarantined to
    ``<key>.json.corrupt`` with a warning and the cell requeued), cells
    execute under crash-reclaimable leases with bounded retries (cells
    still failing after ``max_attempts`` lease claims land in
    ``out_dir/quarantine.json`` and contribute a ``quarantined=True`` stub
    row instead of aborting the grid), and aggregate ``results.json`` /
    ``results.csv`` are (re)written at the end.  Callers sharing one
    ``out_dir`` across several grids should pass ``write_aggregate=False``
    so the aggregate files never reflect a partial grid.
    """
    with obs.span("sweep.run", cells=len(cells)):
        if cache_dir is None and out_dir is not None:
            cache_dir = os.path.join(out_dir, "trace_cache")
        pol = _exec_policy(max_attempts, lease_ttl_s)
        rows: Dict[int, Dict] = {}
        pending: List[int] = []
        for i, cell in enumerate(cells):
            if out_dir:
                path = _cell_path(out_dir, cell)
                if resume:
                    row, reason = load_cell_row(path)
                    if row is not None:
                        rows[i] = row
                        continue
                    if reason in ("corrupt", "version"):
                        quarantine_artifact(
                            path, f"resume: invalid cell file for "
                            f"{cell.bench}/{cell.prefetcher} ({reason}); "
                            "requeueing")
                    qdoc = _read_json(path + ".quarantine")
                    if qdoc is not None:
                        rows[i] = _quarantine_stub(cell, qdoc)
                        continue
                else:
                    # a fresh (non-resumed) run must not inherit results,
                    # attempt counts, or quarantine verdicts from earlier
                    # runs — the leased executor would short-circuit on them
                    for suffix in ("", ".quarantine", ".attempts"):
                        try:
                            os.unlink(path + suffix)
                        except OSError:
                            pass
            pending.append(i)

        def _record(i: int, row: Dict, persist: bool = True) -> None:
            rows[i] = row
            if out_dir and persist:
                write_cell_row(_cell_path(out_dir, cells[i]), row)
            if verbose:
                if row.get("quarantined"):
                    print(f"[sweep] {row['bench']}/{row['prefetcher']}"
                          f" frac={row.get('device_frac')} QUARANTINED"
                          f" after {row.get('retries')} retries", flush=True)
                else:
                    print(f"[sweep] {row['bench']}/{row['prefetcher']}"
                          f" frac={row.get('device_frac')}"
                          f" backend={row.get('backend')}"
                          f" hit={row['hit_rate']:.3f} ipc={row['ipc']:.2f}"
                          f" ({row['seconds']:.2f}s)", flush=True)

        # lane-batch scheduler: pack pallas-bound cells into multi-lane kernel
        # launches in the parent process (they are already batched — worker
        # fan-out would only serialize them again); whatever the backend
        # declines falls back to the per-cell path below
        lane_pending = [i for i in pending if _wants_lanes(cells[i])]
        if lane_pending:
            lane_rows = _run_lane_batches([cells[i] for i in lane_pending],
                                          cache_dir, verbose=verbose)
            for j, row in lane_rows.items():
                _record(lane_pending[j], row)
            handled = {lane_pending[j] for j in lane_rows}
            pending = [i for i in pending if i not in handled]

        # one process holds the chip: learned cells (predictor training and
        # prediction) run here, before the fan-out; workers get the rest
        fan_out = ([i for i in pending if cells[i].prefetcher != "learned"]
                   if workers > 1 else [])
        for i in sorted(set(pending) - set(fan_out)):
            if out_dir:
                # leased execution: every cell resolves to a persisted result
                # or a quarantine verdict, whatever crashes along the way
                status, row = _run_cell_leased(i, cells[i], out_dir, cache_dir,
                                               pol)
                _record(i, row, persist=False)
            else:
                _record(i, simulate_cell(cells[i], cache_dir=cache_dir))
        if fan_out and out_dir:
            _lease_pool(cells, fan_out, out_dir, cache_dir, workers, pol,
                        _record, verbose)
        elif fan_out:
            ctx = _mp_context()
            with ctx.Pool(min(workers, len(fan_out)), initializer=_init_worker,
                          initargs=(list(sys.path),)) as pool:
                args = [(cells[i], cache_dir) for i in fan_out]
                for i, row in zip(fan_out, pool.imap(_worker, args)):
                    _record(i, row)

        out = [rows[i] for i in range(len(cells))]
        if out_dir and write_aggregate:
            write_results(out, out_dir)
            _write_json_atomic(
                os.path.join(out_dir, "quarantine.json"),
                {"cells": [q for q in
                           (_read_json(_cell_path(out_dir, c) + ".quarantine")
                            for c in cells) if q is not None]})
        obs.sample_rss()
    return out


# ---------------------------------------------------------------------------
# structured results
# ---------------------------------------------------------------------------

def write_results(rows: List[Dict], out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "results.json"), "w") as f:
        json.dump({"rows": rows}, f, indent=1, sort_keys=True)
        f.write("\n")
    with open(os.path.join(out_dir, "results.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=ROW_FIELDS, extrasaction="ignore")
        w.writeheader()
        for row in rows:
            w.writerow(row)


def read_results(out_dir: str) -> List[Dict]:
    """Read the aggregate rows.  A missing or corrupt aggregate falls
    back to scanning the per-cell store (checksum-valid, current-version
    cells only) with a warning, so one torn ``results.json`` never loses
    a finished grid."""
    try:
        with open(os.path.join(out_dir, "results.json")) as f:
            doc = json.load(f)
        rows = doc["rows"]
        if not isinstance(rows, list):
            raise ValueError("aggregate rows is not a list")
        return rows
    except (OSError, ValueError, KeyError, TypeError) as e:
        cell_dir = os.path.join(out_dir, "cells")
        if not os.path.isdir(cell_dir):
            raise
        warnings.warn(f"aggregate results.json unreadable ({e!r}); "
                      "rebuilding from the per-cell store", RuntimeWarning)
        rows = []
        for fname in sorted(os.listdir(cell_dir)):
            if not fname.endswith(".json"):
                continue
            row, reason = load_cell_row(os.path.join(cell_dir, fname))
            if row is not None:
                rows.append(row)
        return rows


def read_results_csv(path: str) -> List[Dict]:
    """CSV round-trip: numeric columns come back as numbers."""
    out = []
    with open(path, newline="") as f:
        for row in csv.DictReader(f):
            parsed: Dict = {}
            for k, v in row.items():
                if v == "" or v == "None":
                    parsed[k] = None
                    continue
                if v in ("True", "False"):
                    parsed[k] = v == "True"
                    continue
                try:
                    fv = float(v)
                    parsed[k] = int(fv) if fv.is_integer() and "." not in v \
                        else fv
                except ValueError:
                    parsed[k] = v
            out.append(parsed)
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Batched UVM sweep: (trace x prefetcher x config) grid")
    ap.add_argument("--benches", default="ATAX,BICG,Pathfinder,Hotspot")
    ap.add_argument("--prefetchers", default="none,tree,oracle",
                    help=f"comma list from {','.join(PREFETCHERS)}")
    ap.add_argument("--scales", default="1.0")
    ap.add_argument("--windows", default="0.6")
    ap.add_argument("--prediction-us", default="1.0")
    ap.add_argument("--device-fracs", default="",
                    help="e.g. '0.5,0.75' (empty = no oversubscription)")
    ap.add_argument("--capacity-splits", default="",
                    help="multi-tenant capacity splits for '<A>+<B>' "
                         "benches, e.g. 'shared,0.5/0.5,0.4/0.4' "
                         "(empty = shared capacity)")
    ap.add_argument("--evictions", default="lru",
                    help="eviction policies under oversubscription, comma "
                         f"list from {','.join(EVICTION_POLICIES)} or "
                         f"'{adaptive.ADAPTIVE_POLICY}' (resolved per cell "
                         "at prepare time; rows record the concrete policy)")
    ap.add_argument("--model-families", default="simplified",
                    help="predictor families for learned cells, comma list "
                         f"from {','.join(MODEL_FAMILIES)}")
    ap.add_argument("--scenario", default=None,
                    help="expand a named scenario from "
                         "repro.uvm.scenarios (e.g. 'oversub-full': the "
                         "full 11-benchmark x ratio x eviction-policy x "
                         "prefetcher matrix) instead of the grid flags; "
                         "--backend/--out/--workers still apply "
                         "and completed cells resume as usual")
    ap.add_argument("--backend", default=None, choices=list(BACKENDS),
                    help="replay backend: numpy, pallas (multi-lane "
                         "kernel batches), legacy (the reference loop), "
                         "or auto (pallas on a TPU, numpy otherwise); "
                         "defaults to $REPRO_SWEEP_BACKEND or auto")
    ap.add_argument("--out", default=None, help="results directory")
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args(argv)

    from repro.traces.generators import BENCHMARKS
    backend = args.backend or os.environ.get("REPRO_SWEEP_BACKEND", "auto")
    if backend not in BACKENDS:
        ap.error(f"unknown backend {backend!r}; "
                 f"choose from {','.join(BACKENDS)}")
    if args.scenario:
        from repro.uvm.scenarios import available_scenarios, expand_scenario
        try:
            cells = expand_scenario(args.scenario, backend=backend)
        except KeyError:
            ap.error(f"unknown scenario {args.scenario!r}; choose from "
                     f"{','.join(available_scenarios())}")
        print(f"[sweep] scenario {args.scenario!r}: {len(cells)} cells")
    else:
        benches = args.benches.split(",")
        pfs = args.prefetchers.split(",")
        bad = [p for p in pfs if p not in PREFETCHERS]
        if bad:
            ap.error(f"unknown prefetcher(s) {','.join(bad)}; "
                     f"choose from {','.join(PREFETCHERS)}")
        from repro.offload.serve_trace import SERVE_WORKLOADS, is_serve_bench
        from repro.traces.interleave import is_mt_bench
        bad = [b for b in benches
               if b not in BENCHMARKS and not is_serve_bench(b)
               and not is_mt_bench(b)]
        if bad:
            ap.error(f"unknown benchmark(s) {','.join(bad)}; "
                     f"choose from {','.join(sorted(BENCHMARKS))}, "
                     "multi-tenant pairs like ATAX+Pathfinder, or serve "
                     f"workloads {','.join(sorted(SERVE_WORKLOADS))} "
                     "(rate variants like ServeBursty@r128 accepted)")
        splits: List[Optional[str]] = [None]
        if args.capacity_splits:
            splits = list(args.capacity_splits.split(","))
            for s in splits:
                try:
                    parse_capacity_split(s)
                except ValueError as e:
                    ap.error(str(e))
            mt_less = [b for b in benches if not is_mt_bench(b)]
            if mt_less and any(parse_capacity_split(s) for s in splits):
                ap.error(f"--capacity-splits needs multi-tenant benches; "
                         f"{','.join(mt_less)} are single-tenant")
        evictions = args.evictions.split(",")
        ev_vocab = EVICTION_POLICIES + (adaptive.ADAPTIVE_POLICY,)
        bad = [e for e in evictions if e not in ev_vocab]
        if bad:
            ap.error(f"unknown eviction policy(ies) {','.join(bad)}; "
                     f"choose from {','.join(ev_vocab)}")
        model_families = args.model_families.split(",")
        bad = [m for m in model_families if m not in MODEL_FAMILIES]
        if bad:
            ap.error(f"unknown model family(ies) {','.join(bad)}; "
                     f"choose from {','.join(MODEL_FAMILIES)}")
        fracs: List[Optional[float]] = [None]
        if args.device_fracs:
            fracs += [float(x) for x in args.device_fracs.split(",")]
        cells = expand_grid(
            benches, pfs,
            scales=[float(x) for x in args.scales.split(",")],
            windows=[None if x == "full" else float(x)
                     for x in args.windows.split(",")],
            prediction_us=[float(x) for x in args.prediction_us.split(",")],
            device_fracs=fracs, evictions=evictions,
            model_families=model_families, capacity_splits=splits,
            backend=backend)
    t0 = time.time()
    rows = run_sweep(cells, out_dir=args.out, workers=args.workers,
                     resume=not args.no_resume, verbose=True)
    dt = time.time() - t0
    n_quar = sum(1 for r in rows if r.get("quarantined"))
    print(f"\n{len(rows)} cells in {dt:.1f}s "
          f"({sum(r['n_accesses'] or 0 for r in rows) / max(dt, 1e-9) / 1e6:.2f}"
          " M accesses/s aggregate)"
          + (f" [{n_quar} QUARANTINED - see quarantine.json]"
             if n_quar else ""))
    cols = ["bench", "prefetcher", "device_frac", "eviction", "backend",
            "hit_rate", "ipc", "unity"]
    print(",".join(cols))
    for r in rows:
        print(",".join(f"{r[c]:.4f}" if isinstance(r[c], float) else str(r[c])
                       for c in cols))
    if n_quar:
        raise SystemExit(f"{n_quar} of {len(rows)} cells quarantined")


if __name__ == "__main__":
    main()
