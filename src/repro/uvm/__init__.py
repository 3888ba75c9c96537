"""UVM substrate: page-granular CPU-GPU unified-virtual-memory simulation.

Implements on-demand page migration with far-faults, a PCIe interconnect
queue, the CUDA-driver tree-based neighborhood prefetcher (the UVMSmart
baseline), delayed migration / zero-copy policies, pluggable eviction
under oversubscription (LRU / counter-based random / access-frequency
hot-cold, see ``repro.uvm.eviction``), and the paper's evaluation metrics
(page hit rate, PCIe traffic, prefetcher accuracy/coverage, Unity).
``repro.uvm.scenarios`` holds the declarative oversubscription scenario
matrix (benchmark × capacity ratio × eviction policy × prefetcher;
``python -m repro.uvm.sweep --scenario oversub-full``).

Backend-pluggable replay core
-----------------------------
The replay stack has three layers (see ``repro.uvm.backends/README.md``):

* ``repro.uvm.replay_core`` — the backend-agnostic chunked state machine
  (pure array program) and the narrow ``ReplayBackend`` interface.
* ``repro.uvm.backends`` — ``legacy`` (the reference per-access Python
  loop, accepts anything), ``numpy`` (NumPy-chunked replay,
  **bit-identical** to the reference), and ``pallas`` (jax_pallas
  multi-lane kernel packing many cells into one accelerator launch;
  integer counters exact, floats within the golden tolerance).  All
  backends are pinned by ``tests/test_uvm_golden.py`` against recorded
  fixtures (regenerate after an intentional timing-model change with
  ``PYTHONPATH=src python scripts/regen_uvm_golden.py``).
* the scheduler in ``repro.uvm.sweep`` — groups packable sweep cells into
  lane batches, dispatches to the selected backend
  (``--backend {numpy,pallas,auto}``), falls back per cell to the NumPy
  path for anything unpackable, and records the backend that actually
  ran in every result row.

``UVMSimulator`` is the reference loop; ``VectorizedUVMSimulator`` is a
drop-in equivalent on the numpy backend; ``simulate(trace, prefetcher,
config, engine=..., backend=...)`` picks both per cell.

Batched sweeps
--------------
``repro.uvm.sweep`` runs (trace × prefetcher × config) grids in one call::

    from repro.uvm.sweep import SweepCell, expand_grid, run_sweep
    cells = expand_grid(["ATAX", "Pathfinder"], ["none", "tree", "oracle"],
                        device_fracs=[None, 0.5])
    rows = run_sweep(cells, out_dir="results/", workers=8)

Traces are generated once and cached on disk; each completed cell is
persisted under ``out_dir/cells/`` so an interrupted sweep resumes where it
stopped; aggregate results are written as both JSON and CSV.  The CLI wraps
the same API: ``PYTHONPATH=src python -m repro.uvm.sweep --help``.

Learned cells are train-once: ``repro.uvm.predcache`` content-addresses the
predictor's ``predict_trace`` arrays by (trace content, model config), so a
(trace × prediction_us × device_frac) grid trains one model per trace and
every variant — in-process, across concurrent sweeps (atomic
write-rename + training lock), and across runs — reuses the cached array.
All device work (lane batches, predictor training and prediction) runs
in the process that calls ``run_sweep``, before any ``--workers``
fan-out: one process holds the chip.
"""
from repro.uvm.config import UVMConfig
from repro.uvm.engine import VectorizedUVMSimulator, simulate
from repro.uvm.eviction import EVICTION_POLICIES
from repro.uvm.metrics import unity
from repro.uvm.replay_core import (ReplayBackend, ReplayRequest,
                                   available_backends, get_backend)
from repro.uvm.prefetchers import (
    NoPrefetcher, TreePrefetcher, LearnedPrefetcher, OraclePrefetcher,
    Prefetcher,
)
from repro.uvm.simulator import UVMSimulator, UVMStats

__all__ = [
    "UVMConfig", "UVMSimulator", "UVMStats", "VectorizedUVMSimulator",
    "simulate", "unity", "EVICTION_POLICIES",
    "ReplayBackend", "ReplayRequest", "available_backends", "get_backend",
    "Prefetcher", "NoPrefetcher", "TreePrefetcher", "LearnedPrefetcher",
    "OraclePrefetcher",
]
