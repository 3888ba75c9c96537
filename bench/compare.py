"""The comparison that decides ``correct``.

The plain reference (``bench/reference``) builds the cell's trace from
the seed on its own and replays every sweep cell of the grid with the
per-access loop.  Every row the window produced is then held against
the reference row of its sweep cell:

* ``trace_records_differ``: access records of the program's trace that
  differ from the reference trace (the trace build layer), exact;
* ``int_mismatches``: integer counters of the window's rows that differ
  from the reference (hits, faults, migrations, evictions, ...), and
  the row's prefetcher and eviction policy, exact;
* ``float_rel_gap``: the widest relative gap of a float column (cycles,
  IPC, PCIe bytes and the rates derived from them) over every row.

The control is the reference put in the program's place one step below
the precision the configuration states: the replay with a float32
timing state instead of float64 (``precise=False``).  It must fail these
limits.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench.reference import replay as ref_replay
from bench.reference import tracegen


def reference_rows(tr, sweep_cells, precise: bool = True) -> List[Dict]:
    """One reference row per sweep cell on the reference trace ``tr``."""
    return [ref_replay.replay(tr, dataclasses.asdict(c), precise=precise)
            for c in sweep_cells]


def row_checks(grids: List[List[Dict]], ref: List[Dict]) -> Dict:
    """``int_mismatches`` and ``float_rel_gap`` of every row of every
    grid against the reference row of its sweep cell.  A grid that
    returned more or fewer rows than it has sweep cells counts each
    missing or extra row as a mismatch."""
    mism = 0
    gap = 0.0
    for rows in grids:
        mism += abs(len(rows) - len(ref))
        for row, want in zip(rows, ref):
            mism += sum(1 for f in ref_replay.EXACT_FIELDS
                        if row.get(f) != want[f])
            for f in ref_replay.FLOAT_FIELDS:
                gap = max(gap, ref_replay.rel_gap(row.get(f), want[f]))
    return {"int_mismatches": mism, "float_rel_gap": gap}


def trace_checks(program_trace, ref) -> Dict:
    got = np.asarray(program_trace.accesses)
    n = min(len(got), len(ref.accesses))
    bad = np.zeros(n, dtype=bool)
    for name in ref.accesses.dtype.names:
        bad |= (np.asarray(got[name][:n], dtype=np.int64)
                != np.asarray(ref.accesses[name][:n], dtype=np.int64))
    differ = int(np.count_nonzero(bad)) + abs(len(got) - len(ref.accesses))
    return {"trace_records_differ": differ}


def check_window(config: Dict, program_traces: Dict, grids: List,
                 sweeps: Dict) -> Dict:
    """Every number the cell compares, by name, over every trace the
    window replayed: ``program_traces`` and ``sweeps`` map a trace seed
    to the program's trace and to the grid's sweep cells, ``grids`` holds
    ``(trace seed, rows)`` of each grid the window ran."""
    checks = {"trace_records_differ": 0, "int_mismatches": 0,
              "float_rel_gap": 0.0}
    for ts, program_trace in program_traces.items():
        tr = tracegen.build_trace(config, ts)
        got = trace_checks(program_trace, tr)
        got.update(row_checks([rows for s, rows in grids if s == ts],
                              reference_rows(tr, sweeps[ts])))
        checks["trace_records_differ"] += got["trace_records_differ"]
        checks["int_mismatches"] += got["int_mismatches"]
        checks["float_rel_gap"] = max(checks["float_rel_gap"],
                                      got["float_rel_gap"])
    return checks


def control_checks(config: Dict, seed: int, sweep_cells) -> Dict:
    """The same numbers with the control in the program's place: the
    reference's rows replayed with a float32 timing state."""
    tr = tracegen.build_trace(config, seed)
    checks = trace_checks(tr, tr)
    checks.update(row_checks(
        [reference_rows(tr, sweep_cells, precise=False)],
        reference_rows(tr, sweep_cells)))
    return checks
