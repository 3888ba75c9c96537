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
  IPC, PCIe bytes and the rates derived from them) over every row;
* the numbers a prefetcher family reports of what the program produced
  in a grid (``CHECKS`` of its module, ``bench/reference/family.py``),
  summed or taken at their maximum over the grids, as the family says.

A family whose predictor the program trains inside the grid
(``TRAINED``) is replayed with the record of what the chip trained in
that grid for the row's ``model_family``; a row without one is a
mismatch.

The control is the reference put in the program's place one step below
the precision the configuration states: the replay with a float32
timing state instead of float64 (``precise=False``), and each family's
own control (``control_checks``).  It must fail these limits.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench.reference import family
from bench.reference import replay as ref_replay
from bench.reference import tracegen

#: the numbers every cell compares, and how they merge over traces
BASE_CHECKS = {"trace_records_differ": "sum", "int_mismatches": "sum",
               "float_rel_gap": "max"}


def check_kinds(prefetchers) -> Dict[str, str]:
    """Every number a grid of ``prefetchers`` can be held to, with
    ``"sum"`` or ``"max"``: the replay's and each family's own."""
    kinds = dict(BASE_CHECKS)
    for name in sorted(set(prefetchers)):
        kinds.update(getattr(family.load(name), "CHECKS", {}))
    return kinds


def merge(checks: Dict, got: Dict, kinds: Dict[str, str]) -> None:
    """Add ``got`` into ``checks``, each number by its kind."""
    for k, v in got.items():
        checks[k] = max(checks[k], v) if kinds[k] == "max" else checks[k] + v


def _row_cell(c, trained) -> Dict:
    """Sweep cell ``c`` as the reference replays it: a trained family's
    row carries the record of its ``model_family`` (None: none came)."""
    cell = dataclasses.asdict(c)
    if family.trained(c.prefetcher):
        cell["trained"] = (trained or {}).get(c.model_family)
    return cell


def _no_record(cell: Dict) -> bool:
    return cell.get("trained", True) is None


def reference_rows(tr, sweep_cells, precise: bool = True,
                   trained=None) -> List[Optional[Dict]]:
    """One reference row per sweep cell on the reference trace ``tr``;
    None for a trained family's row without a record."""
    cells = [_row_cell(c, trained) for c in sweep_cells]
    return [None if _no_record(cell)
            else ref_replay.replay(tr, cell, precise=precise)
            for cell in cells]


def row_checks(grids: List[List[Dict]], ref: List[Optional[Dict]]) -> Dict:
    """``int_mismatches`` and ``float_rel_gap`` of every row of every
    grid against the reference row of its sweep cell.  A grid that
    returned more or fewer rows than it has sweep cells counts each
    missing or extra row as a mismatch, and so does a row that has no
    reference row."""
    mism = 0
    gap = 0.0
    for rows in grids:
        mism += abs(len(rows) - len(ref))
        for row, want in zip(rows, ref):
            if want is None:
                mism += 1
                continue
            mism += sum(1 for f in ref_replay.EXACT_FIELDS
                        if row.get(f) != want[f])
            for f in ref_replay.FLOAT_FIELDS:
                gap = max(gap, ref_replay.rel_gap(row.get(f), want[f]))
    return {"int_mismatches": mism, "float_rel_gap": gap}


def family_checks(tr, sweep_cells, trained, kinds, control=False) -> Dict:
    """The numbers the families of ``sweep_cells`` report
    (``control_checks`` with ``control``), merged by ``kinds``.  A family
    reports once for each thing it produced in the grid: a trained
    family once for each ``model_family`` that has a record, given the
    sweep cells that record served, and any other once, given its
    cells."""
    out = {k: 0 for k in kinds if k not in BASE_CHECKS}
    groups: Dict = {}
    for c in sweep_cells:
        fam = c.model_family if family.trained(c.prefetcher) else None
        groups.setdefault((c.prefetcher, fam), []).append(
            dataclasses.asdict(c))
    for (name, fam), cells in groups.items():
        fn = getattr(family.load(name),
                     "control_checks" if control else "checks", None)
        produced = None if fam is None else (trained or {}).get(fam)
        if fn is None or (fam is not None and produced is None):
            continue
        merge(out, fn(tr, cells, produced), kinds)
    return out


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
    ``(trace seed, rows, trained)`` of each grid the window ran, with
    ``trained`` the records of what the chip trained there, by
    ``model_family``."""
    kinds = check_kinds(c.prefetcher for cells in sweeps.values()
                        for c in cells)
    checks = {k: 0.0 if how == "max" else 0 for k, how in kinds.items()}
    for ts, program_trace in program_traces.items():
        tr = tracegen.build_trace(config, ts)
        merge(checks, trace_checks(program_trace, tr), kinds)
        shared = None           # the rows of a grid without records
        for s, rows, trained in grids:
            if s != ts:
                continue
            if trained:
                ref = reference_rows(tr, sweeps[ts], trained=trained)
            else:
                shared = shared or reference_rows(tr, sweeps[ts])
                ref = shared
            merge(checks, row_checks([rows], ref), kinds)
            merge(checks, family_checks(tr, sweeps[ts], trained, kinds),
                  kinds)
    return checks


def control_checks(config: Dict, seed: int, sweep_cells,
                   trained=None) -> Dict:
    """The same numbers with the control in the program's place: the
    reference's rows replayed with a float32 timing state, and each
    family's own control; a trained family's rows take the records of
    what the chip trained on trace ``seed``."""
    kinds = check_kinds(c.prefetcher for c in sweep_cells)
    tr = tracegen.build_trace(config, seed)
    checks = trace_checks(tr, tr)
    checks.update(row_checks(
        [reference_rows(tr, sweep_cells, precise=False, trained=trained)],
        reference_rows(tr, sweep_cells, trained=trained)))
    checks.update(family_checks(tr, sweep_cells, trained, kinds,
                                control=True))
    return checks
