"""Adaptive eviction: the ``adaptive`` pseudo-policy and its resolvers.

The intelligent-oversubscription framework (arXiv 2204.02974 — the same
place this repo's ``hotcold`` policy comes from) observes that no single
eviction policy wins across benchmarks: access patterns decide whether
recency (``lru``), randomization (``random``), or hotness segregation
(``hotcold``) keeps the right pages resident.  This module makes that a
sweepable axis: grids and scenarios may request ``eviction="adaptive"``,
and the sweep resolves it to a *concrete* policy per cell at prepare
time — the result row records the resolved policy in its ``eviction``
column (never the literal ``adaptive``), so downstream consumers see
exactly what replayed and each lane runs under a concrete policy.

Resolution order:

1. **Selector table** (``REPRO_ADAPTIVE_TABLE``: path to a JSON
   ``{bench: policy}`` mapping, e.g. distilled from a previous scenario
   matrix via :func:`selector_from_rows`) — the "pick the policy per
   benchmark from scenario-matrix results" path.
2. **Probe replay**: with no table entry, a short replay of the cell's
   own trace prefix under every policy (NumPy backend, capacity scaled
   to preserve the cell's oversubscription ratio) picks the
   cheapest-in-cycles policy.  The probe runs under a cheap *proxy* of
   the cell's prefetcher family — demand paging for ``none``, the real
   block/tree prefetchers for theirs, and an oracle over the prefix for
   ``oracle`` **and** ``learned`` (training a predictor inside a probe
   would cost more than the cell) — because the best policy depends on
   which pages prefetching keeps warm, not just the demand stream.
   Deterministic, memoized per (trace content, device capacity, proxy
   family), and cheap relative to a full cell replay.
3. **No eviction pressure** (capacity absent or >= working set): every
   policy is a no-op, resolve to the canonical first policy (``lru``).
"""
from __future__ import annotations

import json
import os
import threading
from typing import Dict, Iterable, Optional, Tuple

from repro.uvm.eviction import EVICTION_POLICIES, validate_policy

#: the pseudo-policy name accepted by sweep grids and scenarios
ADAPTIVE_POLICY = "adaptive"

#: accesses replayed per policy by the probe resolver
PROBE_ACCESSES = 20000

_MEMO: Dict[Tuple, str] = {}
_MEMO_LOCK = threading.Lock()


def is_adaptive(policy: Optional[str]) -> bool:
    return policy == ADAPTIVE_POLICY


def clear_memo() -> None:
    """Drop the probe memo and the parsed-table cache (tests)."""
    with _MEMO_LOCK:
        _MEMO.clear()
        _TABLE_CACHE.clear()


def selector_from_rows(rows: Iterable[Dict]) -> Dict[str, str]:
    """Distill sweep/scenario result rows into a ``{bench: policy}``
    selector: per benchmark, the concrete policy with the lowest mean
    ``cycles`` across its rows (ties break in ``EVICTION_POLICIES``
    order).  Feed the output to ``REPRO_ADAPTIVE_TABLE`` (as JSON) to
    pin later adaptive sweeps to matrix-derived choices."""
    sums: Dict[Tuple[str, str], Tuple[int, int]] = {}
    for row in rows:
        pol = row.get("eviction")
        if pol not in EVICTION_POLICIES or row.get("cycles") is None:
            continue
        k = (row["bench"], pol)
        total, n = sums.get(k, (0, 0))
        sums[k] = (total + int(row["cycles"]), n + 1)
    out: Dict[str, str] = {}
    for bench in sorted({b for b, _ in sums}):
        scored = [(sums[(bench, p)][0] / sums[(bench, p)][1], i, p)
                  for i, p in enumerate(EVICTION_POLICIES)
                  if (bench, p) in sums]
        out[bench] = min(scored)[2]
    return out


#: parsed selector tables keyed by (path, mtime_ns): the sweep's prepare
#: stage resolves a cell per *thread*, and re-reading + re-parsing the
#: JSON once per cell turned the table lookup into a hot stat+parse loop
#: on large grids — the cache re-reads only when the file actually
#: changes on disk
_TABLE_CACHE: Dict[Tuple[str, int], Dict[str, str]] = {}


def _table() -> Dict[str, str]:
    path = os.environ.get("REPRO_ADAPTIVE_TABLE")
    if not path:
        return {}
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError as e:
        raise FileNotFoundError(
            f"REPRO_ADAPTIVE_TABLE points at an unreadable selector "
            f"table {path!r} ({e}); unset the variable or fix the path "
            "(the table format is the JSON written by "
            "'python -m repro.uvm.adaptive')") from e
    key = (path, mtime)
    with _MEMO_LOCK:
        hit = _TABLE_CACHE.get(key)
    if hit is not None:
        return hit
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("selector"), dict):
        doc = doc["selector"]
    table = {str(b): validate_policy(p) for b, p in doc.items()}
    with _MEMO_LOCK:
        _TABLE_CACHE.clear()          # one live table at a time
        _TABLE_CACHE[key] = table
    return table


#: probe prefetcher proxy per cell prefetcher family — ``learned``
#: probes under an oracle over the prefix (its predictions are
#: near-oracle when trained, and training inside a probe would dwarf
#: the cell itself)
_PROBE_PROXIES = {"none": "none", "block": "block", "tree": "tree",
                  "oracle": "oracle", "learned": "oracle"}


def probe_proxy(prefetcher: Optional[str]) -> str:
    """The proxy family a cell's prefetcher probes under (also the memo
    key component, so e.g. oracle and learned cells share one probe)."""
    return _PROBE_PROXIES.get(prefetcher or "none", "none")


def _probe_prefetcher(proxy: str, prefix):
    from repro.uvm.prefetchers import (BlockPrefetcher, NoPrefetcher,
                                       OraclePrefetcher, TreePrefetcher)
    if proxy == "block":
        return BlockPrefetcher()
    if proxy == "tree":
        return TreePrefetcher()
    if proxy == "oracle":
        import numpy as np
        return OraclePrefetcher(np.asarray(prefix.pages))
    return NoPrefetcher()


def _probe(trace, device_pages: int, probe_accesses: int,
           proxy: str = "none") -> str:
    """Replay a prefix of ``trace`` under every concrete policy (with
    the cell's probe-proxy prefetcher) and return the cheapest.
    Capacity is scaled so the prefix sees the same oversubscription
    ratio as the full cell."""
    # local imports: this module is part of the sweep's jax-free surface
    from repro.uvm.config import UVMConfig
    from repro.uvm.replay_core import ReplayRequest, dispatch

    n = len(trace.accesses)
    prefix = trace
    if n > probe_accesses:
        prefix = trace.split(probe_accesses / n)[0]
    ratio = device_pages / trace.working_set_pages
    probe_pages = max(1, int(prefix.working_set_pages * ratio))
    best = None
    for i, policy in enumerate(EVICTION_POLICIES):
        cfg = UVMConfig(device_pages=probe_pages, eviction=policy)
        stats = dispatch(
            ReplayRequest(prefix, _probe_prefetcher(proxy, prefix), cfg),
            backend="numpy")
        score = (stats.cycles, i)
        if best is None or score < best[0]:
            best = (score, policy)
    return best[1]


def resolve_eviction(policy: str, bench: str, trace=None,
                     device_pages: Optional[int] = None,
                     probe_accesses: int = PROBE_ACCESSES,
                     prefetcher: Optional[str] = None) -> str:
    """Resolve a cell's eviction policy to a concrete one.

    Non-adaptive policies validate and pass through unchanged.  For
    ``adaptive``: selector table first, then the probe replay (memoized
    per (trace content, capacity, probe-proxy family) — thread-safe,
    the sweep's prepare stage runs in a pool), and ``lru`` when there
    is no eviction pressure to measure.  ``prefetcher`` is the cell's
    prefetcher name: the probe replays under its proxy family (see
    :func:`probe_proxy`) so a tree-prefetched cell is not resolved from
    demand-paging behavior it will never exhibit.
    """
    if not is_adaptive(policy):
        return validate_policy(policy)
    table = _table()
    if bench in table:
        return table[bench]
    if (trace is None or device_pages is None
            or device_pages >= trace.working_set_pages):
        return EVICTION_POLICIES[0]
    proxy = probe_proxy(prefetcher)
    from repro.uvm import predcache
    memo_key = (predcache.trace_content_key(trace), device_pages,
                probe_accesses, proxy)
    with _MEMO_LOCK:
        hit = _MEMO.get(memo_key)
    if hit is not None:
        return hit
    choice = _probe(trace, device_pages, probe_accesses, proxy)
    with _MEMO_LOCK:
        _MEMO.setdefault(memo_key, choice)
    return choice


def main(argv=None) -> None:
    """Distill sweep results into a selector table::

        python -m repro.uvm.adaptive results.json --out table.json

    ``results.json`` is a sweep output (``{"rows": [...]}`` or a bare row
    list); the table is the ``{bench: policy}`` JSON that
    ``REPRO_ADAPTIVE_TABLE`` consumes.
    """
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        description="Distill sweep result rows into an adaptive-eviction "
                    "selector table (REPRO_ADAPTIVE_TABLE format)")
    ap.add_argument("results", help="sweep results.json (rows with "
                                    "bench/eviction/cycles)")
    ap.add_argument("--out", default=None,
                    help="write the table here (default: stdout)")
    args = ap.parse_args(argv)
    with open(args.results) as f:
        doc = json.load(f)
    rows = doc["rows"] if isinstance(doc, dict) else doc
    table = selector_from_rows(rows)
    if not table:
        ap.error("no usable rows (need bench, concrete eviction, cycles)")
    blob = json.dumps({"selector": table,
                       "note": "bench -> cheapest mean-cycles eviction "
                               "policy; consumed via REPRO_ADAPTIVE_TABLE"},
                      indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")
    else:
        sys.stdout.write(blob + "\n")


if __name__ == "__main__":
    main()
