"""Chip smoke test: the UVM sweep's main path on one TPU, in one process.

    python chip_smoke.py          # from the root of a checkout

Phase 1 replays every golden lane group (``tests/golden/uvm_golden.json``)
as one pallas lane batch on the chip and compares it with the fixtures:
integer counters exact, floats to 1e-6 relative.

Phase 2 calls ``repro.uvm.sweep.run_sweep`` at scale 1.0 with
``device_frac=0.5``: ATAX and Pathfinder under ``lru`` with every
paper-facing prefetcher (``learned`` trains the simplified predictor at
its default 300 steps on the chip; ATAX also trains the ``transformer``
family), and ServeDecode with ``none`` and ``tree`` (step clocks captured
in-kernel, ``slo_source=kernel``).  Every row must report the pallas
backend, none may be quarantined, no fallback warning may be raised, and
each row must equal a host ``numpy`` replay of the same cell.

Each phase prints its wall time, compilation included.  The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed.  Without a TPU the script exits non-zero before
any phase runs.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
GOLDEN = os.path.join(HERE, "tests", "golden", "uvm_golden.json")

#: sweep-row columns compared with the host replay (besides the integer
#: counters): the float accumulators and the serving SLO columns
ROW_FLOAT_FIELDS = ("cycles", "ipc", "hit_rate", "accuracy", "coverage",
                    "unity", "pcie_bytes", "decode_lat_p50_us",
                    "decode_lat_p95_us", "decode_lat_p99_us", "ttft_p50_us",
                    "ttft_p95_us", "ttft_p99_us")
ROW_INT_FIELDS = ("n_accesses", "n_instructions", "hits", "late", "faults",
                  "prefetch_issued", "prefetch_used", "pages_migrated",
                  "pages_evicted", "device_pages")
REL = 1e-6


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _close(got, want) -> bool:
    if got is None or want is None:
        return got is want
    return math.isclose(got, want, rel_tol=REL, abs_tol=1e-9)


def _device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this smoke test runs only on the chip",
              file=sys.stderr, flush=True)
        sys.exit(2)
    return dev, len(jax.devices())


def phase_golden() -> list:
    """Every (lane family, eviction policy) group of golden cells as one
    lane batch on the chip; returns the mismatches (cell, field, got,
    want), empty when all match."""
    from repro.uvm.backends.pallas_backend import lane_family
    from repro.uvm.golden import (FLOAT_FIELDS, INT_FIELDS, golden_cell,
                                  golden_cell_ids, stats_to_dict)
    from repro.uvm.replay_core import ReplayRequest, get_backend

    with open(GOLDEN) as f:
        fixtures = json.load(f)["cells"]
    groups: dict = {}
    for cell_id in golden_cell_ids():
        trace, config, factory = golden_cell(cell_id)
        req = ReplayRequest(trace, factory(), config)
        key = (lane_family(req.prefetcher).split("/")[0], config.eviction)
        groups.setdefault(key, []).append((cell_id, req))
    backend = get_backend("pallas")
    bad = []
    for (family, policy), members in sorted(groups.items()):
        t0 = time.perf_counter()
        stats = backend.replay([req for _, req in members])
        n_bad = 0
        for (cell_id, _), st in zip(members, stats):
            got, want = stats_to_dict(st), fixtures[cell_id]
            if st.backend != "pallas":
                bad.append((cell_id, "backend", st.backend, "pallas"))
            for f in INT_FIELDS + ("tenant_hits", "tenant_accesses"):
                if got.get(f) != want.get(f):
                    bad.append((cell_id, f, got.get(f), want.get(f)))
                    n_bad += 1
            for f in FLOAT_FIELDS:
                if not _close(got[f], want[f]):
                    bad.append((cell_id, f, got[f], want[f]))
                    n_bad += 1
        print(f"  golden {family}/{policy}: {len(members)} cells, "
              f"{n_bad} differing counters, "
              f"{time.perf_counter() - t0:.3f}s", flush=True)
    return bad


def sweep_cells():
    from repro.uvm.sweep import expand_grid

    kw = dict(scales=[1.0], device_fracs=[0.5], evictions=["lru"],
              service_steps=300)
    return (expand_grid(["ATAX", "Pathfinder"],
                        ["none", "block", "tree", "learned", "oracle"], **kw)
            + expand_grid(["ATAX"], ["learned"],
                          model_families=["transformer"], **kw)
            + expand_grid(["ServeDecode"], ["none", "tree"], **kw))


def phase_sweep(expect_backend: str) -> list:
    """The sweep on the chip, then the same cells on the host NumPy
    engine; returns the problems found, empty when every row matches."""
    from repro.uvm.sweep import run_sweep

    cells = sweep_cells()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        rows = run_sweep(cells, workers=1, verbose=True)
        print(f"  sweep on the chip: {len(rows)} rows, "
              f"{time.perf_counter() - t0:.3f}s", flush=True)
    bad = [("warning", str(w.message)) for w in caught
           if issubclass(w.category, RuntimeWarning)
           and os.sep + "repro" + os.sep in w.filename]
    t0 = time.perf_counter()
    ref = run_sweep([dataclasses.replace(c, backend="numpy") for c in cells],
                    workers=1)
    print(f"  host numpy replay: {len(ref)} rows, "
          f"{time.perf_counter() - t0:.3f}s", flush=True)
    for row, want in zip(rows, ref):
        name = (f"{row['bench']}/{row['prefetcher']}"
                f"/{row['model_family']}")
        if row.get("quarantined"):
            bad.append((name, "quarantined"))
            continue
        if row["backend"] != expect_backend:
            bad.append((name, "backend", row["backend"], expect_backend))
        if row["bench"].startswith("Serve") and row["slo_source"] != "kernel":
            bad.append((name, "slo_source", row["slo_source"], "kernel"))
        for f in ROW_INT_FIELDS:
            if row[f] != want[f]:
                bad.append((name, f, row[f], want[f]))
        for f in ROW_FLOAT_FIELDS:
            if not _close(row[f], want[f]):
                bad.append((name, f, row[f], want[f]))
    return bad


def main() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    dev, count = _device()
    print(f"chip_smoke: device {dev.platform} {dev.device_kind}, "
          f"count {count}", flush=True)
    from repro import compile_cache
    print(f"chip_smoke: compilation cache {compile_cache.enable()}",
          flush=True)

    failed = []
    t0 = time.perf_counter()
    bad = phase_golden()
    print(f"phase 1 (golden lane groups): {time.perf_counter() - t0:.3f}s, "
          f"{len(bad)} differing counters", flush=True)
    for cell, field, got, want in bad[:20]:
        print(f"  golden {cell}: {field} {got!r} != {want!r}",
              file=sys.stderr, flush=True)
    if bad:
        cell, field, got, want = bad[0]
        failed.append(f"golden cell {cell} differs on the chip: {field} "
                      f"{got!r} != {want!r}")

    t0 = time.perf_counter()
    bad = phase_sweep("pallas")
    print(f"phase 2 (sweep): {time.perf_counter() - t0:.3f}s, "
          f"{len(bad)} problems", flush=True)
    for b in bad[:20]:
        print(f"  sweep {b}", file=sys.stderr, flush=True)
    if bad:
        failed.append(f"sweep rows differ from the host replay: {bad[0]}")
    if failed:
        _fail("; ".join(failed))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
