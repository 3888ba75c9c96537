"""Plain reference of one sweep row: the per-access UVM replay loop.

A straightforward, independent implementation of the simulator's timing
model (arXiv:2203.12672 §7, Table 9 constants): far faults served in
batched rounds of the 45 us fault latency, PCIe queueing, prefetches
that skip the fault path, MSHR stalls, and eviction under
oversubscription with the in-flight-victim rule.  It follows the
simulator's legacy per-access loop operation by operation, so a sound
program agrees with it exactly in every integer counter and to rounding
in the float accumulators.  It imports nothing of the program.  The
prefetcher of a row is its family's module, found by name
(``bench/reference/family.py``).

``precise=False`` runs every float of the timing chain in float32 instead
of float64: that is the benchmark's control, the step below the float64
timing state the configuration states.
"""
from __future__ import annotations

import heapq
import math
from collections import OrderedDict
from typing import Dict, List

import numpy as np

from bench.reference import family

#: paper Table 9 (GTX 1080 Ti under UVMSmart), GPU core cycles
CORE_MHZ = 1481.0
PAGE_SIZE = 4096
PTW_CYCLES = 100
DRAM_CYCLES = 100
PCIE_LATENCY_CYCLES = 100
FAR_FAULT_US = 45.0
PCIE_GB_S = 15.75
MSHR_ENTRIES = 64
ISSUE_IPC = 512.0
ACCESS_OVERHEAD_CYCLES = 1200.0
PREFETCH_OVERHEAD_CYCLES = 600.0

_MASK32 = 0xFFFFFFFF


def _score(page: int, draw: int) -> int:
    """The ``random`` policy's 32-bit priority of a page at an insert."""
    x = (page & _MASK32) ^ ((draw * 0x9E3779B9) & _MASK32)
    x ^= x >> 16
    x = (x * 0x21F0AAAD) & _MASK32
    x ^= x >> 15
    x = (x * 0x735A2D97) & _MASK32
    x ^= x >> 15
    return x


class _Policy:
    """Victim selection over the resident pages (kept in LRU order)."""

    def __init__(self, name: str) -> None:
        if name not in ("lru", "random", "hotcold"):
            raise ValueError(f"unknown eviction policy {name!r}")
        self.name = name
        self.counter = 0
        self.key: Dict[int, tuple] = {}
        self.heap: List[tuple] = []

    def insert(self, page: int) -> None:
        if self.name == "lru" or page in self.key:
            return
        if self.name == "random":
            k = (_score(page, self.counter), page)
        else:
            k = (0, self.counter, page)
        self.key[page] = k
        heapq.heappush(self.heap, k)
        self.counter += 1

    def touch(self, page: int) -> None:
        if self.name == "hotcold" and page in self.key:
            freq = self.key[page][0]
            self.key[page] = (freq + 1, self.counter, page)
        self.counter += 1

    def evict(self, page: int) -> None:
        self.key.pop(page, None)

    def victim(self, resident: "OrderedDict[int, float]") -> int:
        if self.name == "lru":
            return next(iter(resident))
        while True:
            k = self.heap[0]
            cur = self.key.get(k[-1])
            if cur == k:
                return k[-1]
            if cur is None or self.name == "random":
                heapq.heappop(self.heap)
            else:
                heapq.heapreplace(self.heap, cur)


def replay(trace, cell: Dict, precise: bool = True,
           families: str = family.PREFETCHER_DIR) -> Dict:
    """Replay reference trace ``trace`` under sweep cell ``cell`` (its
    fields as a dict); returns the row's statistics by column name.  The
    prefetcher is the family ``cell["prefetcher"]`` names, from
    ``families``."""
    prefetcher, eviction = cell["prefetcher"], cell["eviction"]
    device_pages = (int(trace.working_set_pages * cell["device_frac"])
                    if cell.get("device_frac") is not None
                    else cell.get("device_pages"))
    n_instructions = trace.n_instructions
    f = float if precise else np.float32
    page_list = [int(p) for p in np.asarray(trace.pages)]
    n = len(page_list)
    ff = f(FAR_FAULT_US * CORE_MHZ)
    page_tx = f(PAGE_SIZE / (PCIE_GB_S * 1e9 / (CORE_MHZ * 1e6)))
    pcie_lat = f(PCIE_LATENCY_CYCLES)
    ptw = f(PTW_CYCLES)
    pf_over = f(PREFETCH_OVERHEAD_CYCLES)
    page_bytes = f(PAGE_SIZE)
    cpa = f(PTW_CYCLES + DRAM_CYCLES + ACCESS_OVERHEAD_CYCLES
            + (n_instructions / max(n, 1)) / ISSUE_IPC)
    pf = family.load(prefetcher, families).make(trace, cell)
    pf_ready = f(pf.extra_latency_cycles)
    policy = _Policy(eviction)
    cap = device_pages
    track = cap is not None

    resident: "OrderedDict[int, float]" = OrderedDict()
    unused: Dict[int, bool] = {}
    outstanding: List = []
    clock = f(0.0)
    pcie_free = f(0.0)
    pcie_bytes = f(0.0)
    hits = late = faults = issued = used = migrated = evicted = 0

    def schedule(extras: List[int], batch: bool) -> None:
        nonlocal pcie_free, migrated, pcie_bytes, issued
        start = max(pcie_free, clock + pf_over + pf_ready)
        end = start + len(extras) * page_tx
        t = start
        for q in extras:
            t = t + page_tx
            resident[q] = (end if batch else t) + pcie_lat
            if track:
                policy.insert(q)
            unused[q] = True
            migrated += 1
            pcie_bytes = pcie_bytes + page_bytes
        pcie_free = end
        issued += len(extras)
        pf.migrated(extras)

    for i, p in enumerate(page_list):
        clock = clock + cpa
        arr = resident.get(p)
        if arr is not None:
            if arr <= clock:
                hits += 1
            else:
                late += 1
                heapq.heappush(outstanding, arr)
            if unused.pop(p, None):
                used += 1
            resident.move_to_end(p)
            if track:
                policy.touch(p)
        else:
            faults += 1
            ready = ((clock // ff) + f(2.0)) * ff + ptw
            start = max(ready, pcie_free)
            arrival = start + pcie_lat + page_tx
            pcie_free = start + page_tx
            resident[p] = arrival
            if track:
                policy.insert(p)
            migrated += 1
            pcie_bytes = pcie_bytes + page_bytes
            heapq.heappush(outstanding, arrival)
            pf.migrated([p])
            extras = pf.on_fault(i, p, resident)
            if extras:
                schedule(extras, batch=True)
        extras = pf.on_access(i, resident, clock)
        if extras:
            schedule(extras, batch=False)
        while len(outstanding) > MSHR_ENTRIES:
            clock = max(clock, heapq.heappop(outstanding))
        while track and len(resident) > cap:
            victim = policy.victim(resident)
            if resident[victim] > clock:
                resident.move_to_end(victim)
                policy.touch(victim)
                break
            del resident[victim]
            policy.evict(victim)
            unused.pop(victim, None)
            pf.evicted(victim)
            evicted += 1
            if evicted % 2 == 0:
                pcie_bytes = pcie_bytes + page_bytes
                pcie_free = pcie_free + page_tx
    while outstanding:
        clock = max(clock, heapq.heappop(outstanding))

    cycles = float(clock)
    accuracy = used / issued if issued else 1.0
    would_be = used + faults + late
    coverage = used / would_be if would_be else 1.0
    hit_rate = hits / max(n, 1)
    return {
        "prefetcher": prefetcher, "eviction": eviction,
        "n_accesses": n, "n_instructions": int(n_instructions),
        "hits": hits, "late": late, "faults": faults,
        "prefetch_issued": issued, "prefetch_used": used,
        "pages_migrated": migrated, "pages_evicted": evicted,
        "device_pages": device_pages,
        "cycles": cycles, "ipc": n_instructions / max(cycles, 1.0),
        "hit_rate": hit_rate, "accuracy": accuracy, "coverage": coverage,
        "unity": float(np.cbrt(accuracy * coverage * hit_rate)),
        "pcie_bytes": float(pcie_bytes),
    }


#: the columns a row is compared on: what ran and the integer counters
#: exactly, the float accumulators (and what derives from them) by
#: relative gap
EXACT_FIELDS = ("prefetcher", "eviction", "n_accesses", "n_instructions",
                "hits", "late", "faults", "prefetch_issued",
                "prefetch_used", "pages_migrated", "pages_evicted",
                "device_pages")
FLOAT_FIELDS = ("cycles", "ipc", "hit_rate", "accuracy", "coverage",
                "unity", "pcie_bytes")


#: the gap of a column the program left empty or non-finite
NOT_COMPARED = 1e300


def rel_gap(got, want) -> float:
    """Relative gap of one float column (0 where both are 0)."""
    if got is None or want is None or not math.isfinite(float(got)):
        return NOT_COMPARED
    scale = max(abs(float(want)), 1e-300)
    return abs(float(got) - float(want)) / scale
