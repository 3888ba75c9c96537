"""Bytes the benchmark's work needs, from its shapes, and the chip peaks
they are held against.

These count the algorithm, not an implementation: a lane program that
moves more bytes than :func:`lane_bytes` reads a lower share of its
roofline, never a higher one.
"""
from __future__ import annotations

import json
import os
from typing import Dict

from bench.reference import family

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

#: per-page replay state: arrival (float64), LRU stamp (int32), the
#: prefetched-unused flag (1 byte), plus the policy's own word
PAGE_STATE_BYTES = 8 + 4 + 1
POLICY_STATE_BYTES = {"lru": 0, "random": 4, "hotcold": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add them with their source")
    return table[device_kind]


def lane_bytes(n_accesses: int, working_set_pages: int, prefetcher: str,
               eviction: str) -> int:
    """Least bytes one lane's replay moves: each access record read once,
    the accessed page's state read and written once per access, and the
    lane's state over its working set moved once per batch.  What an
    access reads and what state the prefetcher keeps, its family module
    declares (``bench/reference/family.py``): constants of the program's
    lane layout (the int32 page id, the oracle's int32 position, the
    tree's node counts), kept beside the family's reference so that a
    family enters as one file; the reference replay itself reads none of
    them."""
    fam = family.load(prefetcher)
    per_page = PAGE_STATE_BYTES + POLICY_STATE_BYTES[eviction]
    state = working_set_pages * per_page + fam.state_bytes(working_set_pages)
    return (n_accesses * (fam.INPUT_BYTES_PER_ACCESS + 2 * per_page)
            + state)


def bytes_roofline_pct(nbytes: float, seconds: float,
                       device_kind: str) -> float:
    """Least time for ``nbytes`` at the chip's HBM peak over the time
    taken, in percent."""
    return 100.0 * nbytes / peaks(device_kind)["hbm_bytes_per_s"] / seconds
