"""Plain rebuild of what the learned prefetcher's predictor reads.

From the records of a reference trace, in NumPy: the trace split into
per-SM streams, each access's 14 feature columns (paper Fig 3: PC,
hit/miss, warp, SM, TPC, CTA, kernel, the page, 64 KB block and 2 MB
root addresses, the input array, and the three address deltas within
the stream), encoded into bounded ids; the class vocabulary of
distance-8 page deltas over the leading 80% of each stream; and one
window of the last 30 accesses at every position of a stream that has
30 behind it.  A prediction at a window's last access is the page that
the window's class says its stream touches 8 accesses later, and its
label is the class of the page its stream does touch then; the leading
80% of each stream's labelled windows are the training split.

These follow arXiv:2203.12672 §4-§5 as the program configures its
predictor service (SM clustering, prediction distance 8, windows of 30,
the 0.35 confidence gate); the id spaces and the hash that buckets the
address-like features are the program's embedding layout, which a
forward pass over its trained tables has to share.  Nothing here
imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import numpy as np

SEQ_LEN = 30
DISTANCE = 8
TRAIN_FRAC = 0.8
MAX_CLASSES = 20000
#: no prefetch below this softmax confidence of the top-1 class
MIN_PROB = 0.35
#: class 0 is "unseen": a delta outside the vocabulary, never prefetched
UNK = 0
BASIC_BLOCK_PAGES = 16
ROOT_PAGES = 512

#: each feature's id space (0 is "unseen")
BUCKETS: Dict[str, int] = {
    "pc": 512, "hit": 2, "warp": 256, "sm": 32, "tpc": 16, "cta": 1024,
    "kernel": 64, "paddr": 4096, "bbaddr": 2048, "raddr": 512, "inarr": 16,
    "dp": 2048, "dbb": 1024, "dr": 256,
}
#: features bucketed by a multiplicative hash; the others by modulo
HASHED = frozenset(("paddr", "bbaddr", "raddr", "dp", "dbb", "dr", "pc",
                    "inarr"))
_HASH_MULT = np.uint64(0x9E3779B97F4A7C15)


@dataclasses.dataclass
class Inputs:
    """What the predictor reads on one trace, window by window."""

    windows: np.ndarray      # (n, SEQ_LEN, n_features) int32 ids
    ends: np.ndarray         # trace position of each window's last access
    end_pages: np.ndarray    # the page of that access
    deltas: np.ndarray       # class id -> page delta (class 0: unseen)
    labels: np.ndarray       # class of each window's distance-8 delta
    #                          (-1: its stream ends within the distance)
    train: np.ndarray        # whether a window is in the training split

    @property
    def n_classes(self) -> int:
        return len(self.deltas)

    def decode(self, classes: np.ndarray) -> np.ndarray:
        """The page each window's class predicts."""
        return self.end_pages + self.deltas[classes]


def _bucket(col: np.ndarray, name: str) -> np.ndarray:
    b = BUCKETS[name]
    if name in HASHED:
        with np.errstate(over="ignore"):
            h = col.astype(np.int64).view(np.uint64) * _HASH_MULT
            h = h ^ (h >> np.uint64(29))
        return (1 + (h % np.uint64(b - 1))).astype(np.int64)
    return 1 + (col % (b - 1))


def streams(accesses: np.ndarray):
    """Per SM, in SM order, the trace positions of its accesses and their
    raw feature columns (SMs with fewer than two accesses are left out,
    as a stream without a delta)."""
    pages = accesses["page"].astype(np.int64)
    miss = np.zeros(len(pages), np.int64)
    miss[np.unique(pages, return_index=True)[1]] = 1
    cols = {"pc": accesses["pc"], "hit": miss, "warp": accesses["warp"],
            "sm": accesses["sm"], "tpc": accesses["tpc"],
            "cta": accesses["cta"], "kernel": accesses["kernel"],
            "paddr": pages, "bbaddr": pages // BASIC_BLOCK_PAGES,
            "raddr": pages // ROOT_PAGES, "inarr": accesses["array"]}
    cols = {k: np.asarray(v).astype(np.int64) for k, v in cols.items()}
    sm = cols["sm"]
    for s in np.unique(sm):
        idx = np.flatnonzero(sm == s)
        if len(idx) < 2:
            continue
        c = {k: v[idx] for k, v in cols.items()}
        for d, a in (("dp", "paddr"), ("dbb", "bbaddr"), ("dr", "raddr")):
            c[d] = np.diff(c[a], prepend=c[a][0])
        yield idx, c


def vocabulary(accesses: np.ndarray) -> np.ndarray:
    """Class id -> page delta: the distinct distance-``DISTANCE`` deltas
    of the leading ``TRAIN_FRAC`` of each SM's stream, the most frequent
    ``MAX_CLASSES - 1`` of them, in increasing order, after the unseen
    class."""
    parts = []
    for _, c in streams(accesses):
        p = c["paddr"]
        if len(p) <= DISTANCE:
            continue
        dd = p[DISTANCE:] - p[:-DISTANCE]
        parts.append(dd[:max(int(len(dd) * TRAIN_FRAC), 1)])
    all_d = np.concatenate(parts) if parts else np.zeros(0, np.int64)
    vals, counts = np.unique(all_d, return_counts=True)
    if len(vals) > MAX_CLASSES - 1:
        vals = vals[np.sort(np.argsort(-counts)[:MAX_CLASSES - 1])]
    return np.concatenate([[np.iinfo(np.int64).min], vals])


def _labels(pages: np.ndarray, n_windows: int,
            deltas: np.ndarray) -> np.ndarray:
    """The class of each window's distance-``DISTANCE`` delta, for the
    windows of one stream of ``pages``; -1 where the stream ends first."""
    ends = np.arange(SEQ_LEN - 1, SEQ_LEN - 1 + n_windows)
    out = np.full(n_windows, -1, np.int64)
    ok = ends + DISTANCE < len(pages)
    want = pages[ends[ok] + DISTANCE] - pages[ends[ok]]
    vals = deltas[1:]
    pos = np.clip(np.searchsorted(vals, want), 0, max(len(vals) - 1, 0))
    hit = (vals[pos] == want) if len(vals) else np.zeros(len(want), bool)
    out[ok] = np.where(hit, pos + 1, UNK)
    return out


def build(accesses: np.ndarray, features: Sequence[str]) -> Inputs:
    """The windows, their positions, labels and training split and the
    vocabulary of a trace's records, the windows' ids in the order
    ``features`` names them."""
    deltas = vocabulary(accesses)
    wins, ends, end_pages, labels, train = [], [], [], [], []
    offsets = np.arange(SEQ_LEN)
    for idx, c in streams(accesses):
        n = len(idx)
        if n < SEQ_LEN:
            continue
        enc = np.stack([_bucket(c[f], f) for f in features],
                       axis=1).astype(np.int32)
        last = np.arange(SEQ_LEN - 1, n)
        wins.append(enc[last[:, None] - (SEQ_LEN - 1) + offsets])
        ends.append(idx[last])
        end_pages.append(c["paddr"][last])
        lab = _labels(c["paddr"], len(last), deltas)
        labels.append(lab)
        # a stream too short to label a window past its first trains none
        n_lab = int(np.count_nonzero(lab >= 0))
        split = np.zeros(len(last), bool)
        if n >= SEQ_LEN + DISTANCE + 1:
            split[:int(n_lab * TRAIN_FRAC)] = True
        train.append(split)
    if not wins:
        return Inputs(np.zeros((0, SEQ_LEN, len(features)), np.int32),
                      np.zeros(0, np.int64), np.zeros(0, np.int64), deltas,
                      np.zeros(0, np.int64), np.zeros(0, bool))
    return Inputs(np.concatenate(wins), np.concatenate(ends),
                  np.concatenate(end_pages), deltas, np.concatenate(labels),
                  np.concatenate(train))
