"""Plain reference of the trace build for the benchmark's configurations.

An independent copy of the GPU execution model (CTA dispatch, per-SM
round-robin bursts, TLB filter, GMMU merge) that the simulator uses as its
trace source (``src/repro/traces/gpu_model.py``).  The page-access
streams of each configuration come from its own module,
``bench/reference/traces/<config name>.py``, an independent copy of the
program's generator for that benchmark, found by name: its
``streams(scale, seed)`` gives the CTA streams and the kernel's
instruction count.  Nothing here imports the program: the benchmark
builds the reference's trace from ``--seed`` here, and the rows the
program replays on its own trace must match the rows this trace gives.

Records are (pc, sm, tpc, cta, warp, kernel, array, page) in GMMU
arrival order; the evaluation window is the leading fraction of them,
and the instruction count is that of the whole kernel.
"""
from __future__ import annotations

import dataclasses
import os
import zlib
from typing import Dict, List, Tuple

import numpy as np

from bench.modules import load_module

#: where each configuration's stream generator lives, by config name
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "traces")

PAGE = 4096
FLOAT = 4
ROOT_PAGES = 512

ACCESS_DTYPE = np.dtype([
    ("pc", np.uint32), ("sm", np.uint16), ("tpc", np.uint16),
    ("cta", np.uint32), ("warp", np.uint32), ("kernel", np.uint16),
    ("array", np.uint16), ("page", np.int64),
])

#: GPU model of the paper's Table 9 (GTX 1080 Ti, 28 SMs)
N_SMS = 28
MAX_CTA_PER_SM = 16
WARPS_PER_CTA = 8
TLB_WINDOW = 1024
SM_RATE_SIGMA = 0.35


@dataclasses.dataclass
class RefTrace:
    name: str
    accesses: np.ndarray
    n_instructions: int

    @property
    def pages(self) -> np.ndarray:
        return self.accesses["page"]

    @property
    def working_set_pages(self) -> int:
        return int(np.unique(self.accesses["page"]).size)


@dataclasses.dataclass
class Stream:
    kernel: int
    cta: int
    pcs: np.ndarray
    arrays: np.ndarray
    pages: np.ndarray
    burst: float


class Alloc:
    """2 MB-aligned bump allocator from a seeded random heap base."""

    def __init__(self, seed: int) -> None:
        rng = np.random.default_rng(seed)
        self.cursor = int(rng.integers(1 << 10, 1 << 20)) * ROOT_PAGES
        self.bases: Dict[str, int] = {}
        self.ids: Dict[str, int] = {}

    def alloc(self, name: str, nbytes: int) -> None:
        pages = -(-nbytes // PAGE)
        self.bases[name] = self.cursor
        self.ids[name] = len(self.ids)
        self.cursor += -(-pages // ROOT_PAGES) * ROOT_PAGES


def pc(kernel: int, slot: int) -> int:
    """The PC of static load/store ``slot`` of kernel launch ``kernel``."""
    return 0x400000 + kernel * 0x1000 + slot * 0x20


def interleave(kernel: int, cta: int,
               parts: List[Tuple[int, int, np.ndarray]],
               burst: float) -> Stream:
    """Element-wise interleave of equal-length (pc, array, pages) parts;
    one part is taken as it is."""
    n, k = len(parts[0][2]), len(parts)
    pcs = np.empty(n * k, np.uint32)
    arrs = np.empty(n * k, np.uint16)
    pages = np.empty(n * k, np.int64)
    for i, (code, aid, pg) in enumerate(parts):
        pcs[i::k] = code
        arrs[i::k] = aid
        pages[i::k] = pg
    return Stream(kernel, cta, pcs, arrs, pages, burst)


def _sm_schedule(sm: int, mine: List[Stream], rng, t_base: float):
    """Round-robin bursts of the CTAs resident on one SM, in waves."""
    n_total = sum(len(s.pages) for s in mine)
    recs = np.zeros(n_total, dtype=ACCESS_DTYPE)
    times = np.empty(n_total, dtype=np.float64)
    pos = 0
    rate = float(np.exp(rng.normal(0.0, SM_RATE_SIGMA)))
    wave_t = t_base
    for w0 in range(0, len(mine), MAX_CTA_PER_SM):
        wave = mine[w0:w0 + MAX_CTA_PER_SM]
        wave_end = wave_t
        for slot, s in enumerate(wave):
            n = len(s.pages)
            bl = max(int(s.burst), 1)
            idx = np.arange(n)
            ts = (wave_t + (idx // bl) * (bl * len(wave)) / rate
                  + slot * bl / rate + (idx % bl) / rate
                  + rng.normal(0.0, 0.05, size=n))
            sl = slice(pos, pos + n)
            recs["pc"][sl] = s.pcs
            recs["sm"][sl] = sm
            recs["tpc"][sl] = sm // 2
            recs["cta"][sl] = s.cta
            warp_base = (s.cta * WARPS_PER_CTA) % 64
            recs["warp"][sl] = (warp_base
                                + (np.arange(n) % WARPS_PER_CTA)) % 64
            recs["kernel"][sl] = s.kernel
            recs["array"][sl] = s.arrays
            recs["page"][sl] = s.pages
            times[sl] = ts
            wave_end = max(wave_end, float(ts[-1]) if n else wave_t)
            pos += n
        wave_t = wave_end
    return recs[:pos], times[:pos]


def _tlb_filter(recs: np.ndarray, times: np.ndarray):
    """Drop an access whose page this SM touched within the last
    ``TLB_WINDOW`` accesses."""
    last_seen: Dict[int, int] = {}
    keep = np.ones(recs.size, dtype=bool)
    for i, p in enumerate(recs["page"].tolist()):
        j = last_seen.get(p)
        if j is not None and i - j <= TLB_WINDOW:
            keep[i] = False
        last_seen[p] = i
    return recs[keep], times[keep]


def generator(config_name: str, directory: str = TRACE_DIR):
    """The stream generator module of configuration ``config_name``; a
    missing one is :class:`bench.modules.Refused`, naming the file."""
    return load_module(directory, config_name, "reference trace generator")


def build_trace(config: Dict, seed: int,
                generators: str = TRACE_DIR) -> RefTrace:
    """The leading ``window`` share of the GMMU trace of ``config`` (a
    configuration file's fields: ``name``, ``bench``, ``scale``,
    ``window``) for trace seed ``seed``.  The merge's rng is keyed on the
    ``bench`` string, as the program keys it.  The configuration's
    stream generator is looked up in ``generators``."""
    bench = config["bench"]
    streams, n_instructions = generator(config["name"], generators).streams(
        config["scale"], seed)
    rng = np.random.default_rng(seed ^ (zlib.crc32(bench.encode()) & 0xFFFF))
    chunks = []
    t_base = 0.0
    for k in sorted({s.kernel for s in streams}):
        mine_all = sorted((s for s in streams if s.kernel == k),
                          key=lambda s: s.cta)
        recs_l, times_l = [], []
        for sm in range(N_SMS):
            mine = mine_all[sm::N_SMS]
            if not mine:
                continue
            recs, times = _tlb_filter(*_sm_schedule(sm, mine, rng, t_base))
            recs_l.append(recs)
            times_l.append(times)
        recs = np.concatenate(recs_l)
        times = np.concatenate(times_l)
        chunks.append(recs[np.argsort(times, kind="stable")])
        t_base = float(times.max())
    accesses = np.concatenate(chunks)
    return RefTrace(bench, accesses[:int(len(accesses) * config["window"])],
                    n_instructions)
