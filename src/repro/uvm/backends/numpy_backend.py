"""The NumPy-chunked replay core as a replay backend.

The chunked array program ``repro.uvm.replay_core.replay_chunked`` behind
the ``ReplayBackend`` interface: bit-identical to the legacy loop for every
supported prefetcher type, pinned by ``tests/test_uvm_golden.py``.  Ask
for it with ``dispatch(request, backend="numpy")``, whose chain falls back
to ``legacy`` for what it declines.
"""
from __future__ import annotations

from typing import List, Sequence

from repro.uvm.eviction import EVICTION_POLICIES
from repro.uvm.replay_core import (SUPPORTED_PREFETCHERS, ReplayBackend,
                                   ReplayRequest, replay_chunked, span_ok)
from repro.uvm.simulator import UVMStats


class NumpyReplayBackend(ReplayBackend):
    name = "numpy"

    def can_replay(self, request: ReplayRequest) -> bool:
        return (type(request.prefetcher) in SUPPORTED_PREFETCHERS
                and request.config.eviction in EVICTION_POLICIES
                and span_ok(request))

    def replay(self, requests: Sequence[ReplayRequest]) -> List[UVMStats]:
        out = []
        for req in requests:
            stats = replay_chunked(req)
            stats.backend = self.name
            out.append(stats)
        return out
