"""What a reference prefetcher family is, and where the benchmark finds it.

A family is the module ``bench/reference/prefetchers/<name>.py``, found by
the name a grid gives on its ``prefetcher`` axis.  It defines:

* ``make(trace, cell)``: the prefetcher of one replay, given the whole
  reference trace (``tracegen.RefTrace``, its records included) and the
  sweep cell's fields as a dict.  The object has the hooks of
  :class:`Prefetcher` below;
* ``INPUT_BYTES_PER_ACCESS``: the bytes of one access's inputs on a lane;
* ``state_bytes(working_set_pages)``: the lane state the family keeps
  beside each page's own, over a working set.

The last two are not the reference's own: they describe the family's
work as the program's lane layout holds it, and give the least bytes a
lane of the family moves (``bench/costs.py``).  They sit here so that a
family brings its bytes with it.  Like the rest of the reference, a
family imports nothing of the program.

A family whose predictions come from a predictor that the program trains
inside each grid also defines:

* ``TRAINED = True``: before each grid that holds such a family the
  harness drops the program's prediction memo, so every grid trains, and
  it hands the comparison what the chip trained there: one record per
  trained predictor, with the trace's content key, the ``model_family``,
  the resolved predictor configuration and the trained parameters as
  plain dicts and NumPy float32 arrays, the predictions the lanes
  consumed, the top-1 confidence of each window that the predictor's
  gate read, and what its first train steps were given and gave back
  (``harness.trained_record``).  ``make`` finds the record of its row
  under ``cell["trained"]``; a row without one is a mismatch;
* ``resolve(cell)``: loads what the comparison of sweep cell ``cell``
  needs, so that a missing file is refused when the cell loads.

Any family may report numbers of its own beside the replay's:

* ``CHECKS``: each number's name, and whether the grids of a window
  ``"sum"`` it or take its ``"max"``; a traffic file may hold any of
  them to a limit;
* ``checks(trace, cells, produced)``: the numbers of what the program
  produced in one grid (the record above, or None for a family that is
  not trained), given the sweep cells, as dicts, that it served;
* ``control_checks(trace, cells, produced)``: the same numbers with the
  family's control in the program's place.
"""
from __future__ import annotations

import os
from typing import List

from bench.modules import load_module

PREFETCHER_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "prefetchers")
#: the paper's 64 KB basic block
BASIC_BLOCK_PAGES = 16


class Prefetcher:
    """The hooks of the replay loop; this base prefetches nothing."""

    #: cycles added to a prefetch's ready time (a model's inference)
    extra_latency_cycles = 0.0

    def on_fault(self, index: int, page: int, resident) -> List[int]:
        """Pages to migrate with the far fault of access ``index``."""
        return []

    def on_access(self, index: int, resident, clock) -> List[int]:
        """Pages to migrate after access ``index``, one by one; ``clock``
        is the replay's clock at that access."""
        return []

    def migrated(self, pages: List[int]) -> None:
        """``pages`` were made resident."""

    def evicted(self, page: int) -> None:
        """``page`` left the device."""


def block_pages(page: int, resident) -> List[int]:
    """The other pages of ``page``'s basic block that are not resident."""
    base = page // BASIC_BLOCK_PAGES * BASIC_BLOCK_PAGES
    return [p for p in range(base, base + BASIC_BLOCK_PAGES)
            if p != page and p not in resident]


def load(name: str, directory: str = PREFETCHER_DIR):
    """The family module of prefetcher ``name``; a missing one is
    :class:`bench.modules.Refused`, naming the file."""
    return load_module(directory, name, "reference prefetcher family")


def trained(name: str) -> bool:
    """Whether family ``name``'s predictor is trained inside the grid."""
    return getattr(load(name), "TRAINED", False)
