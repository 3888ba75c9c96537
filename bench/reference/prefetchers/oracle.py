"""The oracle: it knows the trace's first touches and, after every access,
brings up to 16 of the next 96 first-touched pages that are not
resident."""
import numpy as np

from bench.reference.family import Prefetcher

LOOKAHEAD = 96
MAX_EXTRAS = 16
#: the page id and the stream position (two int32)
INPUT_BYTES_PER_ACCESS = 8


def state_bytes(working_set_pages: int) -> int:
    return 0


class Oracle(Prefetcher):
    def __init__(self, pages) -> None:
        pages = np.asarray(pages)
        _, first = np.unique(pages, return_index=True)
        order = np.sort(first)
        self.ft_pages = pages[order].tolist()
        self.ft_index = order.tolist()
        self.pos = 0

    def on_fault(self, index, page, resident):
        return self.on_access(index, resident, None)

    def on_access(self, index, resident, clock):
        while (self.pos < len(self.ft_index)
               and self.ft_index[self.pos] <= index):
            self.pos += 1
        out = []
        for p in self.ft_pages[self.pos:self.pos + LOOKAHEAD]:
            if p not in resident:
                out.append(p)
                if len(out) >= MAX_EXTRAS:
                    break
        return out


def make(trace, cell) -> Prefetcher:
    return Oracle(trace.pages)
