"""Basic-block prefetch: a far fault brings the rest of its 64 KB block."""
from bench.reference.family import Prefetcher, block_pages

#: the page id (int32)
INPUT_BYTES_PER_ACCESS = 4


def state_bytes(working_set_pages: int) -> int:
    return 0


class Block(Prefetcher):
    def on_fault(self, index, page, resident):
        return block_pages(page, resident)


def make(trace, cell) -> Prefetcher:
    return Block()
