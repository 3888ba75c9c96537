"""No prefetching: every first touch is a far fault."""
from bench.reference.family import Prefetcher

#: the page id (int32)
INPUT_BYTES_PER_ACCESS = 4


def state_bytes(working_set_pages: int) -> int:
    return 0


def make(trace, cell) -> Prefetcher:
    return Prefetcher()
