"""The tree prefetcher of NVIDIA's UVM (the paper's baseline): a far fault
brings its basic block, then each enclosing node of 2**lv blocks (up to
2 MB) whose resident-or-pending pages pass half of it brings the rest of
that node."""
from bench.reference.family import BASIC_BLOCK_PAGES, Prefetcher, block_pages

TREE_LEVELS = 5
#: the page id (int32)
INPUT_BYTES_PER_ACCESS = 4
#: one int32 occupancy count per node of levels 0..5 (64 KB to 2 MB
#: nodes): pages/16 + pages/32 + ... + pages/512
NODES_PER_PAGE = sum(1.0 / (BASIC_BLOCK_PAGES << lv)
                     for lv in range(TREE_LEVELS + 1))


def state_bytes(working_set_pages: int) -> int:
    return int(working_set_pages * NODES_PER_PAGE) * 4


class Tree(Prefetcher):
    def __init__(self) -> None:
        self.counts = {}

    def migrated(self, pages):
        for page in pages:
            for lv in range(TREE_LEVELS + 1):
                key = (lv, page // (BASIC_BLOCK_PAGES << lv))
                self.counts[key] = self.counts.get(key, 0) + 1

    def evicted(self, page):
        for lv in range(TREE_LEVELS + 1):
            key = (lv, page // (BASIC_BLOCK_PAGES << lv))
            if key in self.counts:
                self.counts[key] -= 1
                if self.counts[key] == 0:
                    del self.counts[key]

    def on_fault(self, index, page, resident):
        out = block_pages(page, resident)
        pending = set(out) | {page}
        for lv in range(1, TREE_LEVELS + 1):
            span = BASIC_BLOCK_PAGES << lv
            lo = page // span * span
            cnt = self.counts.get((lv, page // span), 0) + sum(
                1 for p in pending if lo <= p < lo + span)
            if cnt * 2 <= span:
                break
            extra = [p for p in range(lo, lo + span)
                     if p not in resident and p not in pending]
            out.extend(extra)
            pending.update(extra)
        return out


def make(trace, cell) -> Prefetcher:
    return Tree()
