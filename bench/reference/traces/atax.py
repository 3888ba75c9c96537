"""PolyBench/GPU ATAX, y = A^T (A x): two thread-per-row matrix-vector
kernels, each sweeping A one 4 KB column block at a time.  An independent
copy of the program's ATAX generator; it imports nothing of the program.
"""
import numpy as np

from bench.reference.tracegen import FLOAT, PAGE, Alloc, interleave, pc


def streams(scale: float, seed: int):
    """The CTA streams at ``scale`` (N = 4096 at 1.0) and the kernel's
    instruction count."""
    n = int(4096 * max(scale, 0.05))
    ppr = max(1, n * FLOAT // PAGE)
    al = Alloc(seed + 2)
    for name in ("A", "x", "y", "tmp"):
        al.alloc(name, n * n * FLOAT if name == "A" else n * FLOAT)
    out = []
    for kernel in (0, 1):
        for blk in range(ppr):
            for cta in range(n // 256):
                rows = np.arange(cta * 256, cta * 256 + 256, dtype=np.int64)
                pages = al.bases["A"] + rows * ppr + blk
                out.append(interleave(
                    kernel, cta, [(pc(kernel, blk), al.ids["A"], pages)],
                    512.0))
    return out, 2 * n * n
