"""Share of the lane program's roofline: the least time the chip needs
for the bytes the lane replay must move (``bench/costs.py``,
``lane_bytes``: each access record read once, the accessed page's state
read and written once, each lane's working-set state moved once per
batch) at the HBM peak of the device (``bench/peaks.json``), over the
lane program's device time (module ``jit_wrapped``).  The lanes do no
matrix work, so bytes bound it.  Source: the profiler trace.  Moves
``accesses_per_s``.
"""
from bench import costs

LANE_MODULE = "jit_wrapped"


def read(ctx):
    sec = ctx.reduced.module_seconds(LANE_MODULE)
    if sec <= 0 or not ctx.program_traces:
        return None
    nbytes = sum(costs.lane_bytes(
        int(r["n_accesses"]),
        ctx.program_traces[r["seed"]].working_set_pages,
        r["prefetcher"], r["eviction"])
        for r in ctx.rows if r.get("backend") == "pallas")
    if nbytes <= 0:
        return None
    return costs.bytes_roofline_pct(nbytes, sec, ctx.device_kind)
