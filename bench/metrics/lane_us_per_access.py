"""Device time of the lane program per lane access it replayed.

Source: the profiler trace.  The lane program is the jitted
``pallas_call`` of ``repro/uvm/backends/pallas_backend.py``
(``jax.jit(call)``), whose XLA module is named ``jit_wrapped``; its
device seconds in the traced window over the accesses of the rows the
lanes replayed there.  Moves ``accesses_per_s``.
"""

LANE_MODULE = "jit_wrapped"


def read(ctx):
    sec = ctx.reduced.module_seconds(LANE_MODULE)
    if sec <= 0 or ctx.lane_accesses <= 0:
        return None
    return 1e6 * sec / ctx.lane_accesses
