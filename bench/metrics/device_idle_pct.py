"""Share of the traced window in which no XLA module ran on the device.

Source: the profiler trace (``XLA Modules`` line of each TPU plane); busy
is the union of module intervals, the window the benchmark's outermost
``bench.grid`` spans.  Moves ``accesses_per_s``: idle device time is
time the sweep's host path holds the chip back.
"""


def read(ctx):
    red = ctx.reduced
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
