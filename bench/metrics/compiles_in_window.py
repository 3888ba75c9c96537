"""XLA compilations and persistent-cache loads inside the traced window.

Source: JAX's own monitoring events (``/jax/core/compile/
backend_compile_duration``), counted by the harness while the window
runs.  Every program the window needs was compiled in set-up, so each
count here is a program the sweep builds anew per grid (a fresh
``jax.jit`` per predictor training, for one).  Moves ``accesses_per_s``.
"""


def read(ctx):
    return float(ctx.window.compiles)
