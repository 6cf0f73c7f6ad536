"""Compilations, or loads of a cached program, inside the window.

Layer: compilation.  Counts ``/jax/core/compile/backend_compile_duration``
events from ``jax.monitoring`` while the window runs; set-up should have
compiled everything, so this reads 0.  Moves ``sweep_s``.
"""


def read(ctx):
    return ctx.window_compiles
