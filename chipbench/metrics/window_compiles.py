"""Executables built inside the measured window (compiled or read from
the persistent cache), counted through ``jax.monitoring``.  0 is the
healthy reading."""


def read(ctx):
    return ctx.window_compiles
