"""Process start to the first timed request: imports, data generation,
the load through ``insert``, the mapper's catch-up and the warm-up of
every shape the window uses (host clock)."""


def read(ctx):
    return ctx.setup_s
