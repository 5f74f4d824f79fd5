"""Device time of the insert scan per request, from the profiler
trace's ``jit(eh_insert_many)`` programs."""


def read(ctx):
    if ctx.trace is None or not ctx.requests:
        return None
    s = ctx.trace.by_jit.get("jit(eh_insert_many)", 0.0)
    return s / ctx.requests * 1e3 if s > 0 else None
