"""Device time of the lookup kernels per request, summed from the
profiler trace's programs with these jit names."""

LOOKUP_JITS = ("jit(sharded_eh_lookup)", "jit(sharded_shortcut_lookup)",
               "jit(sharded_routed_lookup)", "jit(stacked_shortcut_lookup)",
               "jit(eh_lookup)", "jit(shortcut_lookup)")


def kernel_seconds(ctx):
    if ctx.trace is None:
        return 0.0
    return sum(ctx.trace.by_jit.get(name, 0.0) for name in LOOKUP_JITS)


def read(ctx):
    s = kernel_seconds(ctx)
    if s <= 0 or not ctx.requests:
        return None
    return s / ctx.requests * 1e3
