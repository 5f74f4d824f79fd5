"""Host front of a read: mean time of one ``lookup_batched`` call to its
results on the host, from the harness's span around it (host clock)."""


def read(ctx):
    if ctx.lookup_s.size == 0:
        return None
    return float(ctx.lookup_s.mean()) * 1e3
