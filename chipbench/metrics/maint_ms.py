"""Mapper replay and populate time per request over the window, from
the mapper's ``replay_seconds`` and ``populate_seconds`` (its host
clock; populate ends in ``block_until_ready``).  Only traffic with
updates gives the mapper work."""


def read(ctx):
    if not int(ctx.traffic.get("updates_per_request", 0)) or not ctx.requests:
        return None
    d = sum(ctx.counters_after[k] - ctx.counters_before[k]
            for k in ("replay_seconds", "populate_seconds"))
    return d / ctx.requests * 1e3
