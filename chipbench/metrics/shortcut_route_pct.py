"""Share of per-shard routing decisions in the window that took the
shortcut, from the index's ``routed_shortcut``/``routed_traditional``
counters."""


def read(ctx):
    sc = (ctx.counters_after["routed_shortcut"]
          - ctx.counters_before["routed_shortcut"])
    tr = (ctx.counters_after["routed_traditional"]
          - ctx.counters_before["routed_traditional"])
    if sc + tr == 0:
        return None
    return 100.0 * sc / (sc + tr)
