"""Host time per request spent splitting 64-bit keys and values into
their two uint32 words and joining the answers' words, from the index's
``split_seconds`` counter.  A program without the counter gives
nothing to read."""


def read(ctx):
    before = ctx.counters_before.get("split_seconds")
    after = ctx.counters_after.get("split_seconds")
    if before is None or after is None or not ctx.requests:
        return None
    return (after - before) / ctx.requests * 1e3
