"""Operations completed per second: keys read plus updates acknowledged,
over the whole measured window (host clock)."""


def read(ctx):
    return ctx.ops / ctx.window_s
