"""Distinct states of every check completed in the window, over the
window's seconds (host clock)."""


def read(ctx):
    return sum(r.distinct for r in ctx.records) / ctx.window_s
