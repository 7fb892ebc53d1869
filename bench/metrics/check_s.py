"""Time to a verdict: the window's seconds over the checks completed in
it (host clock)."""


def read(ctx):
    return ctx.window_s / len(ctx.records)
