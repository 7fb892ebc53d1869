"""The program's burst_dispatch span (the fused-level burst program and
its one sync), seconds per window check."""


def read(ctx):
    tot = ctx.spans.get("burst_dispatch")
    if not tot or not ctx.records:
        return None
    return tot["seconds"] / len(ctx.records)
