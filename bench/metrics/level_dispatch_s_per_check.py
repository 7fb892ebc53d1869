"""The program's level_dispatch span (Engine.check's per-level path:
chunk steps, finalize, growth replays), seconds per window check."""


def read(ctx):
    tot = ctx.spans.get("level_dispatch")
    if not tot or not ctx.records:
        return None
    return tot["seconds"] / len(ctx.records)
