"""The program's check_setup span (Engine.check up to its driver loop:
store init, root dedup, carry allocation, root placement, the root
level's finalize and harvest), seconds per window check."""


def read(ctx):
    tot = ctx.spans.get("check_setup")
    if not tot or not ctx.records:
        return None
    return tot["seconds"] / len(ctx.records)
