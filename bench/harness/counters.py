"""The program's counters as the window's checks report them.

The engine records each check's dedup counters as one counter sample
on the span recorder the harness hands it, and the recorder's totals
(``ctx.spans``) carry each counter's samples, sum, min and max.  The
recorder is fresh at the window's start, so the samples are the
window's checks."""


def per_check(ctx, key):
    """Counter ``key`` of one window check.  A check is a deterministic
    program, so every check of the window must read the same; None if
    they disagree, if a check left no sample, or if the program has no
    such counter."""
    tot = ctx.spans.get(key)
    if not tot or "sum" not in tot or tot["count"] != len(ctx.records) \
            or tot["min"] != tot["max"]:
        return None
    return tot["min"]


def generated_per_check(ctx):
    """Candidates one window check generated; None if checks disagree."""
    vals = {r.generated for r in ctx.records}
    return vals.pop() if len(vals) == 1 else None
