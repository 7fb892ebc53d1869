"""Share of a check's enabled lanes whose successors the expander builds
with their action family's kernel, not the delta matmul (the program's
``lanes_kernel_path`` over the sum of its ``lanes_enabled.<Family>``
counters), in percent.  None unless every window check reads the same,
and None on a program without these counters."""

from harness.counters import per_check

PREFIX = "lanes_enabled."


def read(ctx):
    kernel = per_check(ctx, "lanes_kernel_path")
    names = [k for k in ctx.spans if k.startswith(PREFIX)]
    if kernel is None or not names:
        return None
    total = 0
    for nm in names:
        v = per_check(ctx, nm)
        if v is None:
            return None
        total += v
    return 100 * kernel / total if total else None
