"""The dedup claim walk's inner iterations per check (the program's
``dedup_walk_iters`` counter): each is one vector-wide gather round on
the device, run until the slowest lane of its call stops."""

from harness.counters import per_check


def read(ctx):
    return per_check(ctx, "dedup_walk_iters")
