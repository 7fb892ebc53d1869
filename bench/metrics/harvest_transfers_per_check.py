"""Device-to-host reads of level rows per check (the program's
``harvest_transfers`` counter): the harvests' blocking round trips
between dispatches, each a fixed latency whatever its size."""

from harness.counters import per_check


def read(ctx):
    return per_check(ctx, "harvest_transfers")
