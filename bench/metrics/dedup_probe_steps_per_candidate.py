"""Probe advances per candidate the dedup claim walk takes (the
program's ``dedup_probe_steps`` over the check's generated states):
the length of the probe chains, set by the table's load and the hash."""

from harness.counters import generated_per_check, per_check


def read(ctx):
    steps = per_check(ctx, "dedup_probe_steps")
    gen = generated_per_check(ctx)
    if steps is None or not gen:
        return None
    return steps / gen
