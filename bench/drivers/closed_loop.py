"""Closed-loop traffic: one check in flight, as a user who waits on each
verdict before sending the next.  Parameters come from the traffic file
(bench/traffic/<traffic>.json): ``clients`` (1) and
``warmup_checks_max``."""

from __future__ import annotations

import time


def warm(system, traffic, compiles):
    """Whole checks until one runs with no compile event (at most
    ``warmup_checks_max``): the first compiles or loads every program,
    a later one any program that capacity growth in the first made."""
    recs = []
    for _ in range(int(traffic["warmup_checks_max"])):
        before = compiles.total()
        recs.append(system.check())
        if compiles.total() == before:
            break
    return recs


def window(system, traffic, seconds, span, after_check):
    """Checks back to back until ``seconds`` have passed; the window
    ends when the check then in flight completes.  ``span(name)`` wraps
    each check; ``after_check(n, elapsed_s)`` follows each.  Returns the
    records and the window's length in seconds."""
    if int(traffic["clients"]) != 1:
        raise ValueError("closed_loop drives one client")
    recs = []
    t0 = time.perf_counter()
    while True:
        with span("bench.check"):
            recs.append(system.check())
        elapsed = time.perf_counter() - t0
        after_check(len(recs), elapsed)
        if elapsed >= seconds:
            return recs, elapsed
