"""Differential tests: native C++ checker vs the Python oracle.

The native runtime (native/raft_checker.cc) is the framework's CPU
engine and the machine-measured stand-in for the reference's
"TLC -workers N" baseline (BASELINE.md) — it must agree with the oracle
on distinct-state counts, per-level sizes, depth and invariant
verdicts, with and
without symmetry reduction, across the Next families.
"""

import pytest

from raft_tla_tpu import native
from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_DYNAMIC, NEXT_FULL
from raft_tla_tpu.models.explore import explore

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=4,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1),
    symmetry=False)

SMALL = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    bounds=Bounds.make(max_log_length=2, max_timeouts=2),
    symmetry=False)

MEMBER = ModelConfig(
    n_servers=3, init_servers=(0, 1), values=(1,),
    next_family=NEXT_DYNAMIC, max_inflight_override=6,
    bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                       max_client_requests=1, max_membership_changes=1),
    symmetry=False)


def compare(cfg, max_depth=10 ** 9, threads=4):
    want = explore(cfg, max_depth=max_depth)
    got = native.check(cfg, threads=threads, max_depth=max_depth)
    assert got.distinct_states == want.distinct_states, \
        (got.distinct_states, want.distinct_states)
    assert got.depth == want.depth, (got.depth, want.depth)
    assert got.level_sizes == want.level_sizes, \
        (got.level_sizes, want.level_sizes)
    want_viol = {v.invariant for v in want.violations
                 if v.invariant in native.INVARIANT_ORDER}
    assert set(got.violations) == want_viol, (got.violations, want_viol)
    return got


@pytest.mark.parametrize("sym", [False, True], ids=["nosym", "sym"])
def test_native_micro_exhaustive(sym):
    compare(MICRO.with_(symmetry=sym))


def test_native_small_bounded():
    compare(SMALL, max_depth=6)


def test_native_membership_bounded():
    compare(MEMBER, max_depth=5)


def test_native_unreliable_bounded():
    compare(SMALL.with_(next_family=NEXT_FULL), max_depth=4)


def test_native_single_thread_deterministic():
    a = compare(MICRO, threads=1)
    b = compare(MICRO, threads=8)
    assert a.distinct_states == b.distinct_states


def test_native_first_seen_is_thread_count_independent():
    """VIEW drops the history counters, so which of several VIEW-equal
    states joins the frontier decides the constraints' inputs later on.
    With racing workers config #2 drifted from the oracle at level 14
    (7567 frontier states instead of 7579); the smallest-rank winner
    makes every thread count land on the sequential BFS."""
    import os
    from raft_tla_tpu.cfg.parser import load_model
    cfg = load_model(
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "configs", "config2", "raft.cfg"),
        bounds=Bounds.make(max_log_length=3, max_timeouts=2,
                           max_client_requests=3))
    compare(cfg, max_depth=14, threads=8)
