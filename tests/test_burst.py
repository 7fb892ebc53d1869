"""Fused multi-level burst (engine/bfs._burst_core and its engine
wrappers): up to burst_levels whole BFS levels per device call while
the frontier fits the burst ring (_burst_chunks frontier chunks).  The
burst must be an exact drop-in for the per-level driver in EVERY
engine — counts, level sizes, archives, violations, traces and
checkpoints all bit-identical with burst on vs off (and vs the Python
oracle via the suite's existing differential tests, which run with the
default burst=True)."""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.engine.spill import SpillEngine

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=4, symmetry=True,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))

SMALL = ModelConfig(
    n_servers=3, init_servers=(0, 1, 2), values=(1, 2),
    max_inflight_override=4, symmetry=True,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1),
    constraints=("BoundedTimeouts", "BoundedClientRequests"))

# spill-engine micro (test_spill's shape: NEXT_ASYNC keeps the space
# small with segment capacities squeezed)
SPILL_MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))
SPILL_KW = dict(chunk=64, seg=1 << 10, vcap=1 << 12, sync_every=2)

def _counts_match(a, b):
    assert a.distinct_states == b.distinct_states
    assert a.generated_states == b.generated_states
    assert a.depth == b.depth
    assert a.level_sizes == b.level_sizes
    assert a.violations_global == b.violations_global


def _archives_match(e_on, e_off):
    """Archives identical level by level, row by row (same enumeration
    order => same global ids => identical traces)."""
    assert len(e_on._parents) == len(e_off._parents)
    for pa, pb in zip(e_on._parents, e_off._parents):
        np.testing.assert_array_equal(pa, pb)
    for la, lb in zip(e_on._lanes, e_off._lanes):
        np.testing.assert_array_equal(la, lb)
    for sa, sb in zip(e_on._states, e_off._states):
        assert sa.keys() == sb.keys()
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])


# slow-marked (tier-1 budget, PR 2): the burst==driver A/B runs the
# space twice; the default burst path stays covered by
# test_burst_finds_violation and the engine micro differentials
@pytest.mark.slow
@pytest.mark.parametrize("cfg", [MICRO, SMALL], ids=["micro", "small"])
def test_burst_matches_per_level_driver(cfg):
    e_on = Engine(cfg, chunk=64, store_states=True, burst=True)
    r_on = e_on.check()
    e_off = Engine(cfg, chunk=64, store_states=True, burst=False)
    r_off = e_off.check()
    _counts_match(r_on, r_off)
    assert r_on.levels_fused > 0     # the fused path actually engaged
    _archives_match(e_on, e_off)


@pytest.mark.slow
def test_burst_respects_max_depth_and_budget():
    for md in (1, 3, 7):
        r_on = Engine(MICRO, chunk=64, store_states=False,
                      burst=True).check(max_depth=md)
        r_off = Engine(MICRO, chunk=64, store_states=False,
                       burst=False).check(max_depth=md)
        assert r_on.depth == r_off.depth == md
        assert r_on.distinct_states == r_off.distinct_states
        assert r_on.level_sizes == r_off.level_sizes
    # max_states stops at the same level boundary either way
    r_on = Engine(MICRO, chunk=64, store_states=False,
                  burst=True).check(max_states=50)
    r_off = Engine(MICRO, chunk=64, store_states=False,
                   burst=False).check(max_states=50)
    assert r_on.distinct_states == r_off.distinct_states
    assert r_on.depth == r_off.depth


@pytest.mark.slow
def test_burst_checkpoint_resume(tmp_path):
    full = Engine(MICRO, chunk=64, store_states=True,
                  burst=True).check()
    ckpt = str(tmp_path / "b.ckpt")
    e1 = Engine(MICRO, chunk=64, store_states=True, burst=True)
    part = e1.check(max_depth=6, checkpoint_path=ckpt)
    assert part.depth == 6
    # resume with burst OFF: the checkpoint format is driver-agnostic
    e2 = Engine(MICRO, chunk=64, store_states=True, burst=False)
    resumed = e2.check(resume_from=ckpt)
    assert resumed.distinct_states == full.distinct_states
    assert resumed.level_sizes == full.level_sizes


@pytest.mark.slow  # tier-1 budget (round 14): ~18s; violation +
# stop_on_violation parity under the (batched) burst core stays fast
# via test_serve::test_batched_violation_states_and_witness_parity.
def test_burst_finds_violation():
    # a scenario property (negated reachability — FirstBecomeLeader
    # fires at the first leader election, a shallow burst-path level)
    # is found with its decoded state, and stop_on_violation stops
    # the run at the same state either way
    cfg = MICRO.with_(invariants=MICRO.invariants +
                      ("FirstBecomeLeader",))
    e_on = Engine(cfg, chunk=64, store_states=False, burst=True)
    r_on = e_on.check(stop_on_violation=True)
    e_off = Engine(cfg, chunk=64, store_states=False, burst=False)
    r_off = e_off.check(stop_on_violation=True)
    assert r_on.violations and r_off.violations
    v_on, v_off = r_on.violations[0], r_off.violations[0]
    assert v_on.invariant == v_off.invariant
    assert v_on.state_id == v_off.state_id
    assert v_on.state == v_off.state


def test_burst_rejects_nonpositive_levels():
    with pytest.raises(ValueError, match="burst_levels"):
        Engine(MICRO, chunk=64, burst_levels=0)
    with pytest.raises(ValueError, match="burst_levels"):
        SpillEngine(SPILL_MICRO, burst_levels=-3)


# ---------------------------------------------------------------------
# fused multi-chunk dispatch (ISSUE 5): the dispatch-floor acceptance
# pin plus one fast burst≡per-level representative per engine family;
# the heavier full-space duplicates (archives, traces, checkpoints)
# are slow-marked per the ROADMAP tier-1 budget rule.
# ---------------------------------------------------------------------


@pytest.mark.smoke
def test_burst_dispatch_floor_tiny_levels():
    """The acceptance shape (config #3's 12 sub-ring early levels):
    12 levels cost <= 2 burst dispatches instead of 12 per-level round
    trips, with counts identical to the per-level driver — asserted
    via the new levels_fused stat."""
    r_on = Engine(MICRO, chunk=64, store_states=False,
                  burst=True).check(max_depth=12)
    r_off = Engine(MICRO, chunk=64, store_states=False,
                   burst=False).check(max_depth=12)
    _counts_match(r_on, r_off)
    assert r_on.depth == 12
    assert r_on.levels_fused == 12
    assert r_on.burst_dispatches <= 2
    assert r_off.levels_fused == 0 and r_off.burst_dispatches == 0


def test_spill_burst_matches_segment_driver():
    """Fast representative: the spill engine's fused path vs its
    segment driver on a bounded prefix of the space."""
    r_on = SpillEngine(SPILL_MICRO, store_states=False, burst=True,
                       **SPILL_KW).check(max_depth=10)
    r_off = SpillEngine(SPILL_MICRO, store_states=False, burst=False,
                        **SPILL_KW).check(max_depth=10)
    _counts_match(r_on, r_off)
    assert r_on.levels_fused > 0
    assert r_off.levels_fused == 0


@pytest.mark.slow
def test_spill_burst_full_parity_archives_traces():
    """Full space: spill burst on/off counts, archives, violations AND
    witness-trace replay bit-identical (the burst's gid assignment
    must coincide with the spilled harvest order exactly)."""
    e_on = SpillEngine(SPILL_MICRO, store_states=True, burst=True,
                       **SPILL_KW)
    r_on = e_on.check()
    e_off = SpillEngine(SPILL_MICRO, store_states=True, burst=False,
                        **SPILL_KW)
    r_off = e_off.check()
    _counts_match(r_on, r_off)
    assert r_on.levels_fused > 0
    _archives_match(e_on, e_off)
    g = r_on.distinct_states - 1
    ta, tb = e_on.trace(g), e_off.trace(g)
    assert [l for l, _ in ta] == [l for l, _ in tb]
    assert all(sa == sb for (_, sa), (_, sb) in zip(ta, tb))


@pytest.mark.slow
def test_spill_burst_violation_and_checkpoint():
    """Spill burst: violation states identical on/off, and a
    checkpoint written mid-run by the bursting engine resumes on the
    per-level engine to the identical final counts (the checkpoint
    format is driver-agnostic)."""
    cfg = SPILL_MICRO.with_(invariants=SPILL_MICRO.invariants +
                            ("FirstBecomeLeader",))
    a = SpillEngine(cfg, store_states=False, burst=True,
                    **SPILL_KW).check(stop_on_violation=True)
    b = SpillEngine(cfg, store_states=False, burst=False,
                    **SPILL_KW).check(stop_on_violation=True)
    assert a.violations and b.violations
    assert a.violations[0].state_id == b.violations[0].state_id
    assert a.violations[0].state == b.violations[0].state

    import os
    import tempfile
    full = SpillEngine(SPILL_MICRO, store_states=False, burst=True,
                       **SPILL_KW).check()
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "sb.ckpt")
        e1 = SpillEngine(SPILL_MICRO, store_states=False, burst=True,
                         **SPILL_KW)
        part = e1.check(max_depth=6, checkpoint_path=ckpt,
                        checkpoint_every=1)
        assert part.depth == 6
        e2 = SpillEngine(SPILL_MICRO, store_states=False, burst=False,
                         **SPILL_KW)
        resumed = e2.check(resume_from=ckpt)
    assert resumed.distinct_states == full.distinct_states
    assert resumed.level_sizes == full.level_sizes
