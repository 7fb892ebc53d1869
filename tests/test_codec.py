"""Codec round-trip: oracle states -> SoA arrays -> oracle states."""

import pytest

from raft_tla_tpu.config import (Bounds, ModelConfig, NEXT_DYNAMIC,
                                 NEXT_FULL)
from raft_tla_tpu.models.explore import explore
from raft_tla_tpu.models.raft import init_state
from raft_tla_tpu.ops.codec import decode, encode, features_from_hist
from raft_tla_tpu.ops.layout import Layout


def reachable_states(cfg, max_states=4000):
    res = explore(cfg, max_states=max_states, keep_states=True)
    return list(res.states.values())


SMALL = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    bounds=Bounds.make(max_log_length=2, max_timeouts=2),
    symmetry=False)

UNRELIABLE = SMALL.with_(next_family=NEXT_FULL)

MEMBER = ModelConfig(
    n_servers=3, init_servers=(0, 1), values=(1,),
    next_family=NEXT_DYNAMIC,
    bounds=Bounds.make(max_log_length=2, max_timeouts=2),
    symmetry=False)


@pytest.mark.parametrize("cfg", [SMALL, UNRELIABLE, MEMBER],
                         ids=["small", "unreliable", "membership"])
def test_roundtrip(cfg):
    lay = Layout(cfg)
    states = reachable_states(cfg)
    assert len(states) > 50
    # make sure the sample exercises logs and bags
    assert any(s.msgs for s, _h in states)
    assert any(any(s.log) for s, _h in states)
    for sv, h in states:
        arrs = encode(lay, sv, h)
        sv2, h2 = decode(lay, arrs)
        assert sv2 == sv
        assert h2.restarted == h.restarted and h2.timeout == h.timeout
        assert (h2.nleaders, h2.nreq, h2.ntried, h2.nmc) == \
            (h.nleaders, h.nreq, h.ntried, h.nmc)


def test_membership_msgs_roundtrip():
    """Catchup/CheckOldConfig messages (incl. the absent-mcommitIndex
    follow-up CatchupRequest) must round-trip with field-set identity."""
    from raft_tla_tpu.config import MT_CATREQ, MT_CATRESP, MT_COC
    cfg = MEMBER
    lay = Layout(cfg)
    res = explore(cfg, max_states=4000, keep_states=True)
    seen_types = set()
    for sv, h in res.states.values():
        for m, _c in sv.msgs:
            seen_types.add(m[0])
        arrs = encode(lay, sv, h)
        sv2, _h2 = decode(lay, arrs)
        assert sv2.msgs == sv.msgs
    assert MT_CATREQ in seen_types
    assert MT_COC in seen_types


def test_feature_lanes_match_oracle_predicates():
    """The incremental feature lanes must agree with a direct reading of
    the oracle global history."""
    from raft_tla_tpu.ops import codec as C
    cfg = SMALL
    res = explore(cfg, max_states=1500, keep_states=True)
    states = list(res.states.values())
    # BFS at this size does not reach a commit; the scenario-property
    # machinery itself finds one (EntryCommitted as negated reachability,
    # raft.tla:1160-1163) so the CommitEntry feature path is exercised.
    deep = explore(cfg.with_(invariants=("EntryCommitted",)),
                   stop_on_violation=True)
    assert deep.violations
    states.append((deep.violations[0].state, deep.violations[0].hist))
    n_commit = 0
    for sv, h in states:
        feat = features_from_hist(h)
        assert feat[C.F_COMMIT_SEEN] == int(
            any(r[0] == "CommitEntry" for r in h.glob))
        restarts = [k + 1 for k, r in enumerate(h.glob)
                    if r[0] == "Restart"]
        assert feat[C.F_LAST_RESTART_POS] == (restarts[-1] if restarts
                                              else 0)
        if len(restarts) >= 2:
            assert feat[C.F_MIN_RESTART_GAP] == min(
                b - a for a, b in zip(restarts, restarts[1:]))
        n_commit += feat[C.F_COMMIT_SEEN]
    assert n_commit > 0


def test_membership_feature_lanes_match_oracle_predicates():
    """The membership feature lanes must agree with the oracle scenario
    predicates (models/predicates.py) that read the full glob history."""
    from raft_tla_tpu.ops import codec as C
    from raft_tla_tpu.models import predicates as P
    cfg = MEMBER
    # Scenario machinery produces histories with AddServer /
    # CommitMembershipChange / BecomeLeader interleavings.
    samples = []
    for target in ("AddSucessful", "MembershipChangeCommits"):
        res = explore(cfg.with_(invariants=(target,)),
                      stop_on_violation=True, max_states=300_000)
        assert res.violations, f"no witness found for {target}"
        samples.append((res.violations[0].state, res.violations[0].hist))
    res = explore(cfg, max_states=3000, keep_states=True)
    samples.extend(res.states.values())
    # Deeper interleavings (NewlyJoinedBecomeLeader / LeaderChanges...) are
    # beyond a quick BFS; exercise those lanes with synthetic histories.
    sv0, h0 = init_state(cfg)
    synth = [
        (("AddServer", 0, 2), ("BecomeLeader", 2, 0b100)),          # NJBL
        (("AddServer", 0, 2), ("BecomeLeader", 1, 0b010)),          # LCDCC
        (("AddServer", 0, 2), ("CommitMembershipChange", 0, 0b111),
         ("BecomeLeader", 1, 0b010)),                               # neither
        (("AddServer", 0, 2), ("CommitMembershipChange", 0, 0b111),
         ("AddServer", 0, 3), ("BecomeLeader", 2, 0b100)),          # NJBL only
    ]
    samples.extend((sv0, h0._replace(glob=g)) for g in synth)
    seen_added = False
    for sv, h in samples:
        feat = features_from_hist(h)
        added = 0
        for r in h.glob:
            if r[0] == "AddServer":
                added |= 1 << r[2]
        assert feat[C.F_ADDED_SET] == added
        assert feat[C.F_MC_COMMITS] == sum(
            1 for r in h.glob if r[0] == "CommitMembershipChange")
        seen_added = seen_added or added != 0
        # feature-lane forms of the oracle predicates
        assert (feat[C.F_ADD_COMMITS] == 0) == P.add_commits(sv, h, cfg)
        assert (feat[C.F_NJBL] == 0) == \
            P.newly_joined_become_leader(sv, h, cfg)
        assert (feat[C.F_LCDCC] == 0) == \
            P.leader_changes_during_conf_change(sv, h, cfg)
    assert seen_added


def test_narrow_widen_roundtrip_and_fp_parity():
    """Engines store rows in codec.narrow_dtypes; narrowing must be
    lossless under the configured bounds and the fingerprint must be
    bit-identical on narrow and wide rows (the engines fingerprint
    wide rows but store and re-load narrow ones)."""
    import jax
    import numpy as np
    from raft_tla_tpu.engine.fingerprint import Fingerprinter
    from raft_tla_tpu.ops.codec import narrow, widen, stack

    import jax.numpy as jnp

    cfg = MEMBER.with_(symmetry=True)
    lay = Layout(cfg)
    arrs = stack([encode(lay, s, h)
                  for (s, h) in reachable_states(cfg, 250)[:200]])
    nar = narrow(lay, arrs)
    assert nar["ct"].dtype == np.int8
    assert nar["log"].dtype in (np.int8, np.int16)
    assert nar["bag"].dtype == np.uint32 and nar["ctr"].dtype == np.int32
    wide = widen(nar)
    for k in arrs:
        assert (np.asarray(wide[k]) == arrs[k]).all(), k
    fpr = Fingerprinter(cfg)
    fp_w = np.asarray(jax.jit(fpr.fingerprint_batch)(
        {k: jnp.asarray(v) for k, v in arrs.items()}))
    fp_n = np.asarray(jax.jit(fpr.fingerprint_batch)(
        {k: jnp.asarray(v) for k, v in nar.items()}))
    assert (fp_w == fp_n).all()


def test_fingerprint_batch_matches_per_state():
    """The batch-minor fingerprint formulation (the engine's hot path)
    is bit-identical to the per-state reference formulation, for both
    64- and 128-bit streams."""
    import jax
    import jax.numpy as jnp

    from raft_tla_tpu.config import NEXT_ASYNC_CRASH, Bounds, ModelConfig
    from raft_tla_tpu.engine.fingerprint import Fingerprinter
    from raft_tla_tpu.models.explore import explore

    cfg = ModelConfig(
        n_servers=3, init_servers=(0, 1, 2), values=(1, 2),
        next_family=NEXT_ASYNC_CRASH, symmetry=True,
        max_inflight_override=6,
        bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                           max_client_requests=1))
    import numpy as np
    r = explore(cfg, max_states=2000, keep_states=True)
    lay = Layout(cfg)
    pairs = list(r.states.values())[:256]
    arrs = [encode(lay, sv, h) for sv, h in pairs]
    svb = {k: jnp.asarray(np.stack([a[k] for a in arrs]))
           for k in arrs[0]}
    for variant in (cfg, cfg.with_(fp128=True)):
        fpr = Fingerprinter(variant)
        ref = np.asarray(
            jax.jit(lambda s: jax.vmap(fpr.fingerprint)(s))(svb))
        got = np.asarray(jax.jit(fpr.fingerprint_batch)(svb))
        assert np.array_equal(ref, got)
