"""The Raft spec as the first ``SpecIR`` instance.

This is a *re-homing*, not a rewrite: the model oracle
(``models/raft.py``), packed layout/codec (``ops/layout.py`` /
``ops/codec.py``), kernels (``ops/kernels.py``), device predicates
(``ops/vpredicates.py``), symmetry fingerprinter
(``engine/fingerprint.RaftFingerprinter``) and oracle explorer
(``models/explore.py``) all stay where they are — this module only
assembles them into the operator surface the engines consume, and owns
the two tables that used to be hard-wired into ``engine/expand.py``:

  * the action-family registry (``build_families``) — each family now
    carries its guard-algebra declaration (the signed-weight/threshold
    row of the PR-8 int8 guard matmul) instead of the old if/elif chain
    inside ``Expander._build_guard_matrix``; a new family without a
    declaration fails at Expander construction naming THIS spec;
  * the per-family enabled-lane density table (``FAMILY_DENSITY``) —
    the raft-measured buffer-sizing caps ``--fam-cap-density``
    overrides, now namespaced per spec.

All existing Raft differential tests pin this assembly bit-exactly:
lane order, guard weights and densities are byte-identical to the
pre-IR ``engine/expand.py`` tables.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ..config import (NEXT_ASYNC_CRASH, NEXT_DYNAMIC, NEXT_FULL, Bounds)
from . import SpecIR


# ---------------------------------------------------------------------------
# Action families (moved verbatim from engine/expand.build_families),
# now with per-family guard-algebra declarations: each ``guard`` maps
# (feature-offset table, layout, *lane params) -> ([(index, weight)],
# threshold) over ops/kernels.guard_features — the exact rows the old
# Expander._build_guard_matrix if/elif chain produced.
# ---------------------------------------------------------------------------

def build_families(lay) -> List["Family"]:
    from ..config import CANDIDATE, FOLLOWER, LEADER, NIL, VALUE_ENTRY
    from ..engine.expand import Family, d_set
    from ..ops.codec import (C_GLOBLEN, C_NLEADERS, C_NREQ, C_OVERFLOW,
                             F_BL2_SEEN, F_LAST_RESTART_POS, F_LCDCC,
                             F_MIN_RESTART_GAP, F_NJBL)
    from ..ops.kernels import RaftKernels
    cfg = lay.cfg
    kern = RaftKernels(lay)
    S, K = lay.S, lay.K
    fams: List[Family] = []

    def grid(*ranges):
        arrs = np.meshgrid(*[np.asarray(r, np.int32) for r in ranges],
                           indexing="ij")
        return tuple(a.ravel() for a in arrs)

    ij = grid(range(S), range(S))
    ij_ne = tuple(a[ij[0] != ij[1]] for a in ij)        # i != j lanes
    iv = grid(range(S), list(cfg.values))
    i_ = grid(range(S))
    k_ = grid(range(K))

    # ---- delta-algebra declarations (the scatter-as-matmul successor
    # path, engine/expand delta-matrix comment).  The slot-affine
    # majority declares its writes as (slot, source, weight) triples
    # over the flat int32 state view; the data-dependent pieces ride
    # the kernels' delta_features (ops/kernels.delta_feature_offsets).
    # Bag inserts (RequestVote/AppendEntries/...), the Receive branch
    # family and AdvanceCommitIndex's quorum/prefix scan are genuinely
    # nonlinear — they declare NO delta and transparently keep the
    # per-family kernel path.  UpdateTerm (dst-one-hot set-difference
    # features) and Restart (its min-gap min folds into a
    # pre-differenced feature) joined the affine tail in round 17.

    def d_timeout(off, lay, i):
        F, FS = off["_feat"], off["_src_f"]
        X, C = off["_src_x"], off["_const"]
        return (
            d_set(off, off["st"] + i, CANDIDATE) +
            # ct' = min(ct+1, cap): the room feature IS the increment
            [(off["ct"] + i, FS + F["ctroom"] + i, 1)] +
            d_set(off, off["vf"] + i, NIL) +
            [(off["vr"] + i, X + off["vr"] + i, -1),
             (off["vg"] + i, X + off["vg"] + i, -1),
             (off["timeout"] + i, C, 1),
             # overflow = 1 - room
             (off["ctr"] + C_OVERFLOW, C, 1),
             (off["ctr"] + C_OVERFLOW, FS + F["ctroom"] + i, -1),
             (off["ctr"] + C_GLOBLEN, C, 1)])

    def d_become_leader(off, lay, i):
        F, FS = off["_feat"], off["_src_f"]
        X, C = off["_src_x"], off["_const"]
        tr = d_set(off, off["st"] + i, LEADER)
        for j in range(lay.S):
            nij = off["ni"] + i * lay.S + j
            mij = off["mi"] + i * lay.S + j
            # ni' = 1 + llen[i]; mi' = 0
            tr += [(nij, C, 1), (nij, X + off["llen"] + i, 1),
                   (nij, X + nij, -1), (mij, X + mij, -1)]
        tr += [(off["ctr"] + C_NLEADERS, C, 1),
               # the three feat maxes, pre-differenced in the features
               (off["feat"] + F_BL2_SEEN, FS + F["bl2"] + i, 1),
               (off["feat"] + F_NJBL, FS + F["njbl"] + i, 1),
               (off["feat"] + F_LCDCC, FS + F["lcdcc"], 1),
               (off["ctr"] + C_GLOBLEN, C, 1)]
        return tr

    def d_client_request(off, lay, i, v):
        F, FS, C = off["_feat"], off["_src_f"], off["_const"]
        vb = lay.value_bits
        cv = (VALUE_ENTRY << vb) | int(v)     # the term-free entry bits
        tshift = 1 << (1 + vb)                # term field scale
        tr = []
        for p in range(lay.Lcap):
            lp = off["log"] + i * lay.Lcap + p
            fp = i * lay.Lcap + p
            # log[i, llen] = pack_entry(ct, VALUE_ENTRY, v): the llen
            # one-hot places it, × ct scales the term field, × old log
            # word cancels the overwritten value — overflow zeroes all
            tr += [(lp, FS + F["croh"] + fp, cv),
                   (lp, FS + F["crohct"] + fp, tshift),
                   (lp, FS + F["crohold"] + fp, -1)]
        tr += [(off["llen"] + i, FS + F["crroom"] + i, 1),
               (off["ctr"] + C_NREQ, C, 1),
               (off["ctr"] + C_OVERFLOW, C, 1),
               (off["ctr"] + C_OVERFLOW, FS + F["crroom"] + i, -1)]
        return tr

    def d_update_term(off, lay, k):
        # ct[dst]=mterm, st[dst]=FOLLOWER, vf[dst]=NIL: the [K, S]
        # dst-one-hot set-difference features carry (new - old) per
        # server, so each write is one ADD row per (slot, server); the
        # message is NOT consumed and glob does not advance — exactly
        # kernels.update_term
        F, FS = off["_feat"], off["_src_f"]
        tr = []
        for j in range(lay.S):
            kj = k * lay.S + j
            tr += [(off["ct"] + j, FS + F["utdct"] + kj, 1),
                   (off["st"] + j, FS + F["utdst"] + kj, 1),
                   (off["vf"] + j, FS + F["utdvf"] + kj, 1)]
        return tr

    def d_restart(off, lay, i):
        F, FS = off["_feat"], off["_src_f"]
        X, C = off["_src_x"], off["_const"]
        tr = d_set(off, off["st"] + i, FOLLOWER) + [
            (off["vr"] + i, X + off["vr"] + i, -1),
            (off["vg"] + i, X + off["vg"] + i, -1),
            (off["ci"] + i, X + off["ci"] + i, -1)]
        for j in range(lay.S):
            nij = off["ni"] + i * lay.S + j
            mij = off["mi"] + i * lay.S + j
            # ni' = 1; mi' = 0 (nextIndex/matchIndex reset)
            tr += [(nij, C, 1), (nij, X + nij, -1),
                   (mij, X + mij, -1)]
        tr += [(off["restarted"] + i, C, 1),
               # last_restart_pos' = globlen + 1 (set via cancel-old)
               (off["feat"] + F_LAST_RESTART_POS, C, 1),
               (off["feat"] + F_LAST_RESTART_POS,
                X + off["ctr"] + C_GLOBLEN, 1),
               (off["feat"] + F_LAST_RESTART_POS,
                X + off["feat"] + F_LAST_RESTART_POS, -1),
               # min_restart_gap' = min(old, gap): pre-differenced
               (off["feat"] + F_MIN_RESTART_GAP, FS + F["rgap"], 1),
               (off["ctr"] + C_GLOBLEN, C, 1)]
        return tr

    def d_duplicate(off, lay, k):
        return [(off["cnt"] + k, off["_const"], 1)]

    fams.append(Family(
        "RequestVote", kern.request_vote, ij,
        lambda i, j: f"RequestVote({i},{j})",
        guard=lambda off, lay, i, j: (
            [(off["cand"] + i, 1), (off["needvote"] + i * lay.S + j, 1)],
            2)))
    fams.append(Family(
        "BecomeLeader", kern.become_leader, i_,
        lambda i: f"BecomeLeader({i})",
        guard=lambda off, lay, i: (
            [(off["cand"] + i, 1), (off["blq"] + i, 1)], 2),
        delta=d_become_leader))
    fams.append(Family(
        "ClientRequest", kern.client_request, iv,
        lambda i, v: f"ClientRequest({i},{v})",
        guard=lambda off, lay, i, v: ([(off["leader"] + i, 1)], 1),
        delta=d_client_request))
    fams.append(Family(
        "AdvanceCommitIndex", kern.advance_commit_index, i_,
        lambda i: f"AdvanceCommitIndex({i})",
        guard=lambda off, lay, i: ([(off["leader"] + i, 1)], 1)))
    fams.append(Family(
        "AppendEntries", kern.append_entries, ij_ne,
        lambda i, j: f"AppendEntries({i},{j})",
        guard=lambda off, lay, i, j: (
            [(off["leader"] + i, 1), (off["cfg"] + i * lay.S + j, 1)],
            2)))
    fams.append(Family(
        "UpdateTerm", kern.update_term, k_,
        lambda k: f"UpdateTerm[slot{k}]",
        guard=lambda off, lay, k: ([(off["ut"] + k, 1)], 1),
        delta=d_update_term))
    fams.append(Family(
        "CocDiscard", kern.coc_discard, k_,
        lambda k: f"CocDiscard[slot{k}]",
        guard=lambda off, lay, k: ([(off["cocd"] + k, 1)], 1)))
    fams.append(Family(
        "Receive", kern.receive_main, k_,
        lambda k: f"Receive[slot{k}]",
        guard=lambda off, lay, k: ([(off["recv"] + k, 1)], 1)))
    fams.append(Family(
        "Timeout", kern.timeout, i_,
        lambda i: f"Timeout({i})",
        guard=lambda off, lay, i: (
            [(off["folc"] + i, 1), (off["cfg"] + i * lay.S + i, 1)], 2),
        delta=d_timeout))
    if cfg.next_family in (NEXT_ASYNC_CRASH, NEXT_FULL, NEXT_DYNAMIC):
        fams.append(Family(
            "Restart", lambda sv, der, i: kern.restart(sv, i), i_,
            lambda i: f"Restart({i})",
            guard=lambda off, lay, i: ([], 0),    # unconditional
            delta=d_restart))
    if cfg.next_family in (NEXT_FULL, NEXT_DYNAMIC):
        fams.append(Family(
            "Duplicate", lambda sv, der, k: kern.duplicate_message(sv, k),
            k_, lambda k: f"Duplicate[slot{k}]",
            guard=lambda off, lay, k: ([(off["cnt1"] + k, 1)], 1),
            delta=d_duplicate))
        # no delta declaration: a drop closes its slot's gap, shifting
        # the slots above it (ops/kernels.py bag_del_slot)
        fams.append(Family(
            "Drop", lambda sv, der, k: kern.drop_message(sv, k),
            k_, lambda k: f"Drop[slot{k}]",
            guard=lambda off, lay, k: ([(off["cnt1"] + k, 1)], 1)))
    if cfg.next_family == NEXT_DYNAMIC:
        fams.append(Family(
            "AddNewServer", kern.add_new_server, ij,
            lambda i, j: f"AddNewServer({i},{j})",
            # j ∉ config enters with weight -1 and no threshold share
            guard=lambda off, lay, i, j: (
                [(off["leader"] + i, 1),
                 (off["cfg"] + i * lay.S + j, -1)], 1)))
        fams.append(Family(
            "DeleteServer", kern.delete_server, ij_ne,
            lambda i, j: f"DeleteServer({i},{j})",
            guard=lambda off, lay, i, j: (
                [(off["leader"] + i, 1), (off["folc"] + j, 1),
                 (off["cfg"] + i * lay.S + j, 1)], 3)))
    return fams


# Expected enabled-lane density per parent state, by family (measured
# on the BASELINE configs; engine/expand sizes the per-family
# materialization buffers from these — cap_f = chunk * min(lanes, d)).
# Throughput tuning, not correctness bounds: overflow grows + replays.
FAMILY_DENSITY = {
    "Restart": 1 << 30, "Timeout": 1 << 30,
    "RequestVote": 2, "BecomeLeader": 1, "ClientRequest": 2,
    "AdvanceCommitIndex": 2, "AppendEntries": 2,
    "UpdateTerm": 2, "CocDiscard": 1, "Receive": 4,
    "Duplicate": 4, "Drop": 4, "AddNewServer": 2, "DeleteServer": 2,
}


# ---------------------------------------------------------------------------
# The sim engine's punctuated-restart progress ladder (moved from
# sim/walker._progress_T): leader elected < membership changes appended
# < latest-ConfigEntry replication count at a current leader.
# ---------------------------------------------------------------------------

_SCORE_LEADER = 1 << 20
_SCORE_NMC = 1 << 10


def sim_progress(kern, lay):
    import jax
    import jax.numpy as jnp

    from ..config import LEADER
    from ..ops.codec import C_NLEADERS, C_NMC

    def score(svT):
        derT = jax.vmap(kern.derived, in_axes=-1, out_axes=-1)(svT)
        leader_seen = (svT["ctr"][C_NLEADERS] > 0).astype(jnp.int32)
        nmc = svT["ctr"][C_NMC]
        maxcfg = derT["maxcfg"]                       # [S, W]
        repl = jnp.sum(svT["mi"] >= maxcfg[:, None, :],
                       axis=1, dtype=jnp.int32)       # [S, W]
        is_l = (svT["st"] == LEADER) & (maxcfg > 0)
        repl = jnp.max(jnp.where(is_l, repl, 0), axis=0)
        return leader_seen * _SCORE_LEADER + \
            jnp.minimum(nmc, _SCORE_LEADER // _SCORE_NMC - 1) * \
            _SCORE_NMC + jnp.minimum(repl, _SCORE_NMC - 1)

    return score


# Which search bound each Bounded* constraint guards: a bound may pad
# up to the serving ceiling ONLY while its constraint is active —
# without the constraint the bound is load-bearing in the kernels'
# representability clamps (ops/kernels.term_cap), and padding it would
# change the reachable set.  An inactive constraint keeps the bound
# exact in the ceiling, so such jobs simply bucket by exact value.
_BOUND_CONSTRAINTS = {
    "max_log_length": "BoundedLogSize",
    "max_restarts": "BoundedRestarts",
    "max_timeouts": "BoundedTimeouts",
    "max_terms": "BoundedTerms",
    "max_client_requests": "BoundedClientRequests",
    "max_tried_membership_changes": "BoundedTriedMembershipChanges",
    "max_membership_changes": "BoundedMembershipChanges",
}


def serve_bucket(cfg):
    """Bucket ceiling for the batched serving layer (serve/batch).

    Round 13 — constant-padding ceilings: every constraint-guarded
    search bound pads up to the shared rung ladder (``spec.pad_rung``)
    so heterogeneous tenants (differing MaxTerm/MaxTimeouts/... under
    the stock constraint set) land in ONE bucket and share one
    AOT-compiled program.  The int8 guard matrix, the delta matrices
    and the packed layout compile at the CEILING's widths; each job's
    own bounds ride the runtime-bounds vector (``serve_runtime``
    below) into the constraint predicates, and the witness-bearing
    clamps (terms, log room) stay at the ceiling's representability
    width — exact, because a constraint-pruned state is never expanded
    in either layout, so an in-bounds job can never reach a clamp.

    Bounds WITHOUT their guarding constraint stay exact in the ceiling
    (see _BOUND_CONSTRAINTS); structural constants (servers, values,
    NEXT family, rounds, the predicate name lists, symmetry/fp128)
    always key the bucket exactly — padding those would change the
    compiled operator surface itself.  max_trace stays exact too: it
    backs the BoundedTrace *scenario invariant*, whose verdict is part
    of the job's answer.

    The params size the per-job rings for small serving jobs: ring =
    4 * chunk frontier rows per job, a 2^15-slot visited table
    (~13k keys at the 0.40 load bound).  A job outgrowing either bails
    to the sequential fallback."""
    from . import pad_rung
    b = cfg.bounds
    cons = set(cfg.constraints)

    def pad(name):
        # floor 4: every bound in the small-serving range rounds onto
        # ONE rung (raft bound padding only widens bit-packed fields,
        # so a generous floor is near-free and maximizes bucket hits)
        v = getattr(b, name)
        return pad_rung(v, floor=4) if _BOUND_CONSTRAINTS[name] in cons \
            else v

    ceiling_bounds = Bounds(
        max_log_length=pad("max_log_length"),
        max_restarts=pad("max_restarts"),
        max_timeouts=pad("max_timeouts"),
        max_client_requests=pad("max_client_requests"),
        max_membership_changes=pad("max_membership_changes"),
        max_terms=pad("max_terms"),
        max_tried_membership_changes=pad(
            "max_tried_membership_changes"),
        max_trace=b.max_trace)
    kw = {}
    if ceiling_bounds != b:
        kw["bounds"] = ceiling_bounds
    if cfg.max_inflight_override is not None and \
            "BoundedInFlightMessages" in cons:
        # the override is a real bound (shape-bearing via bag slots):
        # pad it like the rest; the derived 2*S^2 default is a formula
        # over the structural |Server| and stays as-is
        padded = pad_rung(cfg.max_inflight_override)
        if padded != cfg.max_inflight_override:
            kw["max_inflight_override"] = padded
    ceiling = cfg.with_(**kw) if kw else cfg
    return ceiling, dict(chunk=128, vcap=1 << 15, burst_levels=8)


def serve_runtime(expander, cfg):
    """The job's runtime-thresholds data under the bucket's ceiling
    expander (SpecIR.serve_runtime contract): ceiling guard thresholds
    as device data, an all-enabled lane mask (raft lane grids are
    structural — servers/values/bag slots — and bag-slot lanes must
    stay enabled even under a padded MaxInFlight, since occupancy of
    the first K_job slots drives them), and the job's own search
    bounds for the constraint predicates."""
    from ..ops.vpredicates import runtime_bounds
    thr, mask = expander.runtime_thresholds()
    return dict(thr=thr, mask=mask, bounds=runtime_bounds(cfg))


# ---------------------------------------------------------------------------
# IR assembly
# ---------------------------------------------------------------------------

def build_ir() -> SpecIR:
    from ..models import predicates as OP
    from ..models.explore import (_walk_key, explore, symmetry_perms)
    from ..models.golden import prefix_pin_seeds
    from ..models.raft import (init_state, state_from_obj, state_to_obj,
                               successors)
    from ..ops import codec
    from ..ops.layout import Layout
    from ..ops.kernels import RaftKernels
    from ..ops.vpredicates import (CONSTRAINTS as VC, INVARIANTS as VI,
                                   Predicates, SCENARIO_PROPERTIES)

    def make_fingerprinter(cfg, sym_canon="minperm"):
        from ..engine.fingerprint import RaftFingerprinter
        return RaftFingerprinter(cfg, sym_canon=sym_canon)

    def server_signature(fpr, svT, prep):
        from ..engine.fingerprint import raft_server_signature
        return raft_server_signature(fpr, svT, prep)

    return SpecIR(
        name="raft",
        version=1,
        make_layout=Layout,
        init_state=init_state,
        encode=codec.encode,
        decode=codec.decode,
        narrow=codec.narrow,
        widen=codec.widen,
        view_keys=codec.VIEW_KEYS,
        nonview_keys=codec.NONVIEW_KEYS,
        state_to_obj=state_to_obj,
        state_from_obj=state_from_obj,
        make_kernels=RaftKernels,
        build_families=build_families,
        family_density=dict(FAMILY_DENSITY),
        make_predicates=Predicates,
        scenario_properties=SCENARIO_PROPERTIES,
        known_invariants=frozenset(VI) | frozenset(OP.INVARIANTS),
        known_constraints=frozenset(VC) | frozenset(OP.CONSTRAINTS),
        known_action_constraints=frozenset(OP.ACTION_CONSTRAINTS),
        glob_dependent=frozenset(OP.GLOB_DEPENDENT),
        make_fingerprinter=make_fingerprinter,
        symmetry_perms=symmetry_perms,
        server_signature=server_signature,
        oracle_explore=explore,
        oracle_successors=successors,
        oracle_walk_key=_walk_key,
        prefix_pin_seeds=prefix_pin_seeds,
        sim_progress=sim_progress,
        default_config=None,
        serve_bucket=serve_bucket,
        serve_runtime=serve_runtime,
    )
