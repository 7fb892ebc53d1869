"""Vectorizable action kernels: the Next-relation as pure jnp functions.

Each kernel maps a *single* state (the SoA dict of ops/codec.py) plus
static-shaped parameters to ``(ok, state')`` — ``ok`` is the action's
enabling guard; when False the returned state is garbage and the engine
masks it out.  The engine vmaps kernels over the frontier axis and over
parameter grids (SURVEY §7.2 L1/L2).

Semantics contract: models/raft.py (the oracle), which cites the reference
spec line-by-line; every kernel here names its oracle twin.  Differential
tests (tests/test_kernels.py) assert successor-set equality on reachable
states.

Control-flow style: no data-dependent Python branching — guards become
masks, the AppendEntries branch family (raft.tla:617-683) becomes nested
``jnp.where`` selects (the branches are mutually exclusive, SURVEY §2.5),
and variable-length log/bag ops become masked gathers/scatters over static
Lcap/K extents.  History counters and scenario feature lanes are updated
in-kernel (they are inputs to constraints, SURVEY §2.2); the global history
*sequence* lives host-side and only its length rides along.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..config import (CANDIDATE, CONFIG_ENTRY, FOLLOWER, LEADER, MT_AEREQ,
                      MT_AERESP, MT_CATREQ, MT_CATRESP, MT_COC, MT_RVREQ,
                      MT_RVRESP, NIL, VALUE_ENTRY)
from .codec import (C_GLOBLEN, C_NLEADERS, C_NMC, C_NREQ, C_NTRIED,
                    C_OVERFLOW, F_ADD_COMMITS, F_ADDED_SET, F_BL2_SEEN,
                    F_COMMIT_SEEN, F_CWCL_POS, F_LAST_RESTART_POS, F_LCDCC,
                    F_MC_COMMITS, F_MIN_RESTART_GAP, F_NJBL, F_OPEN_ADD,
                    NO_GAP)
from . import layout as layout_mod
from .layout import Layout, get_field, put_field

State = Dict[str, jnp.ndarray]


def popcount(x, nbits):
    """Popcount over the low ``nbits`` of small server bitmasks."""
    x = jnp.asarray(x)
    total = jnp.zeros_like(x)
    for k in range(nbits):
        total = total + ((x >> k) & 1)
    return total


def select_enabled(ok, u):
    """The ``u``-th enabled lane of a flat guard mask (0-based), or -1
    when no lane is enabled.

    This is the random-walk engine's sampling kernel (sim/walker.py):
    ``ok`` is one state's [A] enabling-guard vector over the expander's
    lane grid and ``u`` a uniform draw in [0, sum(ok)), so picking the
    u-th set bit IS the uniform choice over enabled (action, server,
    param) lanes — the same successor surface TLC's ``-simulate`` mode
    samples uniformly.  One cumsum + argmax, no data-dependent shapes,
    so it vmaps over walker fleets."""
    csum = jnp.cumsum(ok.astype(jnp.int32))
    idx = jnp.argmax(csum > u).astype(jnp.int32)
    return jnp.where(csum[-1] > 0, idx, jnp.int32(-1))


class RaftKernels:
    """Kernel family bound to one (Layout, ModelConfig)."""

    def __init__(self, lay: Layout):
        self.lay = lay
        self.cfg = lay.cfg
        self.S = lay.S
        self.Lmax = lay.Lmax
        self.Lcap = lay.Lcap
        self.K = lay.K

    @property
    def term_cap(self) -> int:
        """The term REPRESENTABILITY clamp: the packing holds
        max_terms + 1 (the one unconstrained step past BoundedTerms).
        This is a property of the LAYOUT's bounds, deliberately NOT of
        any per-job runtime bound: under a padded serving ceiling
        (spec serve_bucket, round 13) the job's BoundedTerms rides the
        runtime-bounds vector while this clamp stays at the ceiling's
        width — exact, because constraint-pruned states are never
        expanded, so an in-bounds job can never reach the clamp in
        either layout."""
        return self.cfg.bounds.max_terms + 1

    # ------------------------------------------------------------------
    # Derived per-state quantities (recomputed once per expansion)
    # ------------------------------------------------------------------

    def derived(self, sv: State) -> State:
        lay = self.lay
        log = sv["log"]                                   # [S, Lcap]
        etype = (log >> lay.value_bits) & 1
        occupied = log != 0
        is_cfg = (etype == CONFIG_ENTRY) & occupied
        pos = jnp.arange(1, self.Lcap + 1, dtype=jnp.int32)
        # GetMaxConfigIndex (raft.tla:346-351), 1-based, 0 if none
        maxcfg = jnp.max(jnp.where(is_cfg, pos, 0), axis=1)
        payload = log & ((1 << lay.value_bits) - 1)
        cfg_payload = jnp.take_along_axis(
            payload, jnp.maximum(maxcfg - 1, 0)[:, None], axis=1)[:, 0]
        # GetConfig (raft.tla:354-360): latest ConfigEntry else InitServer
        config = jnp.where(maxcfg > 0, cfg_payload,
                           jnp.int32(self.cfg.init_mask))
        lastterm = jnp.where(
            sv["llen"] > 0,
            self.entry_term(jnp.take_along_axis(
                log, jnp.maximum(sv["llen"] - 1, 0)[:, None], axis=1)[:, 0]),
            0)
        leaders = jnp.sum(
            jnp.where(sv["st"] == LEADER,
                      jnp.int32(1) << jnp.arange(self.S), 0))
        return {"config": config, "maxcfg": maxcfg, "lastterm": lastterm,
                "leaders": leaders}

    def guard_feature_offsets(self) -> Dict[str, int]:
        """The SpecIR kernels contract: the flat layout of this spec's
        ``guard_features`` vector (module-level table below)."""
        return guard_feature_offsets(self.lay)

    # ------------------------------------------------------------------
    # Entry / message packing helpers (device side)
    # ------------------------------------------------------------------

    # single source of truth for the entry bit layout: ops/layout.py
    def entry_term(self, e):
        return layout_mod.entry_term(self.lay, e)

    def entry_type(self, e):
        return layout_mod.entry_type(self.lay, e)

    def entry_payload(self, e):
        return layout_mod.entry_payload(self.lay, e)

    def pack_entry(self, term, etype, payload):
        return layout_mod.pack_entry(self.lay, term, etype, payload)

    def pack_msg(self, mtype, mterm, msrc, mdst, a=-1, b=-1, c=-1,
                 ent=None, entlen=0):
        """Build u32[msg_words].  An absent a/b/c (-1) packs as the
        field's all-ones value (codec.pack_msg is the host twin)."""
        lay = self.lay
        hs = lay.header_shifts
        w0 = (put_field(jnp.int32(mtype), hs["mtype"]) |
              put_field(mterm, hs["mterm"]) |
              put_field(msrc, hs["msrc"]) | put_field(mdst, hs["mdst"]) |
              put_field(jnp.asarray(a, jnp.int32), hs["a"]) |
              put_field(jnp.asarray(b, jnp.int32), hs["b"]) |
              put_field(jnp.asarray(c, jnp.int32), hs["c"]) |
              put_field(jnp.asarray(entlen, jnp.int32), hs["entlen"]))
        words = [w0.astype(jnp.uint32)]
        epw = lay.entries_per_word
        for w in range(1, lay.msg_words):
            acc = jnp.uint32(0)
            for k in range((w - 1) * epw, min(w * epw, self.Lmax)):
                e = ent[k] if ent is not None else jnp.int32(0)
                live = jnp.asarray(k < entlen, jnp.uint32)
                acc = acc | (live * e.astype(jnp.uint32)
                             << lay.entry_shift(k))
            words.append(acc)
        return jnp.stack(words)

    def msg_fields(self, words):
        """u32[msg_words] -> dict of i32 header fields + ent[Lmax]."""
        lay = self.lay
        hs = lay.header_shifts
        w0 = words[0]
        f = {name: get_field(w0, hs[name]).astype(jnp.int32)
             for name in ("mtype", "mterm", "msrc", "mdst", "entlen")}
        for name in ("a", "b", "c"):
            v = get_field(w0, hs[name]).astype(jnp.int32)
            f[name] = jnp.where(v == (1 << hs[name][1]) - 1, -1, v)
        mask = (1 << lay.entry_bits) - 1
        ent = [((words[1 + k // lay.entries_per_word] >> lay.entry_shift(k))
                & mask).astype(jnp.int32) for k in range(self.Lmax)]
        f["ent"] = jnp.stack(ent) if ent else jnp.zeros(0, jnp.int32)
        return f

    # ------------------------------------------------------------------
    # Bag ops (TypedBags (+)/(-), raft.tla:226-231).  The occupied slots
    # are a prefix of the bag in message order (ops/layout.py docstring):
    # the lanes that walk the bag walk it as the reference does, so BFS
    # keeps the reference's member of a VIEW class.
    # ------------------------------------------------------------------

    def bag_put(self, sv: State, words) -> State:
        """WithMessage: +1 count, merging into the slot of the same
        message, else inserting at its rank and shifting the slots above
        it up one; a full bag faults."""
        bag, cnt = sv["bag"], sv["cnt"]
        lay = self.lay
        occ = cnt > 0
        kb, kw = lay.msg_key(bag), lay.msg_key(words)
        # one pass over the slots: equal to the new message, and below it
        same = occ
        below = jnp.zeros_like(occ)
        for w in range(lay.msg_words):
            below = below | (same & (kb[w] < kw[w]))
            same = same & (kb[w] == kw[w])
        exists = jnp.any(same)
        rank = jnp.sum(below, dtype=jnp.int32)
        overflow = (~exists) & occ[-1]
        pos = jnp.arange(self.K, dtype=jnp.int32)
        up_bag = jnp.concatenate([jnp.zeros_like(bag[:1]), bag[:-1]])
        up_cnt = jnp.concatenate([jnp.zeros_like(cnt[:1]), cnt[:-1]])
        ins_bag = jnp.where((pos < rank)[:, None], bag,
                            jnp.where((pos == rank)[:, None],
                                      words[None, :], up_bag))
        ins_cnt = jnp.where(pos < rank, cnt,
                            jnp.where(pos == rank, 1, up_cnt))
        sv2 = dict(sv)
        sv2["bag"] = jnp.where(exists | overflow, bag, ins_bag)
        sv2["cnt"] = jnp.where(overflow, cnt,
                               jnp.where(exists, cnt + same, ins_cnt))
        sv2["ctr"] = sv["ctr"].at[C_OVERFLOW].add(overflow.astype(jnp.int32))
        return sv2

    def bag_del_slot(self, sv: State, slot) -> State:
        """WithoutMessage on a known slot: -1 count; at 0 the message
        leaves the bag (TypedBags (-) removes zero-count elements,
        TypedBags.tla:59-69) and the slots above it shift down one."""
        bag, cnt = sv["bag"], sv["cnt"]
        gone = cnt[slot] == 1
        shift = gone & (jnp.arange(self.K, dtype=jnp.int32) >= slot)
        dn_bag = jnp.concatenate([bag[1:], jnp.zeros_like(bag[:1])])
        dn_cnt = jnp.concatenate([cnt[1:], jnp.zeros_like(cnt[:1])])
        sv2 = dict(sv)
        sv2["cnt"] = jnp.where(shift, dn_cnt, cnt.at[slot].add(-1))
        sv2["bag"] = jnp.where(shift[:, None], dn_bag, bag)
        return sv2

    # ------------------------------------------------------------------
    # History / feature helpers
    # ------------------------------------------------------------------

    def _bump(self, sv: State, ctr_idx: int, n=1) -> State:
        sv2 = dict(sv)
        sv2["ctr"] = sv2["ctr"].at[ctr_idx].add(n)
        return sv2

    def _glob(self, sv: State, n) -> State:
        return self._bump(sv, C_GLOBLEN, n)

    # ------------------------------------------------------------------
    # Top-level actions (oracle: models/raft.py; SURVEY §2.4)
    # ------------------------------------------------------------------

    def restart(self, sv: State, i) -> Tuple[jnp.ndarray, State]:
        """Oracle restart(); raft.tla:401-411."""
        sv2 = dict(sv)
        sv2["st"] = sv["st"].at[i].set(FOLLOWER)
        sv2["vr"] = sv["vr"].at[i].set(0)
        sv2["vg"] = sv["vg"].at[i].set(0)
        sv2["ni"] = sv["ni"].at[i].set(jnp.ones(self.S, jnp.int32))
        sv2["mi"] = sv["mi"].at[i].set(jnp.zeros(self.S, jnp.int32))
        sv2["ci"] = sv["ci"].at[i].set(0)
        sv2["restarted"] = sv["restarted"].at[i].add(1)
        # Restart record position feeds MajorityOfClusterRestarts
        # (raft.tla:1212-1226)
        pos = sv["ctr"][C_GLOBLEN] + 1
        last = sv["feat"][F_LAST_RESTART_POS]
        gap = jnp.where(last > 0, pos - last, jnp.int32(NO_GAP))
        feat = sv["feat"].at[F_LAST_RESTART_POS].set(pos)
        feat = feat.at[F_MIN_RESTART_GAP].min(gap)
        sv2["feat"] = feat
        sv2 = self._glob(sv2, 1)
        return jnp.bool_(True), sv2

    def timeout(self, sv: State, der, i) -> Tuple[jnp.ndarray, State]:
        """Oracle timeout(); raft.tla:415-427."""
        ok = ((sv["st"][i] == FOLLOWER) | (sv["st"][i] == CANDIDATE)) \
            & (((der["config"][i] >> i) & 1) == 1)
        sv2 = dict(sv)
        sv2["st"] = sv["st"].at[i].set(CANDIDATE)
        # term-width capacity guard: packing holds max_terms + 1 (the one
        # unconstrained step past BoundedTerms); beyond that, fault AND
        # clamp so the state stays representable (the sibling overflow
        # guards' contract) — reachable only when BoundedTerms is disabled
        # (e.g. the apalache variant cfg) with too small a Bounds.max_terms
        cap = self.term_cap
        overflow = sv["ct"][i] + 1 > cap
        sv2["ct"] = sv["ct"].at[i].set(
            jnp.minimum(sv["ct"][i] + 1, cap))
        sv2["vf"] = sv["vf"].at[i].set(NIL)
        sv2["vr"] = sv["vr"].at[i].set(0)
        sv2["vg"] = sv["vg"].at[i].set(0)
        sv2["timeout"] = sv["timeout"].at[i].add(1)
        sv2["ctr"] = sv2["ctr"].at[C_OVERFLOW].add(overflow.astype(jnp.int32))
        sv2 = self._glob(sv2, 1)
        return ok, sv2

    def request_vote(self, sv: State, der, i, j) -> Tuple[jnp.ndarray, State]:
        """Oracle request_vote(); raft.tla:431-440 (includes j = i)."""
        ok = (sv["st"][i] == CANDIDATE) & \
            ((((der["config"][i] & ~sv["vr"][i]) >> j) & 1) == 1)
        words = self.pack_msg(MT_RVREQ, sv["ct"][i], i, j,
                              a=der["lastterm"][i], b=sv["llen"][i])
        sv2 = self.bag_put(sv, words)
        sv2 = self._glob(sv2, 1)
        return ok, sv2

    def append_entries(self, sv: State, der, i, j) \
            -> Tuple[jnp.ndarray, State]:
        """Oracle append_entries(); raft.tla:446-468 (≤1 entry)."""
        ok = (sv["st"][i] == LEADER) & \
            (((der["config"][i] >> j) & 1) == 1)       # i != j is static
        nij = sv["ni"][i, j]
        prev_idx = nij - 1
        in_range = (prev_idx > 0) & (prev_idx <= sv["llen"][i])
        prev_term = jnp.where(
            in_range,
            self.entry_term(sv["log"][i, jnp.clip(prev_idx - 1, 0,
                                                  self.Lcap - 1)]),
            0)
        last_entry = jnp.minimum(sv["llen"][i], nij)
        has_entry = nij <= last_entry
        ent = jnp.zeros(self.Lmax, jnp.int32).at[0].set(
            sv["log"][i, jnp.clip(nij - 1, 0, self.Lcap - 1)])
        words = self.pack_msg(
            MT_AEREQ, sv["ct"][i], i, j, a=prev_idx, b=prev_term,
            c=jnp.minimum(sv["ci"][i], last_entry),
            ent=ent, entlen=has_entry.astype(jnp.int32))
        sv2 = self.bag_put(sv, words)
        sv2 = self._glob(sv2, 1)
        return ok, sv2

    def in_quorum(self, votes, config):
        """set ∈ Quorum(config) (raft.tla:217) as the counting test
        (SURVEY §3.1 hot spot b): subset + strict majority."""
        subset = (votes & ~config) == 0
        return subset & (2 * popcount(votes, self.S) >
                         popcount(config, self.S))

    def become_leader(self, sv: State, der, i) -> Tuple[jnp.ndarray, State]:
        """Oracle become_leader(); raft.tla:472-484."""
        ok = (sv["st"][i] == CANDIDATE) & \
            self.in_quorum(sv["vg"][i], der["config"][i])
        sv2 = dict(sv)
        sv2["st"] = sv["st"].at[i].set(LEADER)
        sv2["ni"] = sv["ni"].at[i].set(
            jnp.full(self.S, 1, jnp.int32) + sv["llen"][i])
        sv2["mi"] = sv["mi"].at[i].set(jnp.zeros(self.S, jnp.int32))
        sv2 = self._bump(sv2, C_NLEADERS)
        # BecomeLeader record features (raft.tla:480-483; scenario
        # predicates §2.9)
        leaders2 = der["leaders"] | (jnp.int32(1) << i)
        feat = sv["feat"]
        bl2 = popcount(leaders2, self.S) >= 2
        feat = feat.at[F_BL2_SEEN].max(bl2.astype(jnp.int32))
        njbl = ((feat[F_ADDED_SET] >> i) & 1) == 1
        feat = feat.at[F_NJBL].max(njbl.astype(jnp.int32))
        feat = feat.at[F_LCDCC].max(feat[F_OPEN_ADD])
        sv2["feat"] = feat
        sv2 = self._glob(sv2, 1)
        return ok, sv2

    def client_request(self, sv: State, der, i, v) \
            -> Tuple[jnp.ndarray, State]:
        """Oracle client_request(); raft.tla:488-497.  No global record."""
        ok = sv["st"][i] == LEADER
        entry = self.pack_entry(sv["ct"][i], VALUE_ENTRY, jnp.int32(v))
        overflow = sv["llen"][i] >= self.Lcap
        sv2 = dict(sv)
        sv2["log"] = sv["log"].at[i, jnp.clip(sv["llen"][i], 0,
                                              self.Lcap - 1)].set(
            jnp.where(overflow, sv["log"][i, self.Lcap - 1], entry))
        sv2["llen"] = sv["llen"].at[i].add(
            jnp.where(overflow, 0, 1))
        sv2["ctr"] = sv["ctr"].at[C_NREQ].add(1) \
                              .at[C_OVERFLOW].add(overflow.astype(jnp.int32))
        return ok, sv2

    def advance_commit_index(self, sv: State, der, i) \
            -> Tuple[jnp.ndarray, State]:
        """Oracle advance_commit_index(); raft.tla:504-539."""
        ok = sv["st"][i] == LEADER
        config = der["config"][i]
        # Agree(index) = {i} ∪ {k ∈ config : matchIndex[i][k] ≥ index}
        # (raft.tla:507); agreeIndexes via the counting quorum test
        idxs = jnp.arange(1, self.Lcap + 1, dtype=jnp.int32)   # [Lcap]
        kbit = jnp.int32(1) << jnp.arange(self.S)              # [S]
        match_ge = sv["mi"][i][None, :] >= idxs[:, None]       # [Lcap, S]
        agree = (jnp.int32(1) << i) | jnp.sum(
            jnp.where(match_ge & (((config >> jnp.arange(self.S)) & 1) == 1),
                      kbit[None, :], 0), axis=1)               # [Lcap]
        in_q = self.in_quorum(agree, config) & (idxs <= sv["llen"][i])
        max_agree = jnp.max(jnp.where(in_q, idxs, 0))
        term_ok = self.entry_term(
            sv["log"][i, jnp.clip(max_agree - 1, 0, self.Lcap - 1)]) \
            == sv["ct"][i]
        new_ci = jnp.where((max_agree > 0) & term_ok, max_agree, sv["ci"][i])
        did_commit = new_ci > sv["ci"][i]
        sv2 = dict(sv)
        sv2["ci"] = sv["ci"].at[i].set(new_ci)
        # CommitEntry vs CommitMembershipChange (raft.tla:522-538): compare
        # committed entry's config against the config of the log prefix
        entry = sv["log"][i, jnp.clip(new_ci - 1, 0, self.Lcap - 1)]
        is_cfg_entry = self.entry_type(entry) == CONFIG_ENTRY
        # config of log[i][1..new_ci-1] (GetHistoricalConfig on the prefix)
        pos = jnp.arange(1, self.Lcap + 1, dtype=jnp.int32)
        etypes = self.entry_type(sv["log"][i])
        prefix_cfg_pos = jnp.max(jnp.where(
            (etypes == CONFIG_ENTRY) & (sv["log"][i] != 0) &
            (pos < new_ci), pos, 0))
        prefix_cfg = jnp.where(
            prefix_cfg_pos > 0,
            self.entry_payload(sv["log"][i, jnp.clip(prefix_cfg_pos - 1, 0,
                                                     self.Lcap - 1)]),
            jnp.int32(self.cfg.init_mask))
        is_mc = did_commit & is_cfg_entry & \
            (self.entry_payload(entry) != prefix_cfg)
        is_ce = did_commit & ~is_mc
        feat = sv["feat"]
        pos_rec = sv["ctr"][C_GLOBLEN] + 1
        feat = feat.at[F_COMMIT_SEEN].max(is_ce.astype(jnp.int32))
        cwcl_hit = is_ce & (feat[F_BL2_SEEN] == 1) & (feat[F_CWCL_POS] == 0)
        feat = feat.at[F_CWCL_POS].set(
            jnp.where(cwcl_hit, pos_rec, feat[F_CWCL_POS]))
        add_hit = is_mc & ((self.entry_payload(entry) &
                            feat[F_ADDED_SET]) != 0)
        feat = feat.at[F_ADD_COMMITS].max(add_hit.astype(jnp.int32))
        feat = feat.at[F_OPEN_ADD].set(
            jnp.where(is_mc, 0, feat[F_OPEN_ADD]))
        feat = feat.at[F_MC_COMMITS].add(is_mc.astype(jnp.int32))
        sv2["feat"] = feat
        sv2 = self._glob(sv2, did_commit.astype(jnp.int32))
        return ok, sv2

    def add_new_server(self, sv: State, der, i, j) \
            -> Tuple[jnp.ndarray, State]:
        """Oracle add_new_server(); raft.tla:542-555 — the leader resets
        j's term/votedFor (modeling shortcut) and sends CatchupRequest."""
        ok = (sv["st"][i] == LEADER) & \
            (((der["config"][i] >> j) & 1) == 0)
        sv2 = dict(sv)
        sv2["ct"] = sv["ct"].at[j].set(1)
        sv2["vf"] = sv["vf"].at[j].set(NIL)
        # mentries = SubSeq(log, nextIndex[i][j], commitIndex[i]) :550
        nij = sv["ni"][i, j]
        nent_raw = jnp.maximum(sv["ci"][i] - nij + 1, 0)
        nent = jnp.minimum(nent_raw, self.Lmax)
        gather_idx = jnp.clip(nij - 1 + jnp.arange(self.Lmax), 0,
                              self.Lcap - 1)
        ent = sv["log"][i][gather_idx]
        words = self.pack_msg(MT_CATREQ, sv["ct"][i], i, j,
                              a=sv["mi"][i, j], b=sv["ci"][i],
                              c=jnp.int32(self.cfg.num_rounds),
                              ent=ent, entlen=nent)
        sv2 = self.bag_put(sv2, words)
        sv2["ctr"] = sv2["ctr"].at[C_OVERFLOW].add(
            (nent_raw > self.Lmax).astype(jnp.int32))
        sv2 = self._bump(sv2, C_NTRIED)        # TryAddServer (raft.tla:249)
        sv2 = self._glob(sv2, 2)
        return ok, sv2

    def delete_server(self, sv: State, der, i, j) \
            -> Tuple[jnp.ndarray, State]:
        """Oracle delete_server(); raft.tla:558-569 (self-addressed
        CheckOldConfig; j != i is static)."""
        ok = (sv["st"][i] == LEADER) & \
            ((sv["st"][j] == FOLLOWER) | (sv["st"][j] == CANDIDATE)) & \
            (((der["config"][i] >> j) & 1) == 1)
        words = self.pack_msg(MT_COC, sv["ct"][i], i, i, a=0, b=j)
        sv2 = self.bag_put(sv, words)
        sv2 = self._bump(sv2, C_NTRIED)      # TryRemoveServer (raft.tla:253)
        sv2 = self._glob(sv2, 2)
        return ok, sv2

    def duplicate_message(self, sv: State, k) -> Tuple[jnp.ndarray, State]:
        """Oracle duplicate_message(); raft.tla:892-896 with the count==1
        guard of NextUnreliable (raft.tla:926-928).  No history."""
        ok = sv["cnt"][k] == 1
        sv2 = dict(sv)
        sv2["cnt"] = sv["cnt"].at[k].add(1)
        return ok, sv2

    def drop_message(self, sv: State, k) -> Tuple[jnp.ndarray, State]:
        """Oracle drop_message(); raft.tla:900-904."""
        return sv["cnt"][k] == 1, self.bag_del_slot(sv, k)

    # ------------------------------------------------------------------
    # Receive lanes (oracle receive(); raft.tla:842-863).  Three lanes per
    # bag slot: UpdateTerm (non-consuming), the main per-type handler
    # (branches within a type are mutually exclusive -> selects), and the
    # CheckOldConfig discard branch (which OVERLAPS the process branch,
    # models/raft.py handle_coc docstring).
    # ------------------------------------------------------------------

    def update_term(self, sv: State, der, k) -> Tuple[jnp.ndarray, State]:
        """Oracle update_term(); raft.tla:826-832 — msg NOT consumed."""
        f = self.msg_fields(sv["bag"][k])
        i = f["mdst"]
        ok = (sv["cnt"][k] > 0) & (f["mterm"] > sv["ct"][i])
        sv2 = dict(sv)
        sv2["ct"] = sv["ct"].at[i].set(f["mterm"])
        sv2["st"] = sv["st"].at[i].set(FOLLOWER)
        sv2["vf"] = sv["vf"].at[i].set(NIL)
        return ok, sv2

    def coc_discard(self, sv: State, der, k) -> Tuple[jnp.ndarray, State]:
        """HandleCheckOldConfig discard branch (raft.tla:796): guard
        ``state[i] /= Leader \\/ m.mterm = currentTerm[i]`` — overlaps the
        process branch for a Leader at the message's term."""
        f = self.msg_fields(sv["bag"][k])
        i = f["mdst"]
        ok = (sv["cnt"][k] > 0) & (f["mtype"] == MT_COC) & \
            ((sv["st"][i] != LEADER) | (f["mterm"] == sv["ct"][i]))
        sv2 = self.bag_del_slot(sv, k)
        sv2 = self._glob(sv2, 1)
        return ok, sv2

    def receive_main(self, sv: State, der, k) -> Tuple[jnp.ndarray, State]:
        """Main handler lane: per-type dispatch via selects.  Oracle twins:
        handle_rv_req / handle_rv_resp / handle_ae_req / handle_ae_resp /
        handle_cat_req / handle_cat_resp / handle_coc (process branch)."""
        lay = self.lay
        f = self.msg_fields(sv["bag"][k])
        i, j, mterm, mtype = f["mdst"], f["msrc"], f["mterm"], f["mtype"]
        has = sv["cnt"][k] > 0
        ct_i = sv["ct"][i]
        st_i = sv["st"][i]
        llen_i = sv["llen"][i]
        log_i = sv["log"][i]

        # --- per-type guards ------------------------------------------
        is_rvreq = mtype == MT_RVREQ
        is_rvresp = mtype == MT_RVRESP
        is_aereq = mtype == MT_AEREQ
        is_aeresp = mtype == MT_AERESP
        is_catreq = mtype == MT_CATREQ
        is_catresp = mtype == MT_CATRESP
        is_coc = mtype == MT_COC

        # ==============================================================
        # RVREQ (raft.tla:578-597)
        # ==============================================================
        lt = der["lastterm"][i]
        rv_logok = (f["a"] > lt) | ((f["a"] == lt) & (f["b"] >= llen_i))
        rv_grant = (mterm == ct_i) & rv_logok & \
            ((sv["vf"][i] == NIL) | (sv["vf"][i] == j))
        rvreq_ok = is_rvreq & (mterm <= ct_i)
        # mlog carries the full log (proof artifact, raft.tla:591-593);
        # llen > Lmax is only reachable with stock constraints disabled —
        # fault rather than silently truncate mlog
        rv_of = is_rvreq & (llen_i > self.Lmax)
        rv_resp = self.pack_msg(
            MT_RVRESP, ct_i, i, j, a=rv_grant.astype(jnp.int32),
            ent=log_i[:self.Lmax], entlen=jnp.minimum(llen_i, self.Lmax))

        # ==============================================================
        # RVRESP (raft.tla:836-839, 602-614)
        # ==============================================================
        rvresp_stale = mterm < ct_i
        rvresp_ok = is_rvresp & (mterm <= ct_i)
        rv_vr = sv["vr"][i] | (jnp.int32(1) << j)
        rv_vg = sv["vg"][i] | jnp.where(f["a"] == 1, jnp.int32(1) << j, 0)

        # ==============================================================
        # AEREQ branch family (raft.tla:617-700)
        # ==============================================================
        prev_idx = f["a"]
        ae_in_range = (prev_idx > 0) & (prev_idx <= llen_i)
        ae_logok = (prev_idx == 0) | (
            ae_in_range &
            (f["b"] == self.entry_term(
                log_i[jnp.clip(prev_idx - 1, 0, self.Lcap - 1)])))
        eq = mterm == ct_i
        ae_reject = (mterm < ct_i) | (eq & (st_i == FOLLOWER) & ~ae_logok)
        ae_rtf = eq & (st_i == CANDIDATE)
        ae_accept = eq & (st_i == FOLLOWER) & ae_logok
        index = prev_idx + 1
        e0 = f["ent"][0]
        have_at = llen_i >= index
        term_match = self.entry_term(
            log_i[jnp.clip(index - 1, 0, self.Lcap - 1)]) \
            == self.entry_term(e0)
        ae_already = ae_accept & ((f["entlen"] == 0) | (have_at & term_match))
        ae_conflict = ae_accept & (f["entlen"] > 0) & have_at & ~term_match
        ae_noconf = ae_accept & (f["entlen"] > 0) & (llen_i == prev_idx)
        aereq_ok = is_aereq & (ae_reject | ae_rtf | ae_already |
                               ae_conflict | ae_noconf)
        ae_resp_reject = self.pack_msg(MT_AERESP, ct_i, i, j, a=0, b=0)
        ae_resp_done = self.pack_msg(MT_AERESP, ct_i, i, j, a=1,
                                     b=prev_idx + f["entlen"])

        # ==============================================================
        # AERESP (raft.tla:705-715)
        # ==============================================================
        aeresp_stale = mterm < ct_i
        aeresp_ok = is_aeresp & (mterm <= ct_i)
        ae_succ = f["a"] == 1

        # ==============================================================
        # CATREQ (raft.tla:718-745)
        # ==============================================================
        cat_stale = mterm < ct_i
        catreq_ok = is_catreq
        # splice: prefix(min(mlogLen, Len)) ++ mentries (raft.tla:734-736)
        prefix_len = jnp.minimum(f["a"], llen_i)
        new_len = prefix_len + f["entlen"]
        cat_overflow = new_len > self.Lcap
        pos0 = jnp.arange(self.Lcap, dtype=jnp.int32)           # 0-based
        ent_idx = jnp.clip(pos0 - prefix_len, 0, self.Lmax - 1)
        spliced = jnp.where(
            pos0 < prefix_len, log_i,
            jnp.where(pos0 < new_len, f["ent"][ent_idx], 0))
        cat_resp_stale = self.pack_msg(MT_CATRESP, ct_i, i, j, a=0, b=0, c=0)
        # success reply: mterm adopted, mmatchIndex = PRE-splice length,
        # roundsLeft = mrounds - 1 (raft.tla:738-744)
        cat_resp_ok = self.pack_msg(MT_CATRESP, mterm, i, j, a=1, b=llen_i,
                                    c=f["c"] - 1)

        # ==============================================================
        # CATRESP (raft.tla:748-792); accept == NOT reject exactly
        # ==============================================================
        ci_i = sv["ci"][i]
        mi_ij = sv["mi"][i, j]
        progress = ((f["b"] != ci_i) & (f["b"] != mi_ij)) | (f["b"] == ci_i)
        cat_accept = (f["a"] == 1) & progress & (st_i == LEADER) & \
            (mterm == ct_i) & (((der["config"][i] >> j) & 1) == 0)
        catresp_ok = is_catresp
        old_nij = sv["ni"][i, j]
        more = f["c"] != 0
        # follow-up CatchupRequest (raft.tla:762-771): unprimed nextIndex,
        # NO mcommitIndex field (b=-1 = absent)
        nent2_raw = jnp.maximum(ci_i - old_nij + 1, 0)
        nent2 = jnp.minimum(nent2_raw, self.Lmax)
        cat_more_of = is_catresp & cat_accept & more & \
            (nent2_raw > self.Lmax)
        gather2 = jnp.clip(old_nij - 1 + jnp.arange(self.Lmax), 0,
                           self.Lcap - 1)
        cat_req_more = self.pack_msg(MT_CATREQ, ct_i, i, j,
                                     a=old_nij - 1, b=-1, c=f["c"],
                                     ent=log_i[gather2], entlen=nent2)
        coc_req_done = self.pack_msg(MT_COC, ct_i, i, i, a=1, b=j)

        # ==============================================================
        # COC process branch (raft.tla:795-822)
        # ==============================================================
        coc_ok = is_coc & (st_i == LEADER) & (mterm == ct_i)
        gate = der["maxcfg"][i] <= ci_i
        cfgmask = der["config"][i]
        madd = f["a"] == 1
        coc_new = jnp.where(madd, cfgmask | (jnp.int32(1) << f["b"]),
                            cfgmask & ~(jnp.int32(1) << f["b"]))
        coc_changed = coc_new != cfgmask
        coc_entry = self.pack_entry(ct_i, CONFIG_ENTRY, coc_new)
        coc_resend = self.pack_msg(MT_COC, ct_i, i, i, a=f["a"], b=f["b"])

        # ==============================================================
        # Combine: ok, then construct the successor by masked writes.
        # ==============================================================
        ok = has & (rvreq_ok | rvresp_ok | aereq_ok | aeresp_ok |
                    catreq_ok | catresp_ok | coc_ok)

        sv2 = dict(sv)

        # ---- votedFor (RVREQ grant)
        sv2["vf"] = sv["vf"].at[i].set(
            jnp.where(is_rvreq & rvreq_ok & rv_grant, j, sv["vf"][i]))
        # ---- vote sets (RVRESP non-stale)
        rvresp_live = is_rvresp & rvresp_ok & ~rvresp_stale
        sv2["vr"] = sv["vr"].at[i].set(
            jnp.where(rvresp_live, rv_vr, sv["vr"][i]))
        sv2["vg"] = sv["vg"].at[i].set(
            jnp.where(rvresp_live, rv_vg, sv["vg"][i]))
        # ---- role change (AEREQ ReturnToFollowerState)
        sv2["st"] = sv["st"].at[i].set(
            jnp.where(is_aereq & ae_rtf, FOLLOWER, sv["st"][i]))
        # ---- commitIndex (AEREQ AlreadyDone: can DECREASE, raft.tla:644)
        sv2["ci"] = sv["ci"].at[i].set(
            jnp.where(is_aereq & ae_already, f["c"], sv["ci"][i]))
        # ---- log edits
        new_log_i, new_llen_i = log_i, llen_i
        # AEREQ Conflict: truncate exactly one tail entry (raft.tla:658-665)
        trunc = is_aereq & ae_conflict
        new_log_i = jnp.where(
            trunc,
            log_i.at[jnp.clip(llen_i - 1, 0, self.Lcap - 1)].set(0),
            new_log_i)
        new_llen_i = jnp.where(trunc, llen_i - 1, new_llen_i)
        # AEREQ NoConflict: append one entry (raft.tla:668-672)
        app = is_aereq & ae_noconf
        new_log_i = jnp.where(
            app,
            log_i.at[jnp.clip(llen_i, 0, self.Lcap - 1)].set(
                jnp.where(llen_i >= self.Lcap, log_i[self.Lcap - 1], e0)),
            new_log_i)
        new_llen_i = jnp.where(app & (llen_i < self.Lcap),
                               llen_i + 1, new_llen_i)
        # CATREQ splice
        cat_live = is_catreq & ~cat_stale
        new_log_i = jnp.where(cat_live, jnp.where(cat_overflow, log_i,
                                                  spliced), new_log_i)
        new_llen_i = jnp.where(cat_live & ~cat_overflow, new_len,
                               new_llen_i)
        # COC append ConfigEntry
        coc_app = coc_ok & gate & coc_changed
        coc_of = llen_i >= self.Lcap
        new_log_i = jnp.where(
            coc_app,
            log_i.at[jnp.clip(llen_i, 0, self.Lcap - 1)].set(
                jnp.where(coc_of, log_i[self.Lcap - 1], coc_entry)),
            new_log_i)
        new_llen_i = jnp.where(coc_app & ~coc_of, llen_i + 1, new_llen_i)
        sv2["log"] = sv["log"].at[i].set(new_log_i)
        sv2["llen"] = sv["llen"].at[i].set(new_llen_i)
        # ---- currentTerm adopt (CATREQ success branch, raft.tla:737)
        sv2["ct"] = sv["ct"].at[i].set(
            jnp.where(cat_live, jnp.maximum(mterm, ct_i), sv["ct"][i]))
        # ---- next/match updates (AERESP, CATRESP-accept)
        ni_new = jnp.where(
            is_aeresp & aeresp_ok & ~aeresp_stale,
            jnp.where(ae_succ, f["b"] + 1,
                      jnp.maximum(sv["ni"][i, j] - 1, 1)),
            jnp.where(is_catresp & cat_accept, f["b"] + 1,
                      sv["ni"][i, j]))
        mi_new = jnp.where(
            (is_aeresp & aeresp_ok & ~aeresp_stale & ae_succ) |
            (is_catresp & cat_accept),
            f["b"], sv["mi"][i, j])
        sv2["ni"] = sv["ni"].at[i, j].set(ni_new)
        sv2["mi"] = sv["mi"].at[i, j].set(mi_new)
        # ---- membership-change counter + features (COC apply)
        sv2["ctr"] = sv2["ctr"].at[C_NMC].add(
            (coc_app).astype(jnp.int32))
        feat = sv2["feat"]
        add_rec = coc_app & madd
        feat = feat.at[F_ADDED_SET].set(
            jnp.where(add_rec, feat[F_ADDED_SET] | (jnp.int32(1) << f["b"]),
                      feat[F_ADDED_SET]))
        feat = feat.at[F_OPEN_ADD].max(add_rec.astype(jnp.int32))
        sv2["feat"] = feat
        sv2["ctr"] = sv2["ctr"].at[C_OVERFLOW].add(
            ((cat_live & cat_overflow) | (coc_app & coc_of) |
             rv_of | cat_more_of).astype(jnp.int32))

        # ---- bag update: consume request? send reply?
        consume = (is_rvreq & rvreq_ok) | rvresp_live | \
            (is_rvresp & rvresp_ok & rvresp_stale) | \
            (is_aereq & (ae_reject | ae_already)) | \
            (is_aeresp & aeresp_ok) | is_catreq | is_catresp | coc_ok
        # (ReturnToFollower / Conflict / NoConflict do NOT consume,
        # raft.tla:632-672)
        reply_words = jnp.where(
            is_rvreq, rv_resp,
            jnp.where(is_aereq & ae_reject, ae_resp_reject,
            jnp.where(is_aereq & ae_already, ae_resp_done,
            jnp.where(is_catreq & cat_stale, cat_resp_stale,
            jnp.where(is_catreq, cat_resp_ok,
            jnp.where(is_catresp & cat_accept & more, cat_req_more,
            jnp.where(is_catresp & cat_accept, coc_req_done,
                      coc_resend)))))))
        has_reply = (is_rvreq & rvreq_ok) | \
            (is_aereq & (ae_reject | ae_already)) | is_catreq | \
            (is_catresp & cat_accept) | (coc_ok & ~gate)
        sv3 = self.bag_del_slot(sv2, k)
        sv3 = {key: jnp.where(consume, sv3[key], sv2[key])
               if key in ("bag", "cnt") else sv3[key] for key in sv3}
        sv4 = self.bag_put(sv3, reply_words)
        sv_final = {key: jnp.where(has_reply, sv4[key], sv3[key])
                    if key in ("bag", "cnt", "ctr") else sv4[key]
                    for key in sv4}
        # ---- history record count: Reply=2, Discard=1, silent=0;
        # DiscardDirectWithMembershipChange appends Receive + the
        # AddServer/RemoveServer record = 2 (raft.tla:285-290)
        n_rec = jnp.where(has_reply | coc_app, 2,
                          jnp.where(consume, 1, 0)).astype(jnp.int32)
        sv_final["ctr"] = sv_final["ctr"].at[C_GLOBLEN].add(n_rec)
        return ok, sv_final

    # ------------------------------------------------------------------
    # Guard-only twins (the MXU guard-matrix path, engine/expand).
    #
    # The packed guard matrix reduces every lane's enabling guard to a
    # thresholded int8 dot product against a per-state FEATURE vector;
    # the message-slot families' guards are data-dependent per slot, so
    # they ARE the features — computed here once per (state, slot)
    # instead of once per (state, lane) by the vmapped kernel sweep.
    # Each guard_* below must stay in lockstep with its kernel twin's
    # ``ok`` (update_term / coc_discard / receive_main above); the
    # matmul≡lane differential tests (tests/test_guard_matmul.py) and
    # every engine's oracle differential pin the equivalence.
    # ------------------------------------------------------------------

    def guard_update_term(self, sv: State, k) -> jnp.ndarray:
        """update_term's ``ok`` without the successor (header-only)."""
        hs = self.lay.header_shifts
        w0 = sv["bag"][k, 0]
        i = get_field(w0, hs["mdst"]).astype(jnp.int32)
        mterm = get_field(w0, hs["mterm"]).astype(jnp.int32)
        return (sv["cnt"][k] > 0) & (mterm > sv["ct"][i])

    def guard_coc_discard(self, sv: State, k) -> jnp.ndarray:
        """coc_discard's ``ok`` without the successor (header-only)."""
        hs = self.lay.header_shifts
        w0 = sv["bag"][k, 0]
        i = get_field(w0, hs["mdst"]).astype(jnp.int32)
        mterm = get_field(w0, hs["mterm"]).astype(jnp.int32)
        mtype = get_field(w0, hs["mtype"]).astype(jnp.int32)
        return (sv["cnt"][k] > 0) & (mtype == MT_COC) & \
            ((sv["st"][i] != LEADER) | (mterm == sv["ct"][i]))

    def guard_receive(self, sv: State, k) -> jnp.ndarray:
        """receive_main's ``ok`` without the successor construction:
        exactly the guard sub-expressions of the main-handler lane (the
        AEREQ branch family needs the log probe and ent[0], nothing
        else — note rv_logok/rv_grant affect only the REPLY, not the
        guard, so ``der`` is not needed here)."""
        f = self.msg_fields(sv["bag"][k])
        i, mterm, mtype = f["mdst"], f["mterm"], f["mtype"]
        has = sv["cnt"][k] > 0
        ct_i = sv["ct"][i]
        st_i = sv["st"][i]
        llen_i = sv["llen"][i]
        log_i = sv["log"][i]
        rvreq_ok = (mtype == MT_RVREQ) & (mterm <= ct_i)
        rvresp_ok = (mtype == MT_RVRESP) & (mterm <= ct_i)
        # AEREQ branch family (raft.tla:617-700): guard = any branch
        prev_idx = f["a"]
        ae_in_range = (prev_idx > 0) & (prev_idx <= llen_i)
        ae_logok = (prev_idx == 0) | (
            ae_in_range &
            (f["b"] == self.entry_term(
                log_i[jnp.clip(prev_idx - 1, 0, self.Lcap - 1)])))
        eq = mterm == ct_i
        ae_reject = (mterm < ct_i) | (eq & (st_i == FOLLOWER) & ~ae_logok)
        ae_rtf = eq & (st_i == CANDIDATE)
        ae_accept = eq & (st_i == FOLLOWER) & ae_logok
        index = prev_idx + 1
        have_at = llen_i >= index
        term_match = self.entry_term(
            log_i[jnp.clip(index - 1, 0, self.Lcap - 1)]) \
            == self.entry_term(f["ent"][0])
        ae_already = ae_accept & ((f["entlen"] == 0) |
                                  (have_at & term_match))
        ae_conflict = ae_accept & (f["entlen"] > 0) & have_at & ~term_match
        ae_noconf = ae_accept & (f["entlen"] > 0) & (llen_i == prev_idx)
        aereq_ok = (mtype == MT_AEREQ) & \
            (ae_reject | ae_rtf | ae_already | ae_conflict | ae_noconf)
        aeresp_ok = (mtype == MT_AERESP) & (mterm <= ct_i)
        catreq_ok = mtype == MT_CATREQ
        catresp_ok = mtype == MT_CATRESP
        coc_ok = (mtype == MT_COC) & (st_i == LEADER) & (mterm == ct_i)
        return has & (rvreq_ok | rvresp_ok | aereq_ok | aeresp_ok |
                      catreq_ok | catresp_ok | coc_ok)

    def guard_features(self, sv: State, der: State) -> jnp.ndarray:
        """Per-state guard-feature vector φ(s), int8 [n_guard_features].

        Every family's enabling guard is a signed-weight threshold over
        these features (engine/expand builds the weight matrix), so the
        whole [states × lanes] guard grid becomes ONE int8 matmul
        φ @ W compared against the per-lane thresholds — exact by
        construction (0/±1 weights, integer accumulation).  Layout is
        ``guard_feature_offsets``; the two must move together."""
        S = self.S
        st = sv["st"]
        leader = st == LEADER
        cand = st == CANDIDATE
        folc = (st == FOLLOWER) | cand
        # BecomeLeader's quorum test, per server (vectorized in_quorum)
        blq = self.in_quorum(sv["vg"], der["config"])
        jj = jnp.arange(S)
        cfgb = ((der["config"][:, None] >> jj[None, :]) & 1) == 1
        nv = (((der["config"] & ~sv["vr"])[:, None]
               >> jj[None, :]) & 1) == 1
        ks = jnp.arange(self.K)
        ut = jax.vmap(lambda k: self.guard_update_term(sv, k))(ks)
        cocd = jax.vmap(lambda k: self.guard_coc_discard(sv, k))(ks)
        recv = jax.vmap(lambda k: self.guard_receive(sv, k))(ks)
        cnt1 = sv["cnt"] == 1
        return jnp.concatenate([
            leader, cand, folc, blq, cfgb.reshape(-1), nv.reshape(-1),
            ut, cocd, recv, cnt1]).astype(jnp.int8)

    # ------------------------------------------------------------------
    # Delta features (the value-source half of the delta-matmul
    # successor path, engine/expand delta-matrix comment; round 11).
    #
    # Every affine family's state delta is a weighted sum of these
    # per-state int32 sources (plus the constant 1 and the flat state
    # view itself), so successor generation for the declared families
    # runs as one batched scatter-as-matmul.  The features fold the
    # few data-dependent pieces the raft affine actions need:
    #
    # - BecomeLeader's three feat-lane max-updates, pre-differenced
    #   (max(old, x) - old), so the matmul ADD lands the max exactly;
    # - Timeout's term-capacity clamp (ct < cap room / its overflow);
    # - ClientRequest's append machinery: the one-hot of the append
    #   position (llen), the same one-hot scaled by the entry's term
    #   and by the old log word (so set == add with the old value
    #   cancelled), and the llen-room / overflow flags;
    # - UpdateTerm's message-indexed set-updates (round 17): per bag
    #   slot, the dst one-hot scaled by (new - old) for each of the
    #   three per-server writes, so set == add exactly and non-dst
    #   servers get zero;
    # - Restart's min-gap feature, pre-differenced the same way
    #   (min(old, gap) - old) — the nonlinear min/where pair folds
    #   into the feature, leaving the action's writes affine.
    #
    # Layout is ``delta_feature_offsets`` below; the two must move
    # together (same single-definition rule as guard_features).
    # ------------------------------------------------------------------

    def delta_features(self, sv: State, der: State) -> jnp.ndarray:
        S, Lcap = self.S, self.Lcap
        feat = sv["feat"]
        ii = jnp.arange(S)
        # BecomeLeader feat deltas, per candidate server i
        leaders2 = der["leaders"] | (jnp.int32(1) << ii)
        bl2 = (popcount(leaders2, S) >= 2).astype(jnp.int32)
        d_bl2 = jnp.maximum(feat[F_BL2_SEEN], bl2) - feat[F_BL2_SEEN]
        njbl = (feat[F_ADDED_SET] >> ii) & 1
        d_njbl = jnp.maximum(feat[F_NJBL], njbl) - feat[F_NJBL]
        d_lcdcc = (jnp.maximum(feat[F_LCDCC], feat[F_OPEN_ADD]) -
                   feat[F_LCDCC])[None]
        # Timeout's clamped term bump: room == the exact increment
        # (term_cap: the layout's representability clamp, never a
        # per-job runtime bound — see the property's docstring)
        cap = self.term_cap
        ctroom = (sv["ct"] < cap).astype(jnp.int32)
        # ClientRequest append: llen room + the append-position one-hot
        crroom = (sv["llen"] < Lcap).astype(jnp.int32)
        pos = jnp.arange(Lcap, dtype=jnp.int32)
        croh = (sv["llen"][:, None] == pos[None, :]) \
            .astype(jnp.int32)                            # [S, Lcap]
        crohct = croh * sv["ct"][:, None]
        crohold = croh * sv["log"]

        # UpdateTerm's per-slot writes, dst-one-hot scaled and
        # pre-differenced (new - old): ct[dst]=mterm, st[dst]=FOLLOWER,
        # vf[dst]=NIL land as exact matmul ADDs, zero off the dst
        def ut_row(k):
            f = self.msg_fields(sv["bag"][k])
            oh = (f["mdst"] == ii).astype(jnp.int32)          # [S]
            return (oh * (f["mterm"] - sv["ct"]),
                    oh * (jnp.int32(FOLLOWER) - sv["st"]),
                    oh * (jnp.int32(NIL) - sv["vf"]))
        utdct, utdst, utdvf = jax.vmap(ut_row)(
            jnp.arange(self.K))                               # [K, S]
        # Restart's min-gap update, pre-differenced — gap computed
        # exactly as restart() does (same pos/last/NO_GAP dance)
        pos = sv["ctr"][C_GLOBLEN] + 1
        last = feat[F_LAST_RESTART_POS]
        gap = jnp.where(last > 0, pos - last, jnp.int32(NO_GAP))
        rgap = (jnp.minimum(feat[F_MIN_RESTART_GAP], gap) -
                feat[F_MIN_RESTART_GAP])[None]
        return jnp.concatenate([
            d_bl2, d_njbl, d_lcdcc, ctroom, crroom,
            croh.reshape(-1), crohct.reshape(-1),
            crohold.reshape(-1), utdct.reshape(-1),
            utdst.reshape(-1), utdvf.reshape(-1),
            rgap]).astype(jnp.int32)

    def delta_feature_offsets(self) -> Dict[str, int]:
        """The SpecIR kernels contract: flat layout of this spec's
        ``delta_features`` vector (module-level table below)."""
        return delta_feature_offsets(self.lay)


def guard_feature_offsets(lay: Layout) -> Dict[str, int]:
    """Flat layout of ``RaftKernels.guard_features``: per-server role
    blocks (leader / candidate / follower-or-candidate / become-leader
    quorum), the two [S, S] config-bit grids (cfg[i,j], needvote[i,j],
    row-major), then the four per-slot blocks (update_term /
    coc_discard / receive / count==1).  The weight builder in
    engine/expand indexes through THIS table only, so feature order has
    a single definition."""
    S, K = lay.S, lay.K
    off = dict(leader=0, cand=S, folc=2 * S, blq=3 * S, cfg=4 * S,
               needvote=4 * S + S * S)
    base = 4 * S + 2 * S * S
    off.update(ut=base, cocd=base + K, recv=base + 2 * K,
               cnt1=base + 3 * K)
    off["total"] = base + 4 * K
    return off


def delta_feature_offsets(lay: Layout) -> Dict[str, int]:
    """Flat layout of ``RaftKernels.delta_features``: the BecomeLeader
    feat-delta blocks (bl2 / njbl per server, the scalar lcdcc), the
    Timeout term-room block, the ClientRequest append blocks (llen
    room, and the three [S, Lcap] one-hot grids: position, position ×
    term, position × old log word), the three UpdateTerm [K, S]
    dst-one-hot set-difference grids (ct / st / vf, row-major), and
    the scalar Restart min-gap difference."""
    S, Lcap, K = lay.S, lay.Lcap, lay.K
    off = dict(bl2=0, njbl=S, lcdcc=2 * S, ctroom=2 * S + 1,
               crroom=3 * S + 1, croh=4 * S + 1,
               crohct=4 * S + 1 + S * Lcap,
               crohold=4 * S + 1 + 2 * S * Lcap)
    base = 4 * S + 1 + 3 * S * Lcap
    off.update(utdct=base, utdst=base + K * S,
               utdvf=base + 2 * K * S, rgap=base + 3 * K * S)
    off["total"] = base + 3 * K * S + 1
    return off
