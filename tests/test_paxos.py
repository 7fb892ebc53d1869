"""Paxos — the second SpecIR tenant — differentially pinned.

Mirrors the Raft test architecture: the plain-Python oracle
(spec/paxos/model.py) is the semantics anchor; the engines must match
it bit-for-bit through the UNMODIFIED bfs/spill/sim stack.  Fast
tier-1 representatives here are the oracle step-for-step differential
and one engine-vs-oracle count parity run (sub-5s each); full-space
and spill duplicates are slow-marked (tier-1 budget, ROADMAP
standing constraint).
"""

import numpy as np
import pytest

from raft_tla_tpu.spec import get_spec, spec_of
from raft_tla_tpu.spec.paxos.config import PaxosConfig
from raft_tla_tpu.spec.paxos.layout import PaxosLayout, decode, encode
from raft_tla_tpu.spec.paxos.model import (
    PaxosState, agreement, canonicalize, chosen_values, init_state,
    relabel, successors, symmetry_perms, validity, value_chosen,
    walk_key)
from raft_tla_tpu.spec.paxos.oracle import explore

CFG = PaxosConfig()                       # N=3, B=2, V=2, I=1
CFG_NS = CFG.with_(symmetry=False)

# full-space golden counts for the stock model (cross-checked against
# the oracle at runtime in the parity tests; pinned here so a silent
# oracle regression cannot re-pin the engines to a wrong count)
GOLD_SYM = dict(distinct=857, generated=3328, depth=17)
GOLD_NOSYM = dict(distinct=3921, generated=15299, depth=17)


def apply_label(sv, h, cfg, label):
    matches = [(l, s2, h2) for l, s2, h2 in successors(sv, h, cfg)
               if l == label]
    assert len(matches) == 1, f"label {label}: {len(matches)} matches"
    return matches[0][1], matches[0][2]


# ---------------------------------------------------------------------------
# oracle semantics
# ---------------------------------------------------------------------------

def test_oracle_chosen_value_replay():
    """Minimal chosen-value run: 1a, two promises, proposal, quorum of
    accepts — Agreement/Validity hold throughout, ValueChosen flips
    exactly at the quorum accept."""
    sv, h = init_state(CFG)
    steps = ["Phase1a(0,0)", "Phase1b(0,0,0)", "Phase1b(0,1,0)",
             "Phase2a(0,0,1)", "Phase2b(0,0,0,1)"]
    for lbl in steps:
        sv, h = apply_label(sv, h, CFG, lbl)
        assert agreement(sv, h, CFG) and validity(sv, h, CFG)
        assert value_chosen(sv, h, CFG)       # no quorum of 2b yet
    sv, h = apply_label(sv, h, CFG, "Phase2b(0,1,0,1)")
    assert chosen_values(sv, 0, CFG) == {1}
    assert not value_chosen(sv, h, CFG)       # the witness
    assert agreement(sv, h, CFG) and validity(sv, h, CFG)
    assert len(h.glob) == 6


def test_oracle_value_rule_pins_accepted_value():
    """After a value is accepted by a quorum member, a higher ballot's
    Phase2a must re-propose THAT value (the consensus core): with the
    1b reports of {a0 (voted v=1 at b0), a1 (fresh)} the only enabled
    Phase2a at b1 is value 1."""
    sv, h = init_state(CFG)
    for lbl in ["Phase1a(0,0)", "Phase1b(0,0,0)", "Phase1b(0,1,0)",
                "Phase2a(0,0,1)", "Phase2b(0,0,0,1)",
                "Phase1a(0,1)", "Phase1b(0,0,1)", "Phase1b(0,1,1)"]:
        sv, h = apply_label(sv, h, CFG, lbl)
    labels = [l for l, _s, _h in successors(sv, h, CFG)]
    assert "Phase2a(0,1,1)" in labels
    assert "Phase2a(0,1,0)" not in labels
    # and a0 is now preempted: promised b1 above its accepted b0
    assert sv.mb[0][0] == 1 and sv.vb[0][0] == 0


def test_oracle_explore_counts_and_symmetry():
    r = explore(CFG)
    assert (r.distinct_states, r.generated_states, r.depth) == \
        (GOLD_SYM["distinct"], GOLD_SYM["generated"], GOLD_SYM["depth"])
    assert not r.violations
    r2 = explore(CFG_NS)
    assert (r2.distinct_states, r2.generated_states, r2.depth) == \
        (GOLD_NOSYM["distinct"], GOLD_NOSYM["generated"],
         GOLD_NOSYM["depth"])
    # canonicalization sanity: relabeled states share a canonical form
    perms = symmetry_perms(CFG)
    sv, h = init_state(CFG)
    for lbl in ["Phase1a(0,0)", "Phase1b(0,2,0)"]:
        sv, h = apply_label(sv, h, CFG, lbl)
    for sig in perms:
        assert canonicalize(relabel(sv, sig, CFG), perms, CFG) == \
            canonicalize(sv, perms, CFG)


def test_multi_instance_product_law():
    """Independent instances ⇒ the reachable set is the product: the
    I=2 distinct count is exactly the I=1 count squared (symmetry off —
    acceptor relabeling couples instances)."""
    c1 = PaxosConfig(symmetry=False, n_ballots=1, n_values=2)
    c2 = c1.with_(n_instances=2)
    r1, r2 = explore(c1), explore(c2)
    assert r2.distinct_states == r1.distinct_states ** 2
    assert not r1.violations and not r2.violations


# ---------------------------------------------------------------------------
# codec + fingerprint
# ---------------------------------------------------------------------------

def test_codec_roundtrip_reachable():
    lay = PaxosLayout(CFG)
    r = explore(CFG_NS, keep_states=True, max_depth=5)
    assert r.states
    for sv, h in r.states.values():
        sv2, h2 = decode(lay, encode(lay, sv, h))
        assert sv2 == sv


def test_fingerprint_symmetry_and_distinctness():
    """Relabeled states fingerprint identically; distinct canonical
    states fingerprint distinctly (on the reachable sample)."""
    import jax.numpy as jnp
    ir = get_spec("paxos")
    lay = PaxosLayout(CFG)
    fpr = ir.make_fingerprinter(CFG)
    perms = symmetry_perms(CFG)
    r = explore(CFG_NS, keep_states=True, max_depth=4)
    seen = {}
    for sv, h in r.states.values():
        fp = tuple(int(x) for x in np.asarray(
            fpr.fingerprint({k: jnp.asarray(v) for k, v in
                             encode(lay, sv, h).items()})))
        for sig in perms[1:3]:
            svp = relabel(sv, sig, CFG)
            fpp = tuple(int(x) for x in np.asarray(
                fpr.fingerprint({k: jnp.asarray(v) for k, v in
                                 encode(lay, svp, h).items()})))
            assert fpp == fp, "relabeling changed the fingerprint"
        key = canonicalize(sv, perms, CFG)
        if key in seen:
            assert seen[key] == fp
        else:
            assert fp not in set(seen.values()), \
                "distinct canonical states collided"
            seen[key] = fp


# ---------------------------------------------------------------------------
# engine differentials (fast tier-1 representatives)
# ---------------------------------------------------------------------------

def _decode_all(lay, expander, arrs):
    out = []
    for lbl, sv2 in expander.expand_one(arrs):
        out.append((lbl, walk_key(decode(lay, sv2)[0])))
    return out


def test_kernels_step_for_step_differential():
    """Oracle step-for-step: on a reachable-state sample, the
    expander's enabled lanes (labels AND decoded successor states)
    equal the oracle's successor enumeration exactly — the paxos twin
    of tests/test_kernels.py."""
    from raft_tla_tpu.engine.expand import Expander
    lay = PaxosLayout(CFG)
    exp = Expander(CFG)
    r = explore(CFG_NS, keep_states=True, max_depth=6)
    states = list(r.states.values())[::3][:40]
    assert len(states) >= 20
    for sv, h in states:
        got = _decode_all(lay, exp, encode(lay, sv, h))
        want = [(lbl, walk_key(s2))
                for lbl, s2, _h2 in successors(sv, h, CFG)]
        assert got == want, f"successor divergence at {sv}"


def test_engine_vs_oracle_full_space_bfs():
    """The acceptance pin: `check --spec paxos` lands on the oracle's
    exact counts through the unmodified bfs engine (distinct,
    generated, depth, level sizes, zero violations)."""
    from raft_tla_tpu.engine.bfs import Engine
    ro = explore(CFG)
    eng = Engine(CFG, chunk=128, store_states=False)
    r = eng.check()
    assert r.distinct_states == ro.distinct_states == \
        GOLD_SYM["distinct"]
    assert r.generated_states == ro.generated_states
    assert r.depth == ro.depth
    assert r.level_sizes == ro.level_sizes
    assert not r.violations and r.violations_global == 0


def test_engine_vs_oracle_spill_depth_capped():
    """Spill-engine parity rep, depth-capped to stay sub-5s; the
    full-space duplicate is slow-marked below."""
    from raft_tla_tpu.engine.spill import SpillEngine
    ro = explore(CFG, max_depth=9)
    eng = SpillEngine(CFG, chunk=128, store_states=False, seg=1 << 12)
    r = eng.check(max_depth=9)
    assert r.distinct_states == ro.distinct_states
    assert r.generated_states == ro.generated_states
    assert r.depth == ro.depth


def test_sim_engine_walks_and_oracle_replays():
    """The sim engine runs paxos unmodified: a ValueChosen witness is
    found and its decoded trace replays through the oracle transition
    relation (the sim acceptance check, paxos twin of test_sim)."""
    from raft_tla_tpu.sim import SimEngine
    from raft_tla_tpu.spec.paxos.oracle import oracle_validates_walk
    eng = SimEngine(CFG.with_(invariants=("ValueChosen",)),
                    walkers=32, max_depth=24, seed=3)
    r = eng.run(steps=300, steps_per_dispatch=64)
    assert r.hits, "no ValueChosen witness in 300 fleet steps"
    h = eng.decode_hit(r.hits[0])
    states = [sv for _lbl, sv in h.trace]
    labels = oracle_validates_walk(CFG, states)
    assert labels == [lbl for lbl, _sv in h.trace[1:]]


# ---------------------------------------------------------------------------
# registry / error paths + spec stamping
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_spec_registry_error_paths():
    # unknown spec name fails loudly with the known list
    with pytest.raises(ValueError, match="known specs: paxos, raft"):
        get_spec("multipaxos")
    # spec dispatch off the config marker
    assert spec_of(CFG).name == "paxos"
    from raft_tla_tpu.config import ModelConfig
    assert spec_of(ModelConfig()).name == "raft"
    # a family without a declared guard algebra fails at Expander
    # construction, naming the spec
    from raft_tla_tpu.engine.expand import Expander, Family
    ir = get_spec("paxos")
    import raft_tla_tpu.spec.paxos.ir as pir

    def broken(lay):
        fams = pir.build_families(lay)
        f0 = fams[0]
        fams[0] = Family(f0.name, f0.fn, f0.params, f0.labeler,
                         guard=None)
        return fams

    orig = ir.build_families
    object.__setattr__(ir, "build_families", broken)
    try:
        with pytest.raises(KeyError, match="spec 'paxos'"):
            Expander(CFG)
    finally:
        object.__setattr__(ir, "build_families", orig)
    # per-spec fam-cap-density: unknown family names the active spec
    from raft_tla_tpu.engine.expand import parse_fam_density
    with pytest.raises(ValueError, match="spec 'paxos'"):
        parse_fam_density("Receive=4", get_spec("paxos"))
    assert parse_fam_density("Phase2b=2", get_spec("paxos")) == \
        {"Phase2b": 2}
    # raft default preserved for legacy callers
    assert parse_fam_density("Receive=4") == {"Receive": 4}
    # paxos declares no constraints / action constraints
    lay = PaxosLayout(CFG)
    preds = ir.make_predicates(lay)
    with pytest.raises(KeyError, match="spec 'paxos'"):
        preds.constraint_fn("BoundedLogSize")
    with pytest.raises(KeyError, match="spec 'paxos'"):
        preds.action_fn("anything")
    # config bounds validation
    with pytest.raises(ValueError, match="n_servers"):
        PaxosConfig(n_servers=9)


@pytest.mark.smoke
def test_checkpoint_refuses_spec_mismatch(tmp_path):
    """ckpt_read's spec gate: a checkpoint stamped for one spec
    refuses to resume under another, BEFORE the cfg-repr compare (and
    a meta without a spec key reads as raft — every pre-IR checkpoint
    is one)."""
    import json
    from raft_tla_tpu.engine.bfs import CheckpointError, ckpt_read
    path = str(tmp_path / "x.npz")
    meta = dict(spec="paxos", cfg="whatever", chunk=128)
    np.savez(path, meta=np.array(json.dumps(meta)))
    with pytest.raises(CheckpointError, match="spec 'paxos'"):
        ckpt_read(path, "whatever", 128, (), spec_name="raft")
    # legacy meta (no spec key) == raft; passes the spec gate and
    # proceeds to the ordinary validation (here: missing base keys)
    meta2 = dict(cfg="whatever", chunk=128)
    np.savez(path, meta=np.array(json.dumps(meta2)))
    with pytest.raises(CheckpointError, match="older engine"):
        ckpt_read(path, "whatever", 128, (), spec_name="raft")


@pytest.mark.smoke
def test_check_stats_spec_stamp_appends_after_pinned_keys():
    from raft_tla_tpu.engine.bfs import CheckResult
    from raft_tla_tpu.obs.metrics import check_stats
    r = CheckResult(distinct_states=10, generated_states=20, depth=3)
    base = check_stats(r.metrics.as_dict(), 1.5, 0, fp_bits=64)
    out = check_stats(r.metrics.as_dict(), 1.5, 0, fp_bits=64,
                      spec="paxos", ir_fp="abc123")
    assert list(out.keys()) == list(base.keys()) + \
        ["spec", "ir_fingerprint"]
    assert out["spec"] == "paxos" and out["ir_fingerprint"] == "abc123"


def test_ir_fingerprints_are_stable_and_distinct():
    raft_fp = get_spec("raft").fingerprint()
    paxos_fp = get_spec("paxos").fingerprint()
    assert raft_fp != paxos_fp
    assert raft_fp == get_spec("raft").fingerprint()
    assert len(raft_fp) == 12


# ---------------------------------------------------------------------------
# full-space / spill duplicates (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_vs_oracle_spill_full_space():
    from raft_tla_tpu.engine.spill import SpillEngine
    ro = explore(CFG)
    eng = SpillEngine(CFG, chunk=128, store_states=False, seg=1 << 12)
    r = eng.check()
    assert (r.distinct_states, r.generated_states, r.depth) == \
        (ro.distinct_states, ro.generated_states, ro.depth)
    assert r.level_sizes == ro.level_sizes


@pytest.mark.slow
def test_guard_matmul_on_off_identical_paxos():
    from raft_tla_tpu.engine.bfs import Engine
    r_on = Engine(CFG, chunk=128, store_states=False,
                  guard_matmul=True).check()
    r_off = Engine(CFG, chunk=128, store_states=False,
                   guard_matmul=False).check()
    assert (r_on.distinct_states, r_on.generated_states, r_on.depth,
            r_on.level_sizes) == \
        (r_off.distinct_states, r_off.generated_states, r_off.depth,
         r_off.level_sizes)


@pytest.mark.slow
def test_engine_no_symmetry_and_fp128_full_space():
    from raft_tla_tpu.engine.bfs import Engine
    ro = explore(CFG_NS)
    r = Engine(CFG_NS, chunk=256, store_states=False).check()
    assert (r.distinct_states, r.generated_states, r.depth) == \
        (ro.distinct_states, ro.generated_states, ro.depth)
    r128 = Engine(CFG.with_(fp128=True), chunk=128,
                  store_states=False).check()
    assert r128.distinct_states == GOLD_SYM["distinct"]


@pytest.mark.slow
def test_multi_instance_engine_parity():
    from raft_tla_tpu.engine.bfs import Engine
    cfg = PaxosConfig(symmetry=False, n_ballots=1, n_values=2,
                      n_instances=2)
    ro = explore(cfg)
    r = Engine(cfg, chunk=256, store_states=False).check()
    assert (r.distinct_states, r.generated_states, r.depth) == \
        (ro.distinct_states, ro.generated_states, ro.depth)
