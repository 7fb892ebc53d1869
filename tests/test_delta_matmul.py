"""Delta-matmul successor generation (round 11): frontier expansion as
MXU matrix algebra.

The contract is bit-exactness BY CONSTRUCTION, pinned differentially:
``delta_matmul=True`` (default) — every family with a declared delta
algebra applies as ONE batched scatter-as-matmul per family group —
must be an exact drop-in for the per-family kernel path in EVERY
engine: counts, level sizes, global ids, archives, witness traces,
violation states, sim trajectories and batched-serve waves, for raft
AND paxos.  A family without a declaration transparently keeps the
kernel path (pinned below by stripping one).  One fast representative
per engine family runs in tier-1; full-space duplicates are
slow-marked (870s budget)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC, \
    NEXT_DYNAMIC
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.engine.expand import Expander
from raft_tla_tpu.engine.spill import SpillEngine
from raft_tla_tpu.spec import get_spec
from raft_tla_tpu.spec.paxos.config import PaxosConfig

# tiny configs (test_guard_matmul shapes: small spaces, fast)
TINY = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=2, next_family=NEXT_ASYNC, symmetry=False,
    constraints=("BoundedInFlightMessages", "BoundedRequestVote",
                 "BoundedLogSize", "BoundedTerms"),
    invariants=("ElectionSafety", "LogMatching"),
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=4, symmetry=True,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))

# NextDynamic at S=3: every affine family gets lanes (incl. the
# Duplicate/Drop pair), mixed with every kernel-path family
DYN = ModelConfig(
    n_servers=3, init_servers=(0, 1), values=(1,),
    next_family=NEXT_DYNAMIC, symmetry=False, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                       max_client_requests=1))


def _key(r):
    return (r.distinct_states, r.generated_states, r.depth,
            tuple(r.level_sizes), r.violations_global)


def _oracle_key(cfg, max_depth=10 ** 9):
    from conftest import cached_explore
    w = cached_explore(cfg, max_depth=max_depth)
    return (w.distinct_states, w.generated_states, w.depth,
            tuple(w.level_sizes), len(w.violations))


def _reachable_svT(cfg, n=120):
    """A batch of reachable states, batch-last, via the oracle."""
    from conftest import cached_explore
    ir = get_spec(getattr(cfg, "spec", "raft"))
    lay = ir.make_layout(cfg)
    r = cached_explore(cfg, max_states=3 * n, keep_states=True)
    pairs = list(r.states.values())[:n]
    rows = [ir.encode(lay, sv, h) for sv, h in pairs]
    batch = ir.widen({k: np.stack([s[k] for s in rows])
                      for k in rows[0]})
    return {k: jnp.moveaxis(jnp.asarray(v), 0, -1)
            for k, v in batch.items()}


def _materialize_pair(cfg, svT):
    """(cand ON, cand OFF, famx ON, famx OFF, n_enabled) on a real
    guard mask over the batch — the full materialize surface."""
    ex_on = Expander(cfg, delta_matmul=True)
    ex_off = Expander(cfg, delta_matmul=False)
    derT = ex_on.derived_batch_T(svT)
    ok = np.asarray(ex_on.guards_T(svT, derT))
    B = ok.shape[0]
    okf = jnp.asarray(ok.reshape(-1))
    FCAP = int(ok.sum()) + 8
    epos = jnp.where(okf, jnp.cumsum(okf.astype(jnp.int32)) - 1, FCAP)
    caps = ex_on.default_fam_caps(B)
    c_on, f_on = jax.jit(lambda s, d: ex_on.materialize(
        s, d, okf, epos, FCAP, caps))(svT, derT)
    c_off, f_off = jax.jit(lambda s, d: ex_off.materialize(
        s, d, okf, epos, FCAP, caps))(svT, derT)
    return ex_on, c_on, c_off, f_on, f_off, int(ok.sum())


# ---------------------------------------------------------------------
# expander level: delta matmul ≡ kernel path (the @smoke acceptance pin)
# ---------------------------------------------------------------------


@pytest.mark.smoke
def test_delta_matmul_equals_kernel_path_on_reachable_states():
    """The group scatter-as-matmul reproduces every enabled successor
    bit-exactly on reachable NextDynamic states — all six affine
    raft families (Timeout's clamped term, BecomeLeader's feat maxes,
    ClientRequest's log append, Duplicate, and round 17's
    UpdateTerm dst-one-hot sets + Restart minus its min-gap min)
    interleaved with the kernel-path families in oracle enumeration
    order.  Drop has no declaration: it closes its slot's gap in the
    bag's message order (ops/kernels.py bag_del_slot)."""
    svT = _reachable_svT(DYN, n=120)
    ex_on, c_on, c_off, f_on, f_off, n_e = _materialize_pair(DYN, svT)
    assert set(ex_on.delta_family_names) == {
        "BecomeLeader", "ClientRequest", "Timeout", "Duplicate",
        "UpdateTerm", "Restart"}
    # declaration coverage: 6 of the NextDynamic registry's families
    # ride the delta path now — a silently dropped declaration (or a
    # regression back to the kernel path) fails here by count, not
    # just by name
    fam_names = [f.name for f in ex_on.families]
    declared = [f.name for f in ex_on.families
                if f.delta is not None]
    assert len(declared) == 6 and len(fam_names) > len(declared)
    np.testing.assert_array_equal(np.asarray(f_on), np.asarray(f_off))
    for k in c_on:
        np.testing.assert_array_equal(
            np.asarray(c_on[k])[..., :n_e],
            np.asarray(c_off[k])[..., :n_e], err_msg=k)
    assert n_e > 100          # the grid was live


def test_paxos_delta_matmul_equals_kernel_path():
    """Paxos: ALL four families are affine — expansion of the whole
    spec runs with zero per-family kernels (the declarations-only
    vectorization proof), bit-exact vs the kernel path, incl. the
    Phase1b data-dependent report bit and Phase2b re-accept sends."""
    cfg = PaxosConfig()
    svT = _reachable_svT(cfg, n=150)
    ex_on, c_on, c_off, f_on, f_off, n_e = _materialize_pair(cfg, svT)
    assert ex_on.delta_family_names == (
        "Phase1a", "Phase1b", "Phase2a", "Phase2b")
    np.testing.assert_array_equal(np.asarray(f_on), np.asarray(f_off))
    for k in c_on:
        np.testing.assert_array_equal(
            np.asarray(c_on[k])[..., :n_e],
            np.asarray(c_off[k])[..., :n_e], err_msg=k)


# ---------------------------------------------------------------------
# fast representatives, one per engine family (tier-1).
#
# The default flipped to delta_matmul=True, so the ENTIRE existing
# differential suite now exercises the delta path against the oracle;
# fresh fast coverage is (a) the classic-engine ON ≡ OFF pair (counts
# AND archives => identical global ids), and (b) the legacy OFF
# program staying oracle-correct in each engine family — the full
# ON/OFF pairs for the parallel engines are slow-marked below.
# ---------------------------------------------------------------------


def test_engine_delta_on_off_tiny():
    e_on = Engine(TINY, chunk=64, store_states=True, delta_matmul=True)
    r_on = e_on.check(max_depth=9)
    e_off = Engine(TINY, chunk=64, store_states=True,
                   delta_matmul=False)
    r_off = e_off.check(max_depth=9)
    assert _key(r_on) == _key(r_off)
    assert r_on.delta_matmul == 1 and r_off.delta_matmul == 0
    for pa, pb in zip(e_on._parents, e_off._parents):
        np.testing.assert_array_equal(pa, pb)
    for la, lb in zip(e_on._lanes, e_off._lanes):
        np.testing.assert_array_equal(la, lb)


def test_spill_delta_off_matches_oracle():
    r = SpillEngine(TINY, chunk=64, store_states=False, seg=1 << 10,
                    vcap=1 << 12, sync_every=2,
                    delta_matmul=False).check(max_depth=6)
    assert r.delta_matmul == 0
    assert _key(r) == _oracle_key(TINY, max_depth=6)


def test_pjit_delta_off_matches_oracle():
    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    r = PjitShardedEngine(TINY, chunk=64, store_states=False,
                          delta_matmul=False).check(max_depth=6)
    assert r.delta_matmul == 0
    assert _key(r) == _oracle_key(TINY, max_depth=6)


def test_sim_delta_bit_identical_trajectories():
    """The sim engine: same seed, delta ON vs OFF — walker
    trajectories, counters and Bloom estimates all bit-identical
    (identical guards => identical draws => step_lanes must land the
    identical successor through the group matmul)."""
    from raft_tla_tpu.sim.walker import SimEngine
    cfg = TINY.with_(invariants=("ElectionSafety",))
    out = {}
    for dm in (True, False):
        eng = SimEngine(cfg, walkers=8, max_depth=8, seed=3,
                        bloom_bits=12, delta_matmul=dm)
        r = eng.run(steps=24, steps_per_dispatch=8, stop_on_hit=False)
        out[dm] = (r.walker_steps, r.sampled_steps, r.restarts,
                   r.deadlocks, r.promotions, len(r.hits),
                   round(float(r.est_distinct_states), 3))
    assert out[True] == out[False]


def test_paxos_engine_delta_on_off_full_space():
    """Paxos stock model end-to-end: ON ≡ OFF on the full 857-state
    symmetric space (tiny, so the full space IS the fast rep) — the
    declarations-only tenant never touches a hand-written kernel on
    the delta path."""
    pc = PaxosConfig()
    r_on = Engine(pc, chunk=128, store_states=False,
                  delta_matmul=True).check()
    r_off = Engine(pc, chunk=128, store_states=False,
                   delta_matmul=False).check()
    assert _key(r_on) == _key(r_off)
    assert r_on.distinct_states == 857
    assert r_on.delta_matmul == 1 and r_off.delta_matmul == 0


@pytest.mark.slow  # tier-1 budget (round 14): ~38s; batched-serve
# runs with delta ON (the default) in every fast test_serve rep, and
# tools/delta_smoke.py pins CLI ON≡OFF counts each CI run.
def test_serve_batch_delta_wave_matches_sequential():
    """A batched `cli batch` wave with delta ON (the default) is
    bit-exact per job vs the sequential reference — the job-vmapped
    burst core vmaps the group delta matmul cleanly.  (The reference
    is ONE solo engine checked per depth gate — what run_jobs
    --sequential does per job, minus the per-job engine compiles the
    tier-1 budget can't afford.)"""
    from raft_tla_tpu.serve import Job, run_jobs

    rb = run_jobs([Job(MICRO, max_depth=4, label="a",
                       store_states=False),
                   Job(MICRO, max_depth=6, label="b",
                       store_states=False)])
    solo = Engine(MICRO, store_states=False)
    for ob, depth in zip(rb.outcomes, (4, 6)):
        rs = solo.check(max_depth=depth)
        assert ob.status == "done"
        assert ob.report["distinct_states"] == rs.distinct_states
        assert ob.report["generated_states"] == rs.generated_states
        assert ob.report["depth"] == rs.depth
        assert ob.report["level_sizes"] == list(rs.level_sizes)
        assert ob.report["violations"] == len(rs.violations)
        assert ob.report["delta_matmul"] == 1


# ---------------------------------------------------------------------
# the fallback contract: a family WITHOUT a delta declaration silently
# keeps the kernel path (acceptance pin: strip one declaration)
# ---------------------------------------------------------------------


def test_family_without_delta_declaration_uses_kernel_path():
    ir = get_spec("raft")
    orig = ir.build_families

    def stripped(lay):
        fams = orig(lay)
        for f in fams:
            if f.name == "Timeout":
                f.delta = None            # Family is a plain dataclass
        return fams

    # SpecIR is a frozen dataclass and the registry caches the
    # instance: swap the hook via object.__setattr__, restore always
    object.__setattr__(ir, "build_families", stripped)
    try:
        ex = Expander(TINY, delta_matmul=True)
        assert "Timeout" not in ex.delta_family_names
        assert "ClientRequest" in ex.delta_family_names
        r_on = Engine(TINY, chunk=64, store_states=False,
                      delta_matmul=True).check(max_depth=6)
        # still stamped ON: the group just lost one family
        assert r_on.delta_matmul == 1
    finally:
        object.__setattr__(ir, "build_families", orig)
    assert _key(r_on) == _oracle_key(TINY, max_depth=6)


@pytest.mark.smoke
def test_delta_group_compiles_and_validates():
    """Group compilation invariants: the matrices cover exactly the
    declared families' lanes, V has one source per triple, P one slot
    per triple — and a declaration writing outside the state view
    fails loudly naming the family."""
    from raft_tla_tpu.engine.expand import Family
    ex = Expander(TINY, delta_matmul=True)
    dg = ex._dgroup
    assert dg["n_lanes"] == sum(
        f.n_lanes for f in ex.families if f.delta is not None)
    assert (np.asarray(dg["Q"]).sum(axis=0) == 1).all()
    assert (np.asarray(dg["P"]).sum(axis=1) == 1).all()
    # lane_to_aff marks exactly the affine lanes
    marked = (np.asarray(dg["lane_to_aff"]) >= 0).sum()
    assert marked == dg["n_lanes"]
    # a bad declaration errors by family name, not a jit traceback
    ir = get_spec("raft")
    orig = ir.build_families

    def bad(lay):
        fams = orig(lay)
        fams[1] = Family(
            fams[1].name, fams[1].fn, fams[1].params, fams[1].labeler,
            guard=fams[1].guard,
            delta=lambda off, lay, i: [(10 ** 9, 0, 1)])
        return fams

    object.__setattr__(ir, "build_families", bad)
    try:
        with pytest.raises(KeyError, match="BecomeLeader"):
            Expander(TINY, delta_matmul=True)
    finally:
        object.__setattr__(ir, "build_families", orig)


# ---------------------------------------------------------------------
# full-space duplicates (slow: the 870s tier-1 budget)
# ---------------------------------------------------------------------


@pytest.mark.slow
def test_engine_delta_full_space_archives_and_traces():
    """Classic engine on the symmetric micro space (incremental
    fingerprints engaged): ON ≡ OFF across counts, archives (=>
    identical global ids) and a replayed witness trace."""
    e_on = Engine(MICRO, chunk=64, store_states=True, delta_matmul=True)
    r_on = e_on.check()
    e_off = Engine(MICRO, chunk=64, store_states=True,
                   delta_matmul=False)
    r_off = e_off.check()
    assert _key(r_on) == _key(r_off)
    for sa, sb in zip(e_on._states, e_off._states):
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])
    gid = r_on.distinct_states - 1
    ta = [(lbl, repr(sv)) for lbl, sv in e_on.trace(gid)]
    tb = [(lbl, repr(sv)) for lbl, sv in e_off.trace(gid)]
    assert ta == tb


@pytest.mark.slow
def test_delta_violation_states_identical():
    """Scenario witness hunt: reported violation ids, states and
    traces match ON vs OFF."""
    cfg = TINY.with_(invariants=("FirstBecomeLeader",))
    outs = {}
    for dm in (True, False):
        eng = Engine(cfg, chunk=64, store_states=True, delta_matmul=dm)
        r = eng.check(stop_on_violation=True)
        assert r.violations, "scenario witness not found"
        v = r.violations[0]
        outs[dm] = (v.invariant, v.state_id, repr(v.state),
                    [(lbl, repr(sv)) for lbl, sv in
                     eng.trace(v.state_id)])
    assert outs[True] == outs[False]


@pytest.mark.slow
def test_spill_delta_on_off_full_space():
    rs = {}
    for dm in (True, False):
        rs[dm] = SpillEngine(MICRO, chunk=64, store_states=False,
                             seg=1 << 10, vcap=1 << 12, sync_every=2,
                             delta_matmul=dm).check()
    assert _key(rs[True]) == _key(rs[False])


@pytest.mark.slow
def test_delta_without_guard_matmul_cross_mode():
    """The two MXU flags are independent: delta ON composes with the
    legacy guard lane sweep (guard_matmul=False) bit-exactly."""
    r_a = Engine(TINY, chunk=64, store_states=False,
                 guard_matmul=False, delta_matmul=True).check()
    r_b = Engine(TINY, chunk=64, store_states=False,
                 guard_matmul=False, delta_matmul=False).check()
    assert _key(r_a) == _key(r_b)
    assert r_a.delta_matmul == 1 and r_a.guard_matmul == 0


@pytest.mark.slow
def test_paxos_multi_instance_delta_on_off():
    """Multi-instance paxos (I=2): instance-major lane grids stay
    bit-exact through the group delta."""
    pc = PaxosConfig(n_instances=2)
    r_on = Engine(pc, chunk=128, store_states=False,
                  delta_matmul=True).check(max_depth=8)
    r_off = Engine(pc, chunk=128, store_states=False,
                   delta_matmul=False).check(max_depth=8)
    assert _key(r_on) == _key(r_off)


# ---------------------------------------------------------------------
# chunk skip (round 14, the ROADMAP item-3 leftover): the delta group
# applies as per-family lax.cond blocks, skipping a family's whole
# cap-wide slice when the chunk enables none of its lanes.  Default
# follows the MXU lowering (ON on TPU, OFF on CPU); forced ON here to
# pin both cond branches bit-exact against the kernel path.
# ---------------------------------------------------------------------


def _materialize_skip(cfg, svT):
    """cand under delta_chunk_skip=True on a real guard mask, plus the
    kernel-path reference and per-family enabled counts."""
    ex_skip = Expander(cfg, delta_matmul=True, delta_chunk_skip=True)
    ex_off = Expander(cfg, delta_matmul=False)
    assert ex_skip.delta_chunk_skip and not ex_off.delta_chunk_skip
    derT = ex_skip.derived_batch_T(svT)
    ok = np.asarray(ex_skip.guards_T(svT, derT))
    B = ok.shape[0]
    okf = jnp.asarray(ok.reshape(-1))
    FCAP = int(ok.sum()) + 8
    epos = jnp.where(okf, jnp.cumsum(okf.astype(jnp.int32)) - 1, FCAP)
    caps = ex_skip.default_fam_caps(B)
    c_skip, f_skip = jax.jit(lambda s, d: ex_skip.materialize(
        s, d, okf, epos, FCAP, caps))(svT, derT)
    c_off, f_off = jax.jit(lambda s, d: ex_off.materialize(
        s, d, okf, epos, FCAP, caps))(svT, derT)
    famx = {ex_skip.families[fi].name: int(np.asarray(f_skip)[fi])
            for fi in ex_skip._dgroup["fam_idx"]}
    return c_skip, c_off, f_skip, f_off, int(ok.sum()), famx


@pytest.mark.slow
def test_delta_chunk_skip_equals_kernel_path():
    """Reachable-state batch: some affine families enable lanes (apply
    branch), others none (skip branch — early BFS states have no
    leader, so BecomeLeader/ClientRequest sit disabled) — columns
    bit-equal to the kernel path either way.  (The fast/smoke rep is
    the root-chunk test below; the engine-scale pair is slow too.)"""
    svT = _reachable_svT(DYN, n=120)
    c_skip, c_off, f_skip, f_off, n_e, famx = _materialize_skip(
        DYN, svT)
    np.testing.assert_array_equal(np.asarray(f_skip),
                                  np.asarray(f_off))
    assert any(v > 0 for v in famx.values()), famx
    assert any(v == 0 for v in famx.values()), famx
    for k in c_skip:
        np.testing.assert_array_equal(
            np.asarray(c_skip[k])[..., :n_e],
            np.asarray(c_off[k])[..., :n_e], err_msg=k)


@pytest.mark.smoke
def test_delta_chunk_skip_all_disabled_families():
    """Root-only chunk: several affine families enable NO lanes, so
    their conds take the SKIP branch — enabled successors still
    bit-equal the kernel path (the skipped slices were compaction
    garbage no consumer reads)."""
    ir = get_spec("raft")
    lay = ir.make_layout(DYN)
    row = ir.widen(ir.encode(lay, *ir.init_state(DYN)))
    svT = {k: jnp.moveaxis(jnp.asarray(np.stack([np.asarray(v)] * 4)),
                           0, -1) for k, v in row.items()}
    c_skip, c_off, f_skip, f_off, n_e, famx = _materialize_skip(
        DYN, svT)
    np.testing.assert_array_equal(np.asarray(f_skip),
                                  np.asarray(f_off))
    # the init chunk really drives the skip branch: Timeout fires,
    # the message-dependent affine family (Duplicate) cannot
    assert famx["Timeout"] > 0 and famx["Duplicate"] == 0, famx
    assert n_e > 0
    for k in c_skip:
        np.testing.assert_array_equal(
            np.asarray(c_skip[k])[..., :n_e],
            np.asarray(c_off[k])[..., :n_e], err_msg=k)


@pytest.mark.slow
def test_engine_delta_chunk_skip_full_space():
    """End-to-end: a chunk-skip engine reproduces the default engine's
    counts, archives and gids over the full TINY space (per-level
    chunks routinely enable only a subset of families — both cond
    branches exercised at engine scale)."""
    e_skip = Engine(TINY, chunk=64, store_states=True,
                    delta_chunk_skip=True)
    r_skip = e_skip.check()
    e_def = Engine(TINY, chunk=64, store_states=True)
    r_def = e_def.check()
    assert _key(r_skip) == _key(r_def)
    for pa, pb in zip(e_skip._parents, e_def._parents):
        np.testing.assert_array_equal(pa, pb)
    for la, lb in zip(e_skip._lanes, e_def._lanes):
        np.testing.assert_array_equal(la, lb)
