"""MXU-native expansion (round 9): guard grid as int8 matmul, batched
successor einsum; the claim-insert dedup against a sequential twin.

The contract is bit-exactness BY CONSTRUCTION, pinned differentially:
``guard_matmul=True`` (default) must be an exact drop-in for the
historical vmapped lane sweep in EVERY engine — counts, level sizes,
global ids, archives, witness traces, violation states — and the
parallel claim-insert must reproduce a sequential probe/claim walk's
outcomes (fresh set, slots, table contents) on forced-collision
fixtures.  One fast representative per engine family runs in tier-1;
the full-space duplicates are slow-marked (870s budget)."""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC, \
    NEXT_DYNAMIC
from raft_tla_tpu.engine.bfs import Engine, U32MAX
from raft_tla_tpu.engine.expand import Expander, parse_fam_density
from raft_tla_tpu.engine.spill import SpillEngine

# tiny configs (test_obs/test_burst shapes: small spaces, fast)
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

# NextDynamic at S=3: every action family (incl. the membership pair)
# gets lanes, so the guard matrix is exercised row-complete
DYN = ModelConfig(
    n_servers=3, init_servers=(0, 1), values=(1,),
    next_family=NEXT_DYNAMIC, symmetry=False, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                       max_client_requests=1))


def _key(r):
    return (r.distinct_states, r.generated_states, r.depth,
            tuple(r.level_sizes), r.violations_global)


def _reachable_svT(cfg, n=150):
    """A batch of reachable states, batch-last, via the oracle."""
    from conftest import cached_explore
    from raft_tla_tpu.ops.codec import encode, widen
    from raft_tla_tpu.ops.layout import Layout
    lay = Layout(cfg)
    r = cached_explore(cfg, max_states=3 * n, keep_states=True)
    pairs = list(r.states.values())[:n]
    rows = [encode(lay, sv, h) for sv, h in pairs]
    batch = widen({k: np.stack([s[k] for s in rows]) for k in rows[0]})
    return {k: jnp.moveaxis(jnp.asarray(v), 0, -1)
            for k, v in batch.items()}


# ---------------------------------------------------------------------
# guard grid: matmul ≡ lane sweep (the @smoke acceptance pin)
# ---------------------------------------------------------------------


@pytest.mark.smoke
def test_guard_matmul_equals_lane_sweep_on_reachable_states():
    """The packed int8 guard matrix reproduces every lane's enabling
    guard exactly on reachable NextDynamic states (all families incl.
    the signed-weight AddNewServer row)."""
    svT = _reachable_svT(DYN, n=120)
    ex_on = Expander(DYN, guard_matmul=True)
    ex_off = Expander(DYN, guard_matmul=False)
    derT = ex_on.derived_batch_T(svT)
    ok_mm = np.asarray(ex_on.guards_T(svT, derT))
    ok_ln = np.asarray(ex_off.guards_T(svT, derT))
    np.testing.assert_array_equal(ok_mm, ok_ln)
    # and the grid is live (some lanes enabled, some not)
    assert ok_mm.any() and not ok_mm.all()


def test_engine_guard_matmul_on_off_tiny():
    """Fast classic-engine representative: ON ≡ OFF end to end (counts,
    ids via archives) on the tiny config, burst default.  Depth-capped
    for the tier-1 budget — the full space runs in the slow duplicate
    below (and tools/ci_smoke.sh runs the CLI-level ON ≡ OFF smoke)."""
    e_on = Engine(TINY, chunk=64, store_states=True, guard_matmul=True)
    r_on = e_on.check(max_depth=12)
    e_off = Engine(TINY, chunk=64, store_states=True,
                   guard_matmul=False)
    r_off = e_off.check(max_depth=12)
    assert _key(r_on) == _key(r_off)
    assert r_on.guard_matmul == 1 and r_off.guard_matmul == 0
    for pa, pb in zip(e_on._parents, e_off._parents):
        np.testing.assert_array_equal(pa, pb)
    for la, lb in zip(e_on._lanes, e_off._lanes):
        np.testing.assert_array_equal(la, lb)


# ---------------------------------------------------------------------
# fast representatives, one per engine family (tier-1).
#
# The default flipped to guard_matmul=True, so the ENTIRE existing
# differential suite now exercises the matmul path against the oracle;
# what needs fresh fast coverage is (a) the classic-engine ON ≡ OFF
# pair above and (b) the legacy OFF program staying oracle-correct in
# each engine family (one run each — the full ON/OFF pairs for the
# parallel engines are slow-marked below, ~1 min apiece).
# ---------------------------------------------------------------------


def _oracle_key(cfg, max_depth=10 ** 9):
    from conftest import cached_explore
    w = cached_explore(cfg, max_depth=max_depth)
    return (w.distinct_states, w.generated_states, w.depth,
            tuple(w.level_sizes), len(w.violations))


def _engine_key(r):
    return (r.distinct_states, r.generated_states, r.depth,
            tuple(r.level_sizes), r.violations_global)


@pytest.mark.slow
def test_spill_lane_path_matches_oracle():
    # slow-marked (round-13 suite diet): the legacy guard_matmul=False
    # sweep on the spill family — its DEFAULT guard path stays fast in
    # tests/test_delta_matmul.py (spill-vs-oracle with guard ON), and
    # the classic family's fast ON≡OFF pair covers the sweep program
    r = SpillEngine(TINY, chunk=64, store_states=False, seg=1 << 10,
                    vcap=1 << 12, sync_every=2,
                    guard_matmul=False).check(max_depth=10)
    assert r.guard_matmul == 0
    assert _engine_key(r) == _oracle_key(TINY, max_depth=10)


def test_sim_guard_matmul_bit_identical_trajectories():
    """The sim engine: same seed, matmul ON vs OFF — walker
    trajectories, counters and Bloom estimates all bit-identical
    (guards identical => identical uniform draws => identical
    step_lanes selections)."""
    from raft_tla_tpu.sim.walker import SimEngine
    cfg = TINY.with_(invariants=("ElectionSafety",))
    out = {}
    for gm in (True, False):
        eng = SimEngine(cfg, walkers=8, max_depth=8, seed=3,
                        bloom_bits=12, guard_matmul=gm)
        r = eng.run(steps=24, steps_per_dispatch=8, stop_on_hit=False)
        out[gm] = (r.walker_steps, r.sampled_steps, r.restarts,
                   r.deadlocks, r.promotions, len(r.hits),
                   round(float(r.est_distinct_states), 3))
    assert out[True] == out[False]


# ---------------------------------------------------------------------
# claim-insert dedup ≡ sequential host twin (forced collisions)
# ---------------------------------------------------------------------


def _sequential_claim_insert(table, keys, live, home):
    """Plain reference: lanes in index order, each quadratic-probes
    (pos += ++t) until it meets its key (duplicate) or an empty slot
    (claim)."""
    table = [list(t) for t in table]
    vcap = len(table[0])
    fresh, pos = [], []
    for m in range(len(live)):
        key = [int(k[m]) for k in keys]
        p, t = int(home[m]), 0
        if not live[m]:
            fresh.append(False)
            pos.append(p)
            continue
        while True:
            cur = [tw[p] for tw in table]
            if cur == key:
                fresh.append(False)
                break
            if all(c == 0xFFFFFFFF for c in cur):
                for w, tw in enumerate(table):
                    tw[p] = key[w]
                fresh.append(True)
                break
            t += 1
            p = (p + t) & (vcap - 1)
        pos.append(p)
    return table, fresh, pos


def test_claim_insert_forced_collision_fixture():
    """A small table, few distinct keys, many duplicates and dead
    lanes, a pre-populated cohort: the parallel claim/scatter-min walk
    must land on the sequential-by-rank fixpoint — same table
    contents, fresh set and final slot for every live lane."""
    eng = Engine(MICRO, chunk=64, store_states=False)
    W = eng.W
    rng = np.random.RandomState(7)
    VCAP, M = 128, 96
    distinct = rng.randint(0, 1 << 32, size=(24, W)).astype(np.uint32)
    keys_np = distinct[rng.randint(0, 24, size=M)]
    live_np = rng.rand(M) > 0.2
    keys_np[~live_np] = 0xFFFFFFFF
    keys = tuple(jnp.asarray(keys_np[:, w]) for w in range(W))
    live = jnp.asarray(live_np)
    table0 = tuple(jnp.full((VCAP,), U32MAX) for _ in range(W))
    claims0 = jnp.full((VCAP,), U32MAX)
    # pre-populate (cross-call duplicate detection)
    pre = tuple(jnp.asarray(distinct[:4, w]) for w in range(W))
    t1, c1, _f, _p, _h = eng._probe_insert(
        table0, claims0, pre, jnp.ones(4, bool),
        jnp.arange(4, dtype=jnp.uint32))
    tA, _cA, fA, pA, hA = eng._probe_insert(
        t1, c1, keys, live, jnp.arange(M, dtype=jnp.uint32))
    home = np.asarray(eng._home(keys, VCAP))
    tB, fB, pB = _sequential_claim_insert(
        [np.asarray(t) for t in t1], keys_np.T, live_np, home)
    for w in range(W):
        np.testing.assert_array_equal(np.asarray(tA[w]), tB[w])
    np.testing.assert_array_equal(np.asarray(fA), fB)
    np.testing.assert_array_equal(np.asarray(pA)[live_np],
                                  np.asarray(pB)[live_np])
    assert not bool(hA)
    # the fixture actually forced duplicates AND dead lanes
    assert fA.sum() < live_np.sum()


# ---------------------------------------------------------------------
# fam-cap-density tunable (satellite)
# ---------------------------------------------------------------------


@pytest.mark.smoke
def test_fam_cap_density_parse_and_validate():
    assert parse_fam_density("Receive=8, Timeout=2") == {
        "Receive": 8, "Timeout": 2}
    with pytest.raises(ValueError, match="unknown action family"):
        parse_fam_density("NoSuchFamily=3")
    with pytest.raises(ValueError, match="must be >= 1"):
        parse_fam_density("Receive=0")
    with pytest.raises(ValueError, match="must be an integer"):
        parse_fam_density("Receive=abc")
    with pytest.raises(ValueError, match="fam=k"):
        parse_fam_density("Receive")
    # engine kwarg path raises the same clear error, not a jit trace
    with pytest.raises(ValueError, match="unknown action family"):
        Engine(TINY, chunk=64, fam_density={"Nope": 2})


def test_fam_cap_density_changes_caps_not_counts():
    """A density override resizes the materialization buffers only —
    counts are invariant (overflowing families grow-and-replay).
    Compared against the oracle (one engine run, tier-1 budget)."""
    e_dflt = Engine(TINY, chunk=64, store_states=False)
    e_tight = Engine(TINY, chunk=64, store_states=False,
                     fam_density={"Receive": 1, "UpdateTerm": 1})
    assert e_tight.FAM_CAPS != e_dflt.FAM_CAPS
    r = e_tight.check(max_depth=10)
    assert _engine_key(r) == _oracle_key(TINY, max_depth=10)


# ---------------------------------------------------------------------
# full-space duplicates (slow: the 870s tier-1 budget)
# ---------------------------------------------------------------------


@pytest.mark.slow
def test_engine_guard_matmul_full_space_archives_and_traces():
    """Classic engine on the symmetric micro space: ON ≡ OFF across
    counts, archives (=> identical global ids) and a replayed trace."""
    e_on = Engine(MICRO, chunk=64, store_states=True, guard_matmul=True)
    r_on = e_on.check()
    e_off = Engine(MICRO, chunk=64, store_states=True,
                   guard_matmul=False)
    r_off = e_off.check()
    assert _key(r_on) == _key(r_off)
    for sa, sb in zip(e_on._states, e_off._states):
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])
    # witness-trace parity on an arbitrary deep state
    gid = r_on.distinct_states - 1
    ta = [(lbl, repr(sv)) for lbl, sv in e_on.trace(gid)]
    tb = [(lbl, repr(sv)) for lbl, sv in e_off.trace(gid)]
    assert ta == tb


@pytest.mark.slow
def test_guard_matmul_violation_states_identical():
    """Scenario witness hunt (negated-reachability 'violation'): the
    reported violation ids, states and traces match ON vs OFF."""
    cfg = TINY.with_(invariants=("FirstBecomeLeader",))
    outs = {}
    for gm in (True, False):
        eng = Engine(cfg, chunk=64, store_states=True, guard_matmul=gm)
        r = eng.check(stop_on_violation=True)
        assert r.violations, "scenario witness not found"
        v = r.violations[0]
        outs[gm] = (v.invariant, v.state_id, repr(v.state),
                    [(lbl, repr(sv)) for lbl, sv in
                     eng.trace(v.state_id)])
    assert outs[True] == outs[False]


@pytest.mark.slow
def test_spill_guard_matmul_full_space_with_bursts():
    """Spill engine with squeezed segments (burst + segment driver both
    engaged): ON ≡ OFF, and the OCAP-compacted burst path commits."""
    rs = {}
    for gm in (True, False):
        eng = SpillEngine(MICRO, chunk=64, store_states=False,
                          seg=1 << 10, vcap=1 << 12, sync_every=2,
                          guard_matmul=gm)
        rs[gm] = eng.check()
        assert rs[gm].levels_fused > 0
    assert _key(rs[True]) == _key(rs[False])


