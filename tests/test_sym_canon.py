"""Orbit-sort symmetry canonicalization (round 15).

The sort canonicalizer (engine/fingerprint) must induce EXACTLY the
orbit partition of the P-fold min-over-perms on every config shape:
equivariant per-server signatures + argsort pick one canonical
relabeling, adjacent-transposition certificates verify signature
ties, and any uncertified tie (a WL-hard state) falls back to the
full min-over-perms.  These tests pin the partition against the
oracle's ``symmetry_perms`` canonicalization, the hard-fallback
trigger on constructed WL-hard fixtures, the cross-mode checkpoint
refusal, the mesh chunk rounding, and the sim Bloom staying
canonical at S=5 (P=120)."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tla_tpu.config import (Bounds, ModelConfig, NEXT_ASYNC,
                                 NEXT_DYNAMIC)
from raft_tla_tpu.engine.fingerprint import (Fingerprinter,
                                             resolve_sym_canon)
from raft_tla_tpu.models.explore import canonicalize, symmetry_perms
from raft_tla_tpu.models.raft import init_state
from raft_tla_tpu.ops.codec import encode, stack
from raft_tla_tpu.ops.layout import Layout

# S=3 with a 2-server init block: the perm group is the inside x
# outside block product (models/explore.symmetry_perms), so this pins
# the per-block argsort + per-block salts
DYN3 = ModelConfig(
    n_servers=3, init_servers=(0, 1), values=(1,),
    next_family=NEXT_DYNAMIC, max_inflight_override=6,
    bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                       max_client_requests=1,
                       max_membership_changes=1),
    symmetry=True)

# BASELINE config #5 shape: Server=5 all-init (full S_5, P=120) —
# the group size where min-over-perms stops being viable
CFG5 = ModelConfig(
    n_servers=5, init_servers=(0, 1, 2, 3, 4), values=(1,),
    next_family=NEXT_ASYNC, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=4, max_timeouts=3,
                       max_client_requests=3),
    symmetry=True)


def _partition(keys):
    groups = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    return sorted(tuple(v) for v in groups.values())


def _raft_batch(cfg, pairs):
    lay = Layout(cfg)
    return stack([encode(lay, s, h) for s, h in pairs])


def _fp_partition(fpr, arrs):
    svb = {k: jnp.asarray(v) for k, v in arrs.items()}
    fp = np.asarray(jax.jit(fpr.fingerprint_batch)(svb))
    return _partition([tuple(r) for r in fp]), fp


@pytest.fixture(scope="module")
def fpr5():
    """The ONE (minperm, sort) fingerprinter pair at P=120 — the
    120-way vmap compiles once per shape, shared module-wide."""
    return (Fingerprinter(CFG5, "minperm"), Fingerprinter(CFG5, "sort"))


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_resolve_sym_canon():
    # symmetry off: there is no group to canonicalize over
    assert resolve_sym_canon(CFG5.with_(symmetry=False), "auto") \
        == "minperm"
    assert resolve_sym_canon(CFG5.with_(symmetry=False), "sort") \
        == "minperm"
    # auto: sort only past the tiny-group threshold
    assert resolve_sym_canon(DYN3, "auto") == "minperm"      # P = 2
    assert resolve_sym_canon(CFG5, "auto") == "sort"         # P = 120
    # explicit modes pass through
    assert resolve_sym_canon(DYN3, "sort") == "sort"
    assert resolve_sym_canon(CFG5, "minperm") == "minperm"
    with pytest.raises(ValueError, match="sym_canon"):
        resolve_sym_canon(DYN3, "fast")


@pytest.mark.smoke
def test_sort_mode_disables_incremental_fp():
    fpr = Fingerprinter(DYN3, "sort")
    assert fpr.sym_canon == "sort"
    assert not fpr.supports_incremental()
    assert Fingerprinter(DYN3, "minperm").supports_incremental()


# ---------------------------------------------------------------------------
# orbit-partition parity vs the oracle canonicalization
# ---------------------------------------------------------------------------

def test_orbit_partition_parity_dynamic_blocks():
    """NextDynamic S=3 (inside/outside perm blocks): the sort and
    minperm partitions both equal the oracle's min-over-perms orbit
    partition, and the two modes' VALUES differ (the mode-separation
    bijection)."""
    from conftest import cached_explore
    res = cached_explore(DYN3.with_(symmetry=False), max_depth=10 ** 9,
                         max_states=800, keep_states=True)
    pairs = list(res.states.values())
    perms = symmetry_perms(DYN3)
    po = _partition([canonicalize(s, perms, DYN3) for s, _h in pairs])
    arrs = _raft_batch(DYN3, pairs)
    pm, fm = _fp_partition(Fingerprinter(DYN3, "minperm"), arrs)
    ps, fs = _fp_partition(Fingerprinter(DYN3, "sort"), arrs)
    assert pm == po
    assert ps == po
    assert not np.array_equal(fm, fs)


def test_orbit_partition_parity_config5_shape(fpr5):
    """Config #5 shape (S=5 all-init, P=120), depth-capped: sort ≡
    minperm ≡ oracle on every reachable state, and the per-state
    fingerprint path matches the batch path."""
    from conftest import cached_explore
    fpr_m, fpr_s = fpr5
    res = cached_explore(CFG5.with_(symmetry=False), max_depth=3,
                         keep_states=True)
    pairs = list(res.states.values())
    assert len(pairs) > 100
    perms = symmetry_perms(CFG5)
    po = _partition([canonicalize(s, perms, CFG5) for s, _h in pairs])
    arrs = _raft_batch(CFG5, pairs)
    pm, _fm = _fp_partition(fpr_m, arrs)
    ps, fs = _fp_partition(fpr_s, arrs)
    assert pm == po
    assert ps == po
    one = {k: jnp.asarray(v[0]) for k, v in arrs.items()}
    f1 = np.asarray(jax.jit(fpr_s.fingerprint)(one))
    assert (f1 == fs[0]).all()


@pytest.mark.slow
def test_orbit_partition_parity_s5_deeper(fpr5):
    """Deeper S=5 sweep (the fast rep's full-space duplicate)."""
    from conftest import cached_explore
    fpr_m, fpr_s = fpr5
    res = cached_explore(CFG5.with_(symmetry=False), max_depth=4,
                         max_states=4000, keep_states=True)
    pairs = list(res.states.values())
    perms = symmetry_perms(CFG5)
    po = _partition([canonicalize(s, perms, CFG5) for s, _h in pairs])
    arrs = _raft_batch(CFG5, pairs)
    assert _fp_partition(fpr_m, arrs)[0] == po
    assert _fp_partition(fpr_s, arrs)[0] == po


def test_signature_tie_hard_fallback(fpr5):
    """WL-hard fixtures: servers identical except the vf functional
    graph.  1-WL refinement cannot rank them (every server has in/out
    degree 1), so the argsort tie is real and UNCERTIFIED — the
    min-over-perms fallback must fire, isomorphic 5-cycles must
    collide, and distinct cycle types must separate."""
    _fpr_m, fpr_s = fpr5

    def vf_state(vf):
        sv, h = init_state(CFG5)
        return sv._replace(vf=tuple(vf)), h

    fixtures = [
        vf_state((1, 2, 3, 4, 0)),    # 5-cycle i -> i+1
        vf_state((2, 3, 4, 0, 1)),    # 5-cycle i -> i+2 (isomorphic)
        vf_state((1, 2, 0, 4, 3)),    # 3-cycle + 2-cycle
        vf_state((1, 0, 2, 4, 3)),    # 2-cycle + fixed + 2-cycle
    ]
    perms = symmetry_perms(CFG5)
    po = _partition([canonicalize(s, perms, CFG5)
                     for s, _h in fixtures])
    assert po == [(0, 1), (2,), (3,)]
    arrs = _raft_batch(CFG5, fixtures)
    # (minperm parity at this P is pinned by the config-#5 test — no
    # second 120-way vmap compile at this batch shape)
    assert _fp_partition(fpr_s, arrs)[0] == po
    dbg = fpr_s.sort_debug(arrs)
    # every fixture carries an uncertifiable tie -> hard fallback
    assert dbg["tie"].all()
    assert dbg["hard"].all()


def test_paxos_partition_parity():
    """Paxos full-S_N sort (affine owned-bit salt map): the sort
    partition equals min-over-perms on the stock model's reachable
    prefix; per-state equals batch."""
    from conftest import cached_explore
    from raft_tla_tpu.spec.paxos.config import PaxosConfig
    from raft_tla_tpu.spec.paxos import layout as pl
    from raft_tla_tpu.spec.paxos.fingerprint import PaxosFingerprinter
    from raft_tla_tpu.spec.paxos.layout import PaxosLayout
    cfg = PaxosConfig()
    res = cached_explore(cfg.with_(symmetry=False), max_depth=6,
                         keep_states=True)
    pairs = list(res.states.values())
    lay = PaxosLayout(cfg)
    rows = [pl.encode(lay, s, h) for s, h in pairs]
    arrs = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
    fpr_s = PaxosFingerprinter(cfg, "sort")
    pm, fm = _fp_partition(PaxosFingerprinter(cfg, "minperm"), arrs)
    ps, fs = _fp_partition(fpr_s, arrs)
    assert pm == ps
    assert len(ps) < len(pairs)          # symmetry actually collapsed
    assert not np.array_equal(fm, fs)
    one = {k: jnp.asarray(v[0]) for k, v in arrs.items()}
    f1 = np.asarray(jax.jit(fpr_s.fingerprint)(one))
    assert (f1 == fs[0]).all()


# ---------------------------------------------------------------------------
# engine surface: checkpoint refusal, chunk rounding, sim Bloom
# ---------------------------------------------------------------------------

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=4, symmetry=True,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))


def test_ckpt_read_refuses_cross_mode(tmp_path):
    """The serializer-level refusal (shared by every engine family):
    a minperm-stamped checkpoint handed to a sort engine raises a
    named CheckpointError BEFORE any array or compile is touched."""
    from raft_tla_tpu.engine.bfs import (CheckpointError, CheckResult,
                                         ckpt_read, ckpt_write)
    path = str(tmp_path / "mode.ckpt")
    meta = dict(cfg=repr(MICRO), chunk=64, spec="raft",
                sym_canon="minperm", depth=1, n_states=1, n_vis=1,
                n_front=1)
    ckpt_write(path, {"x": np.zeros(4, np.int32)}, False, [], [], [],
               CheckResult(), meta)
    with pytest.raises(CheckpointError,
                       match=r"--sym-canon minperm.*resolved sort"):
        ckpt_read(path, repr(MICRO), 64, (), sym_canon="sort")
    # a legacy checkpoint (no sym_canon key) reads as minperm
    meta.pop("sym_canon")
    ckpt_write(path, {"x": np.zeros(4, np.int32)}, False, [], [], [],
               CheckResult(), meta)
    with pytest.raises(CheckpointError, match="--sym-canon minperm"):
        ckpt_read(path, repr(MICRO), 64, (), sym_canon="sort")


@pytest.mark.slow
def test_checkpoint_refuses_cross_mode_resume(tmp_path):
    """End-to-end rep of the serializer-level refusal above: a real
    minperm run's checkpoint, a sort engine's refusal, and a
    same-mode resume that still works."""
    from raft_tla_tpu.engine.bfs import CheckpointError, Engine
    ckpt = str(tmp_path / "run.ckpt")
    Engine(MICRO, chunk=64, store_states=False,
           sym_canon="minperm").check(max_depth=6,
                                      checkpoint_path=ckpt)
    other = Engine(MICRO, chunk=64, store_states=False,
                   sym_canon="sort")
    with pytest.raises(CheckpointError,
                       match=r"--sym-canon minperm.*resolved sort"):
        other.check(resume_from=ckpt)
    # same mode resumes fine
    res = Engine(MICRO, chunk=64, store_states=False,
                 sym_canon="minperm").check(resume_from=ckpt)
    assert res.depth >= 6


@pytest.mark.smoke
def test_mesh_chunk_rounds_up_to_devices():
    from raft_tla_tpu.parallel import pjit_mesh
    assert pjit_mesh._round_chunk_to_devices(512, 8) == 512
    pjit_mesh._warned_uneven_chunk = False
    with pytest.warns(UserWarning, match="not a multiple"):
        assert pjit_mesh._round_chunk_to_devices(20, 8) == 24
    # warn-once: the second uneven call is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert pjit_mesh._round_chunk_to_devices(20, 8) == 24
    pjit_mesh._warned_uneven_chunk = False


def test_pjit_engine_rounds_chunk():
    import jax as _jax
    from raft_tla_tpu.parallel import pjit_mesh
    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    devs = _jax.devices()
    pjit_mesh._warned_uneven_chunk = False
    with pytest.warns(UserWarning, match="rounded up"):
        pe = PjitShardedEngine(MICRO, devices=devs,
                               chunk=len(devs) * 8 - 1)
    assert pe.chunk == len(devs) * 8
    pjit_mesh._warned_uneven_chunk = False


def test_sim_bloom_stays_canonical_at_s5():
    """P=120 used to force the novelty Bloom onto identity-perm
    fingerprints; under orbit-sort it stays canonical, with
    bit-identical stats across same-seed runs."""
    from raft_tla_tpu.sim import SimEngine
    cfg = CFG5.with_(invariants=(),
                     bounds=Bounds.make(max_log_length=1,
                                        max_timeouts=1,
                                        max_client_requests=1))
    eng = SimEngine(cfg, walkers=4, max_depth=8, seed=3,
                    bloom_bits=12)
    assert eng.bloom_canonical
    assert eng.fpr.sym_canon == "sort"
    st_a = eng._dispatch(eng.fresh_carry(), 12)
    st_b = eng._dispatch(eng.fresh_carry(), 12)
    for k in ("traj", "depth", "stats", "bloom"):
        assert np.array_equal(np.asarray(st_a[k]),
                              np.asarray(st_b[k])), k


@pytest.mark.smoke
def test_sim_forced_minperm_still_disables_and_names_flag():
    from raft_tla_tpu.sim import SimEngine
    cfg = CFG5.with_(invariants=(),
                     bounds=Bounds.make(max_log_length=1,
                                        max_timeouts=1,
                                        max_client_requests=1))
    with pytest.warns(UserWarning, match="--sym-canon sort"):
        eng = SimEngine(cfg, walkers=4, max_depth=8, seed=3,
                        bloom_bits=12, sym_canon="minperm")
    assert not eng.bloom_canonical
    assert eng.fpr.sym_canon == "minperm"
