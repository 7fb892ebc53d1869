"""The harvest's packed device-to-host transfer (engine/pack, and its
use in engine/bfs ``Engine.check``).

- unit: pack then unpack gives the per-leaf slices exactly (dtype,
  shape, bytes) for every leaf dtype of configs #2, #3 and #4 and the
  bool invariant rows, in the per-level layout (batch-last leaves) and
  in the burst's ring stacks, at the row counts the buckets turn on;
- the row buckets: few programs per doubling, bounded padding;
- integration (CPU): one transfer per harvest that needs rows, none
  where states are not stored and nothing is violated; config #4's
  archives equal the per-level driver's and its traces agree with the
  oracle;
  a per-level violation decodes as the oracle and the burst decode it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.cfg.parser import load_model
from raft_tla_tpu.config import Bounds, ModelConfig
from raft_tla_tpu.engine import pack
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.models import predicates
from raft_tla_tpu.models.explore import explore
from raft_tla_tpu.obs import Obs, SpanRecorder
from raft_tla_tpu.spec import spec_of

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "configs")
CONFIGS = {"config2": "raft-tlc-s3-l3.cfg",
           "config3": "raft-tlc-s4-membership.cfg",
           "config4": "raft-apalache-s2-k10.cfg"}
CHUNK = 16

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=4,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1),
    symmetry=False)


def _cfg(name):
    return load_model(os.path.join(_BENCH, CONFIGS[name]))


@pytest.fixture(scope="module")
def leaf_specs():
    """Per config: (leading shape, dtype) of every state leaf, the
    invariant count, and the small engine's LCAP and OCAP."""
    out = {}
    for name in CONFIGS:
        e = Engine(_cfg(name), chunk=CHUNK, lcap=256, fcap=256, ocap=64)
        tmpl = jax.eval_shape(lambda: e._fresh_carry_impl(
            e.LCAP, e.VCAP, e.FCAP, e.OCAP))
        out[name] = dict(
            front=[(v.shape[:-1], v.dtype) for v in tmpl["front"].values()],
            n_inv=len(e.inv_names), lcap=e.LCAP, ocap=e.OCAP,
            levels=e.burst_levels, kb=e._burst_width())
    return out


def _leaves(spec, tail, seed):
    """Random device leaves in the harvest's order: parents, lanes,
    every state leaf, the invariant bits; each with ``tail`` as its
    last axes (rows, or ring levels and rows)."""
    rng = np.random.default_rng(seed)

    def rand(shape, dt):
        dt = np.dtype(dt)
        if dt == np.bool_:
            return rng.random(shape) < 0.5
        info = np.iinfo(dt)
        return rng.integers(info.min, info.max, size=shape,
                            dtype=dt, endpoint=True)

    host = [rand(tail, np.int32), rand(tail, np.int32)]
    host += [rand(lead + tail, dt) for lead, dt in spec["front"]]
    host.append(rand((spec["n_inv"],) + tail, np.bool_))
    return [jnp.asarray(x) for x in host], host


def _check_roundtrip(dev, host, n, cap, levels=None):
    rows = pack.row_bucket(n, CHUNK, cap)
    assert n <= rows <= cap
    buf = pack.pack(dev, 0, rows=rows, levels=levels)
    got = pack.unpack(np.asarray(buf), pack.layout(dev, rows, levels), rows)
    assert len(got) == len(host)
    for g, x in zip(got, host):
        want = x[..., :rows] if levels is None else x[..., :levels, :rows]
        assert g.dtype == x.dtype
        assert g.shape == want.shape
        assert g.tobytes() == np.ascontiguousarray(want).tobytes()
        np.testing.assert_array_equal(g[..., :n], want[..., :n])


def _level_rows(spec):
    # 1, chunk-1, chunk, chunk+1, the 5-chunk bucket edge and one row
    # past it, and the most rows a level may hold (LCAP - OCAP)
    return [1, CHUNK - 1, CHUNK, CHUNK + 1, 5 * CHUNK, 5 * CHUNK + 1,
            spec["lcap"] - spec["ocap"]]


@pytest.mark.parametrize("name", list(CONFIGS))
def test_pack_unpack_per_level_layout(leaf_specs, name):
    spec = leaf_specs[name]
    dev, host = _leaves(spec, (spec["lcap"],), seed=len(name))
    dtypes = {np.dtype(x.dtype) for x in host}
    assert {np.dtype(t) for t in (np.uint32, np.int32, np.int16, np.int8,
                                  np.bool_)} <= dtypes
    for n in _level_rows(spec):
        _check_roundtrip(dev, host, n, spec["lcap"])


@pytest.mark.parametrize("name", list(CONFIGS))
def test_pack_unpack_burst_ring_stacks(leaf_specs, name):
    spec = leaf_specs[name]
    L, KB = spec["levels"], spec["kb"]
    dev, host = _leaves(spec, (L, KB), seed=7 * len(name))
    for n, nlev in ((1, 1), (CHUNK - 1, 3), (CHUNK, 3), (CHUNK + 1, 7),
                    (KB, L)):
        _check_roundtrip(dev, host, n, KB, levels=nlev)


def test_pack_pads_rows_to_whole_words(leaf_specs):
    """A chunk that is no multiple of 4 makes buckets that are not
    either: the narrow leaves' words are zero-padded, the host cut
    drops the padding."""
    spec = leaf_specs["config4"]
    dev, host = _leaves(spec, (spec["levels"], 2 * CHUNK), seed=5)
    for rows, levels in ((13, None), (7, 3), (2 * CHUNK - 1, 5)):
        got = pack.unpack(np.asarray(pack.pack(dev, 0, rows=rows,
                                               levels=levels)),
                          pack.layout(dev, rows, levels), rows)
        for g, x in zip(got, host):
            want = x[..., :rows] if levels is None else \
                x[..., :levels, :rows]
            assert g.dtype == x.dtype and g.shape == want.shape
            np.testing.assert_array_equal(g, want)


def test_row_buckets_few_programs_bounded_padding():
    for chunk, cap in ((16, 1 << 16), (512, 1 << 22)):
        by_doubling = {}
        for n in range(1, 64 * chunk, max(1, chunk // 8)):
            r = pack.row_bucket(n, chunk, cap)
            c = -(-n // chunk)            # whole chunks the rows need
            assert r % chunk == 0 and n <= r
            if c > 4:
                assert r // chunk < 1.25 * c
            by_doubling.setdefault((r // chunk).bit_length(),
                                   set()).add(r)
        assert all(len(b) <= 4 for b in by_doubling.values()), by_doubling
    # never past the buffer
    assert pack.row_bucket(1000, 16, 1008) == 1008


# ---------------------------------------------------------------------
# integration: config #4 (the cell's spec) and a violating micro config
# ---------------------------------------------------------------------

C4_DEPTH = 7          # levels 1-4 fit the 4-chunk ring, 5-7 run per level


@pytest.fixture(scope="module")
def config4():
    """Config #4 to depth 7 at chunk 16: a traced burst engine storing
    states, the per-level engine storing states, and a burst engine
    storing none."""
    cfg = _cfg("config4")
    rec = SpanRecorder()
    on = Engine(cfg, chunk=CHUNK, store_states=True, burst=True)
    r_on = on.check(max_depth=C4_DEPTH, obs=Obs(spans=rec))
    off = Engine(cfg, chunk=CHUNK, store_states=True, burst=False)
    r_off = off.check(max_depth=C4_DEPTH)
    bare = Engine(cfg, chunk=CHUNK, store_states=False, burst=True)
    r_bare = bare.check(max_depth=C4_DEPTH)
    # reads bounded to 16 KiB: the wider levels come in blocks
    split = Engine(cfg, chunk=CHUNK, store_states=True, burst=False)
    split._PACK_BYTES = 1 << 14
    r_split = split.check(max_depth=C4_DEPTH)
    return dict(cfg=cfg, on=on, r_on=r_on, off=off, r_off=r_off,
                r_bare=r_bare, split=split, r_split=r_split,
                tot=rec.totals())


def test_one_transfer_per_harvest(config4):
    r_on, r_off, tot = config4["r_on"], config4["r_off"], config4["tot"]
    assert r_on.levels_fused > 0 and r_on.levels_fused < r_on.depth
    # the root's harvest runs in check_setup; every later harvest
    # (a committed burst or a per-level level) opens one span
    assert r_on.harvest_transfers == 1 + tot["harvest"]["count"]
    assert r_on.harvest_transfers == 1 + 1 + (r_on.depth
                                              - r_on.levels_fused)
    assert r_off.harvest_transfers == 1 + r_off.depth
    # the check's counter sample carries it
    assert tot["harvest_transfers"]["min"] == r_on.harvest_transfers


def test_no_transfer_without_stored_states_or_violations(config4):
    r = config4["r_bare"]
    assert r.distinct_states == config4["r_on"].distinct_states
    assert not r.violations and r.harvest_transfers == 0


def test_config4_archives_match_the_per_level_driver(config4):
    on, off = config4["on"], config4["off"]
    assert config4["r_on"].level_sizes == config4["r_off"].level_sizes
    assert len(on._parents) == len(off._parents) == C4_DEPTH + 1
    for a, b in zip(on._parents + on._lanes, off._parents + off._lanes):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    for sa, sb in zip(on._states, off._states):
        assert sa.keys() == sb.keys()
        for k in sa:
            assert sa[k].dtype == sb[k].dtype and sa[k].shape == sb[k].shape
            np.testing.assert_array_equal(sa[k], sb[k])


def test_levels_wider_than_a_read_come_in_blocks(config4):
    split, r = config4["split"], config4["r_split"]
    sizes = [len(p) for p in split._parents]
    carry = split._fresh_carry(split.LCAP, split.VCAP)
    block = split._pack_block([carry["lpar"], carry["llane"],
                               *carry["front"].values()])
    del carry
    assert CHUNK <= block < max(sizes)
    assert r.harvest_transfers == sum(-(-n // block) or 1 for n in sizes)
    off = config4["off"]
    for a, b in zip(split._parents + split._lanes,
                    off._parents + off._lanes):
        np.testing.assert_array_equal(a, b)
    for sa, sb in zip(split._states, off._states):
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k])


def _oracle_depth(cfg, target, depth, monkeypatch):
    """The oracle's BFS depth of ``target``: the length of its trace to
    a one-off invariant that state alone violates."""
    monkeypatch.setitem(predicates.INVARIANTS, "HarvestTarget",
                        lambda sv, h, c: sv != target)
    w = explore(cfg.with_(invariants=("HarvestTarget",)),
                max_depth=depth, trace_violations=True)
    (v,) = w.violations
    return len(v.trace)


def test_config4_traces_agree_with_the_oracle(config4, monkeypatch):
    """Each archived trace is a path of the oracle's transitions from
    Init, as short as the oracle's own trace to its end, and the same
    trace the per-level driver's archives give.  (Where a level holds
    two parents of one state, the engine's lane order and the oracle's
    action order may pick different ones, so the states on the way may
    differ: both are shortest paths.)"""
    cfg, on, r_on = config4["cfg"], config4["on"], config4["r_on"]
    ir = spec_of(cfg)
    # the last row of a burst level and of each per-level level
    ends = np.cumsum([len(p) for p in on._parents]) - 1
    for gid in (int(ends[2]), *(int(g) for g in ends[-3:]),
                r_on.distinct_states // 2):
        chain = on.trace(gid)
        assert chain == config4["off"].trace(gid)
        sv, h = ir.init_state(cfg)
        assert chain[0] == ("Init", sv)
        for _, nxt in chain[1:]:
            sv, h = next((s2, h2) for _, s2, h2
                         in ir.oracle_successors(sv, h, cfg) if s2 == nxt)
        assert len(chain) - 1 == _oracle_depth(cfg, chain[-1][1],
                                               C4_DEPTH, monkeypatch), gid


def test_per_level_violation_decodes_as_oracle_and_burst():
    """FirstBecomeLeader first breaks at level 9 of MICRO: the per-level
    driver decodes it from its packed read, the burst from the packed
    ring stacks; both as the oracle, with the same ids."""
    cfg = MICRO.with_(invariants=("FirstBecomeLeader",))
    want = explore(cfg, max_depth=9)
    got = {}
    for burst in (False, True):
        r = Engine(cfg, chunk=64, store_states=False,
                   burst=burst).check(max_depth=9)
        assert r.levels_fused == (9 if burst else 0)
        # the violating level's rows alone came to the host
        assert r.harvest_transfers == 1
        got[burst] = [(v.invariant, v.state_id, v.state, v.hist)
                      for v in r.violations]
    assert got[False] == got[True]
    assert sorted((v.invariant, repr(v.state)) for v in want.violations) \
        == sorted((nm, repr(s)) for nm, _, s, _ in got[False])
    assert len(got[False]) == len(want.violations) > 0
