"""The message bag in message order (ops/layout.py).

Every bag the program writes keeps its occupied slots as a prefix, in
the plain reference's order of messages (bench/reference/plain_bfs.cc:
the record's bytes, an absent field last).  The lanes that walk the
bag then walk it as the reference does, so BFS keeps the same member of
a VIEW class whose members' histories differ:

- the sort key orders messages as the reference's bytes do;
- the bag kernels (put, delete, Duplicate, Drop) keep the order over
  random sequences, against a plain multiset;
- ``codec.encode`` places an oracle state's messages in that order;
- every successor the expander builds, on the kernel path and on the
  delta path, and every state an engine run stores, keeps it;
- the native checker, the oracle and the engine agree with each other
  level by level where the arrival order of the bag used to change the
  answer (the membership fixture's servers under ``Next`` at micro
  bounds, depth 16: level 16 holds 51,401 states).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.config import (MT_AEREQ, MT_AERESP, MT_CATREQ, MT_COC,
                                 MT_RVREQ, MT_RVRESP, Bounds, ModelConfig,
                                 NEXT_DYNAMIC, NEXT_FULL)
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.engine.expand import Expander
from raft_tla_tpu.models.raft import _SRC_DST, init_state
from raft_tla_tpu.ops.codec import encode, pack_msg, unpack_msg
from raft_tla_tpu.ops.kernels import RaftKernels
from raft_tla_tpu.ops.layout import MSG_FIELDS, Layout

# the membership fixture's servers (4 beyond 3 initial) at micro bounds
MICRO_BOUNDS = Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1, max_membership_changes=1)
MEMBER = ModelConfig(n_servers=4, init_servers=(0, 1, 2), values=(1, 2),
                     next_family=NEXT_DYNAMIC, symmetry=True,
                     bounds=MICRO_BOUNDS)
# two entries a message, typed config entries: every key field in play
WIDE = ModelConfig(n_servers=4, init_servers=(0, 1, 2), values=(1, 2, 3),
                   next_family=NEXT_DYNAMIC, symmetry=False,
                   bounds=Bounds.make(max_log_length=2, max_timeouts=1))


def _ref_bytes(m):
    """The plain reference's record of an oracle message tuple: type,
    term, src, dst, a, b, c (ABSENT = 255), entry count, then each entry
    as term, value, type."""
    f = MSG_FIELDS[m[0]]
    ent = m[f["ent"]] if f["ent"] is not None else ()

    def g(key):
        v = m[f[key]] if f[key] is not None else 0
        return 255 if v == -1 else v

    out = [m[0], m[1], m[f["src"]], m[f["dst"]], g("a"), g("b"), g("c"),
           len(ent)]
    for e in ent:
        out += [e[0], e[2], e[1]]
    return tuple(out)


def _key(lay, words):
    return tuple(int(x) for x in lay.msg_key(np.asarray(words)))


def _message_pool(cfg):
    """Messages of every type, with absent fields and typed entries."""
    S = cfg.n_servers
    ents = [(), ((1, 0, 1),), ((1, 0, 3),), ((1, 1, 1),), ((1, 1, 6),),
            ((2, 0, 2),), ((1, 0, 1), (2, 1, 7)), ((1, 1, 1), (1, 0, 2))]
    pool = set()
    for t, (i, j) in itertools.product((1, 2), [(0, 1), (1, 0), (2, 3)]):
        pool.add((MT_RVREQ, t, 1, 0, i, j))
        pool.add((MT_AERESP, t, 1, 2, i, j))
        pool.add((MT_COC, t, 0, j, i, i))
        for e in ents:
            e1 = e[:1]
            pool.add((MT_RVRESP, t, 1, e, i, j))
            pool.add((MT_AEREQ, t, 0, 0, e1, 1, i, j))
            for mc in (-1, 0, 1):
                pool.add((MT_CATREQ, t, 1, e, mc, i, j, 1))
    assert all(max(m[_SRC_DST[m[0]][0]], m[_SRC_DST[m[0]][1]]) < S
               for m in pool)
    return sorted(pool)


def test_message_key_is_the_references_order():
    lay = Layout(WIDE)
    pool = _message_pool(WIDE)
    by_ref = sorted(pool, key=_ref_bytes)
    by_key = sorted(pool, key=lambda m: _key(lay, pack_msg(lay, m)))
    assert by_key == by_ref
    for m in pool:            # the packing still round-trips
        assert unpack_msg(lay, pack_msg(lay, m)) == m


def _assert_ordered(lay, bag, cnt):
    """Occupied slots form a prefix, strictly increasing in key; empty
    slots are all zero."""
    bag, cnt = np.asarray(bag), np.asarray(cnt)
    n = int((cnt > 0).sum())
    assert (cnt[:n] > 0).all() and (cnt[n:] == 0).all(), cnt
    assert not bag[n:].any()
    keys = [_key(lay, bag[k]) for k in range(n)]
    assert keys == sorted(set(keys)), keys


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bag_kernels_keep_message_order(seed):
    """Random puts (merging repeats), deletes, Duplicate and Drop on
    one bag, against a plain multiset."""
    lay = Layout(WIDE)
    kern = RaftKernels(lay)
    pool = _message_pool(WIDE)
    sv = {k: jnp.asarray(v) for k, v in
          encode(lay, *init_state(WIDE)).items()}
    put = jax.jit(kern.bag_put)
    dele = jax.jit(kern.bag_del_slot)
    dup = jax.jit(kern.duplicate_message)
    drop = jax.jit(kern.drop_message)
    rng = np.random.RandomState(seed)
    model = {}
    for _ in range(120):
        n = len(model)
        op = rng.randint(4) if n else 0
        if op == 0 and n < lay.K:
            m = pool[rng.randint(len(pool))]
            sv = put(sv, jnp.asarray(pack_msg(lay, m)))
            model[m] = model.get(m, 0) + 1
        elif op in (1, 2, 3) and n:
            slot = rng.randint(n)
            m = unpack_msg(lay, np.asarray(sv["bag"][slot]))
            c = int(sv["cnt"][slot])
            assert model[m] == c
            if op == 1:
                sv = dele(sv, jnp.int32(slot))
                model[m] -= 1
            elif op == 2:
                ok, sv2 = dup(sv, jnp.int32(slot))
                if bool(ok):
                    sv = sv2
                    model[m] += 1
            else:
                ok, sv2 = drop(sv, jnp.int32(slot))
                if bool(ok):
                    sv = sv2
                    model[m] = 0
            if model[m] == 0:
                del model[m]
        _assert_ordered(lay, sv["bag"], sv["cnt"])
        got = {unpack_msg(lay, np.asarray(sv["bag"][k])): int(c)
               for k, c in enumerate(np.asarray(sv["cnt"])) if c}
        assert got == model


def test_encode_places_messages_in_message_order():
    lay = Layout(WIDE)
    sv, h = init_state(WIDE)
    pool = _message_pool(WIDE)
    rng = np.random.RandomState(7)
    picks = [pool[i] for i in rng.choice(len(pool), 12, replace=False)]
    sv = sv._replace(msgs=tuple(sorted((m, 1 + k % 2)
                                       for k, m in enumerate(picks))))
    arrs = encode(lay, sv, h)
    _assert_ordered(lay, arrs["bag"], arrs["cnt"])
    order = [unpack_msg(lay, arrs["bag"][k]) for k in range(len(picks))]
    assert order == sorted(picks, key=_ref_bytes)


@pytest.mark.parametrize("delta", [True, False])
def test_every_successor_keeps_message_order(delta):
    """All enabled successors of reachable membership states, built
    through the delta matmul (Duplicate, UpdateTerm) and the family
    kernels (Drop, Receive, CocDiscard and the sends)."""
    from conftest import cached_explore
    from raft_tla_tpu.spec import get_spec
    cfg = WIDE.with_(bounds=Bounds.make(max_log_length=1, max_timeouts=1))
    ir = get_spec("raft")
    lay = Layout(cfg)
    r = cached_explore(cfg, max_states=600, keep_states=True)
    rows = [encode(lay, sv, h) for sv, h in r.states.values()]
    svT = {k: jnp.moveaxis(jnp.asarray(v), 0, -1) for k, v in ir.widen(
        {k: np.stack([s[k] for s in rows]) for k in rows[0]}).items()}
    ex = Expander(cfg, delta_matmul=delta)
    derT = ex.derived_batch_T(svT)
    ok = np.asarray(ex.guards_T(svT, derT))
    okf = jnp.asarray(ok.reshape(-1))
    n_e = int(ok.sum())
    epos = jnp.where(okf, jnp.cumsum(okf.astype(jnp.int32)) - 1, n_e)
    caps, off = [], 0            # every family's buffer holds its lanes
    for fam in ex.families:
        caps.append(max(1, int(ok[:, off:off + fam.n_lanes].sum())))
        off += fam.n_lanes
    cand, counts = jax.jit(lambda s, d: ex.materialize(
        s, d, okf, epos, n_e, tuple(caps)))(svT, derT)
    names = [f.name for f in ex.families]
    counts = dict(zip(names, np.asarray(counts)))
    assert counts["Drop"] and counts["Duplicate"] and counts["Receive"]
    assert ("Duplicate" in ex.delta_family_names) is delta
    assert "Drop" not in ex.delta_family_names
    bag, cnt = np.asarray(cand["bag"]), np.asarray(cand["cnt"])
    for c in range(n_e):
        _assert_ordered(lay, bag[..., c], cnt[..., c])


def test_every_stored_state_keeps_message_order():
    eng = Engine(MEMBER, chunk=128)
    r = eng.check(max_depth=11)
    assert r.distinct_states > 2000
    lay = Layout(MEMBER)
    n = 0
    for blk in eng._states:
        for bag, cnt in zip(blk["bag"], blk["cnt"]):
            _assert_ordered(lay, bag, cnt)
            n += 1
    assert n == r.distinct_states


def test_oracle_and_native_agree_where_bag_order_mattered():
    """The membership fixture's servers under ``Next`` at micro bounds,
    to depth 16: the oracle walks its bag in message order, the native
    checker in slot order; in arrival order the native kept 51,402
    states in level 16, where the oracle and the plain reference keep
    51,401 (tests/benchmark/test_member_cell.py holds the engine and
    the reference to the native checker there)."""
    from raft_tla_tpu import native
    from raft_tla_tpu.models.explore import explore
    cfg = MEMBER.with_(next_family=NEXT_FULL)
    nat = native.check(cfg, threads=2, max_depth=16)
    orc = explore(cfg, max_depth=16)
    assert orc.level_sizes[-1] == 51401
    assert (nat.distinct_states, nat.generated_states, nat.depth,
            list(nat.level_sizes)) == (
        orc.distinct_states, orc.generated_states, orc.depth,
        list(orc.level_sizes))
