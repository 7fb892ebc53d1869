"""The fresh-start set-up program (engine/bfs Engine._setup_carry).

A check's fresh start allocates its carry, narrows the deduplicated
roots, evaluates their invariants/constraints and places them in ONE
jitted program.  Its carry must equal the eager composition it replaced
(an eagerly built empty carry, host-narrowed roots, the jitted phase 2
and eager ``.at[:n].set`` placement, written out below) leaf for leaf,
bit for bit, for one Init state and for seeded starts; a seeded check
must still land on the oracle's counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.models.explore import explore

CFG = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=2, next_family=NEXT_ASYNC, symmetry=False,
    constraints=("BoundedInFlightMessages", "BoundedRequestVote",
                 "BoundedLogSize", "BoundedTerms"),
    invariants=("ElectionSafety", "LogMatching"),
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))


@pytest.fixture(scope="module")
def eng():
    return Engine(CFG, chunk=64, store_states=False)


@pytest.fixture(scope="module")
def seeds():
    """Distinct (State, Hist) pairs within two steps of Init."""
    return list(explore(CFG, max_depth=2, keep_states=True)
                .states.values())


def _eager_setup(eng, roots, rk):
    """The eager composition the set-up program replaced, placement
    included, so the reference does not run Engine._place_roots."""
    n = len(rk)
    carry = dict(eng._fresh_carry_impl(eng.LCAP, eng.VCAP, eng.FCAP,
                                       eng.OCAP))
    roots_n = {k: jnp.asarray(np.moveaxis(v, 0, -1))
               for k, v in eng.ir.narrow(eng.lay, roots).items()}
    inv_r, con_r = eng._phase2({k: jnp.asarray(v) for k, v in roots.items()})
    slots = jnp.asarray(eng._host_probe_assign(rk))
    rk = jnp.asarray(rk)
    carry["lvl"] = {k: v.at[..., :n].set(roots_n[k])
                    for k, v in carry["lvl"].items()}
    carry["vis"] = tuple(carry["vis"][w].at[slots].set(rk[:, w])
                         for w in range(eng.W))
    carry["jslot"] = carry["jslot"].at[:n].set(slots)
    carry["n_lvl"] = jnp.int32(n)
    carry["linv"] = carry["linv"].at[:, :n].set(inv_r.T)
    carry["lcon"] = carry["lcon"].at[:n].set(con_r)
    return carry


def _assert_same_carry(got, want):
    g, gdef = jax.tree_util.tree_flatten_with_path(got)
    w, wdef = jax.tree_util.tree_flatten_with_path(want)
    assert gdef == wdef
    for (path, a), (_p, b) in zip(g, w):
        name = jax.tree_util.keystr(path)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


@pytest.mark.parametrize("n_seeds", [None, 3, 5],
                         ids=["init", "seeded3", "seeded5"])
def test_setup_program_matches_eager_composition(eng, seeds, n_seeds):
    roots, rk, _ = eng._dedup_roots(
        None if n_seeds is None else seeds[:n_seeds])
    n = len(rk)
    assert n == (1 if n_seeds is None else n_seeds)
    got = eng._setup_carry(roots, rk)
    _assert_same_carry(got, _eager_setup(eng, roots, rk))
    assert int(got["n_lvl"]) == n
    # one key per root in the table
    assert int((np.asarray(got["vis"][0]) != 0xFFFFFFFF).sum()) == n


def test_setup_program_holds_one_carry(eng, seeds):
    """The fills and the root writes land in the output buffers: the
    program's scratch is a few bytes, not a second carry (a scatter
    along the batch-last row axis would transpose whole buffers)."""
    roots, rk, _ = eng._dedup_roots(seeds[:3])
    eng._setup_carry(roots, rk)
    fn = eng._setup_jit_cache[(eng.LCAP, eng.VCAP, eng.FCAP, eng.OCAP, 3)]
    mem = fn.lower(roots, eng._host_probe_assign(rk), rk).compile() \
        .memory_analysis()
    assert mem.temp_size_in_bytes * 100 < mem.output_size_in_bytes, mem


def test_seeded_check_matches_oracle(seeds):
    """A whole check from three seeds: counts, level sizes, depth and
    verdicts equal the oracle's from the same seeds."""
    want = explore(CFG, seed_states=seeds[:3])
    got = Engine(CFG, chunk=64, store_states=False).check(
        seed_states=seeds[:3])
    assert got.overflow_faults == 0
    assert got.distinct_states == want.distinct_states
    assert got.generated_states == want.generated_states
    assert got.level_sizes == want.level_sizes
    assert got.depth == want.depth
    assert sorted(v.invariant for v in got.violations) == \
        sorted(v.invariant for v in want.violations)


def test_pjit_setup_program_is_born_sharded(eng):
    """The pjit engine's set-up program places the same roots into a
    carry born under its named shardings."""
    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    pj = PjitShardedEngine(CFG, devices=jax.devices()[:2], chunk=64,
                           store_states=False, lcap=eng.LCAP,
                           vcap=eng.VCAP, fcap=eng.FCAP, ocap=eng.OCAP)
    roots, rk, _ = pj._dedup_roots(None)
    got = pj._setup_carry(roots, rk)
    assert got["vis"][0].sharding.spec == pj._table_sh.spec
    assert got["lvl"]["ctr"].sharding.spec == \
        pj._carry_sh["lvl"]["ctr"].spec
    _assert_same_carry(got, eng._setup_carry(roots, rk))
