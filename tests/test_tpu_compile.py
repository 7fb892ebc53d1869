"""Main-path programs compiled for a described TPU v5e (no chip needed).

The TPU compiler refuses what XLA:CPU accepts (the deleted Pallas dedup
kernel's scalar VMEM stores were one such case), and Engine takes
TPU-only branches that a CPU run never traces.  These tests lower the
real programs at BASELINE config #2 shapes for a described chip, with
those branches steered on by attribute:

- the fused chunk step (chunk 2048) with the MXU delta lowering and
  the per-family chunk skip;
- the claim-insert dedup at 2^25 visited slots;
- the growth steps a run with default capacities takes: the visited-
  table rehash to 2^25 slots and the level buffer's 2^21 -> 2^23 rows;
- the pjit engine's carry construction and root placement on a
  4-device described mesh.

The topology is described only inside a fixture (one process may load
libtpu; see the on-chip-measurement guide), and the persistent
compilation cache is off around these compiles: an executable for a
described chip can be written to it but never read back here.
"""

import os

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding

from conftest import jax_cache_off
from raft_tla_tpu.cfg.parser import load_model
from raft_tla_tpu.config import Bounds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, LCAP, VCAP, OCAP = 2048, 3 << 21, 1 << 25, 1 << 14


def _config2():
    return load_model(os.path.join(REPO, "configs", "config2", "raft.cfg"),
                      bounds=Bounds.make(max_log_length=3, max_timeouts=2,
                                         max_client_requests=3))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    t = topologies.get_topology_desc(platform="tpu",
                                     topology_name="v5e:2x2")
    with jax_cache_off():
        yield t


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine():
    from raft_tla_tpu.engine.bfs import Engine
    eng = Engine(_config2(), chunk=CHUNK, store_states=False, lcap=LCAP,
                 vcap=VCAP, ocap=OCAP)
    # the TPU branches Expander picks from jax.default_backend()
    eng.expander._delta_mxu = True
    eng.expander.delta_chunk_skip = True
    return eng


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=sharding), tree)


def test_chunk_step_compiles_for_v5e(engine, one_chip):
    assert engine.expander.delta_active
    carry = _on(one_chip, jax.eval_shape(
        lambda: engine._fresh_carry(engine.LCAP, engine.VCAP)))
    compiled = jax.jit(engine._chunk_step_impl, static_argnums=1,
                       donate_argnums=0).lower(
        carry, engine.FAM_CAPS).compile()
    mem = compiled.memory_analysis()
    # the donated carry is aliased, not copied, and the step fits HBM
    assert mem.alias_size_in_bytes > 4 << 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_claim_insert_compiles_for_v5e_at_2_25_slots(engine, one_chip):
    W, M = engine.W, engine.FCAP
    u32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.uint32,  # noqa: E731
                                         sharding=one_chip)
    compiled = jax.jit(engine._probe_insert, donate_argnums=(0, 1)).lower(
        tuple(u32(VCAP) for _ in range(W)), u32(VCAP),
        tuple(u32(M) for _ in range(W)),
        jax.ShapeDtypeStruct((M,), jnp.bool_, sharding=one_chip),
        u32(M)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes == \
        (W + 1) * VCAP * 4


def test_table_rehash_compiles_for_v5e(engine, one_chip):
    # a default check grows the visited table 4x at a time; this is
    # its last step to config #2's 2^25 slots
    old = tuple(jax.ShapeDtypeStruct((VCAP >> 2,), jnp.uint32,
                                     sharding=one_chip)
                for _ in range(engine.W))
    compiled = engine._rehash_fn(VCAP >> 2, VCAP).lower(old).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        (engine.W + 1) * VCAP * 4


def test_carry_growth_compiles_for_v5e(engine, one_chip):
    # the level buffer's 4x growth step a default config #2 run takes
    # last (2^21 -> 2^23 rows), the table already at 2^25 slots
    small = _on(one_chip, jax.eval_shape(
        lambda: engine._fresh_carry(1 << 21, VCAP)))
    compiled = jax.jit(engine._grow, static_argnums=(1, 2)).lower(
        small, 1 << 23, VCAP).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + \
        mem.temp_size_in_bytes < 16e9


def test_setup_program_compiles_for_v5e_in_one_carry(engine, one_chip):
    # a fresh start's one set-up program at config #2 capacities: the
    # buffer fills and the root writes share the output buffers
    roots, rk, _ = engine._dedup_roots(None)
    n = len(rk)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: engine._setup_impl(engine.LCAP, engine.VCAP,
                                      engine.FCAP, engine.OCAP, *a)).lower(
        {k: arg(v.shape, v.dtype) for k, v in roots.items()},
        arg((n,), jnp.int32), arg((n, engine.W), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 4 << 30
    assert mem.temp_size_in_bytes * 100 < mem.output_size_in_bytes


def test_pjit_carry_placement_compiles_on_4_chip_mesh(topo):
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    eng = PjitShardedEngine(_config2(), devices=topo.devices[:4],
                            chunk=CHUNK, store_states=False, lcap=LCAP,
                            vcap=VCAP, ocap=OCAP)
    # the carry is born under its named shardings, split four ways
    def fresh():
        return Engine._fresh_carry(eng, eng.LCAP, eng.VCAP)

    born = jax.jit(fresh, out_shardings=eng._carry_sh).lower().compile()
    per_dev = born.memory_analysis().output_size_in_bytes
    assert per_dev < 2e9
    # root placement (.at[].set of a few rows into row- and
    # slot-sharded buffers) traces and partitions under the mesh
    n, rep = 3, eng._rep_sh
    carry = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(fresh), eng._carry_sh)
    roots_n = {k: jax.ShapeDtypeStruct(v.shape[:-1] + (n,), v.dtype,
                                       sharding=rep)
               for k, v in carry["lvl"].items()}
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep)
    rk = jax.ShapeDtypeStruct((n, eng.W), jnp.uint32, sharding=rep)
    inv = jax.ShapeDtypeStruct((n, len(eng.inv_names)), jnp.bool_,
                               sharding=rep)
    con = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rep)
    placed = jax.jit(eng._place_roots, out_shardings=eng._carry_sh).lower(
        carry, roots_n, i32, rk, inv, con).compile()
    vis_sh = placed.output_shardings["vis"][0]
    assert isinstance(vis_sh, NamedSharding) and eng.D == 4
    assert vis_sh.spec == eng._table_sh.spec


def test_harvest_pack_compiles_for_v5e_in_bounded_memory(engine, one_chip):
    # a stored-states harvest of config #2's widest level reads blocks
    # of at most _PACK_BYTES, and a full burst ring packs whole: each
    # read's output, the chip's temporaries and the program's code stay
    # a small transient beside the multi-GB carry, whatever the level
    from raft_tla_tpu.engine import pack
    carry = _on(one_chip, jax.eval_shape(
        lambda: engine._fresh_carry(engine.LCAP, VCAP)))
    level = [carry["lpar"], carry["llane"], *carry["front"].values(),
             carry["linv"]]
    block = engine._pack_block(level)
    assert engine.chunk <= block < engine.LCAP - engine.OCAP
    KB, L = engine._burst_width(), engine.burst_levels
    ring = lambda lead, dt: jax.ShapeDtypeStruct(  # noqa: E731
        lead + (L, KB), dt, sharding=one_chip)
    for leaves, rows, levels, most in (
            (level, block, None, engine._PACK_BYTES),
            ([ring((), jnp.int32), ring((), jnp.int32),
              *(ring(v.shape[:-1], v.dtype)
                for v in carry["front"].values()),
              ring((len(engine.inv_names),), jnp.bool_)], KB, L, 64 << 20)):
        compiled = pack.pack.lower(leaves, np.int32(0), rows=rows,
                                   levels=levels).compile()
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes == sum(
            int(np.prod(sh)) * dt.itemsize
            for _, dt, sh in pack.layout(leaves, rows, levels))
        assert mem.output_size_in_bytes <= most
        assert mem.temp_size_in_bytes <= mem.output_size_in_bytes
        assert mem.generated_code_size_in_bytes < 4 << 20
