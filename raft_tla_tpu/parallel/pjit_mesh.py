"""Pod-scale pjit frontier: the WHOLE BFS state under named shardings
(ROADMAP item 2 — the last big perf ceiling).

The classic and spill engines are single-chip; this is the one engine
that spans devices and hosts.  It puts the full logical BFS state —
frontier rows, visited-table partitions, gid cursors, level buffers,
per-level archive staging — under ``NamedSharding``s on a
``jax.make_mesh`` spanning ALL hosts' devices, and lets the compiler
partition the UNCHANGED single-logical-program engine:

- the carry pytree's shardings come from **rule-matched PartitionSpec
  trees** (``match_partition_rules`` — SNIPPETS.md's pjit
  shard/gather exemplar): visited-table words shard on the SLOT axis,
  frontier/level rows on the batch-last axis, scalars replicate;
- ``make_shard_and_gather_fns`` builds the boundary movers: shard fns
  re-partition host/checkpoint arrays onto the mesh, gather fns pull
  replicated host copies for the harvest/archive/checkpoint paths
  (every controller receives the full row set, so archives and
  violation decodes are controller-replicated — the
  store_states × checkpoint combination works here from day one);
- the **hash-ownership exchange is a sharding-constraint-mediated
  collective inside ONE jit program**: a candidate's claim-scatter
  into the slot-sharded table (engine/bfs._probe_insert) IS the
  routing step — ``with_sharding_constraint`` pins the table's named
  sharding and GSPMD emits the cross-device (ICI within a host, DCN
  across hosts) collectives, no hand-written ``all_to_all``;
- every host-read output (the packed scal vector, burst stats and
  ring archives) is declared REPLICATED in ``out_shardings``, so the
  per-level sync is one small all-gather and ``np.asarray`` works on
  every controller.

Because the engine's program is the classic Engine's — same chunk
order, same probe/claim discipline, same finalize — counts, level
sizes, global ids, archives and witness traces are bit-identical to
the single-device engine and therefore to the oracle
(tests/test_pjit.py pins it in-process on a 1-device mesh and under 2
controller processes × 2 virtual CPU devices with gloo collectives —
the DCN stand-in).

Resume rides the round-12 portable-image contract both ways: a
classic or spill checkpoint loads through ``resume_image=`` (the key
SET re-inserts into the slot-sharded table — membership is a set
property — and the gid-ordered frontier rows re-partition onto the
batch axis), and this engine's checkpoints are written in the CLASSIC
engine format (gathered to host, proc-0 publish), so they resume on
the classic engine directly and on the spill engine through the same
portable loader.

The ceiling this moves (BASELINE.md round 14): the visited table and
frontier scale with AGGREGATE pod HBM (+ host RAM via the spill
engines for the archive side), not one chip — the "run configs #1-#2
to exhaustion" substrate.

Multi-controller bring-up (parallel/multihost): call
``init_distributed`` (or ``jax.distributed.initialize``) on every
host BEFORE constructing the engine, then build with
``devices=jax.devices()`` (the default) so the mesh spans the pod.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import ModelConfig
from ..engine.bfs import Engine


# warn-once latch for uneven user chunk overrides (per process, like
# any stacklevel warning filter — the mesh size doesn't change mid-run)
_warned_uneven_chunk = False


def _round_chunk_to_devices(chunk: int, n_devices: int) -> int:
    """Round ``chunk`` up to the next multiple of the mesh size.

    The frontier's batch axis shards chunk/D rows per device, so the
    per-device row count must divide evenly.  Defaults (512, 2048)
    already divide every power-of-two pod slice; a user override that
    doesn't is rounded up (never down — capacities are sized FROM the
    chunk) with a one-time warning naming both numbers."""
    d = max(1, int(n_devices))
    rem = int(chunk) % d
    if rem == 0:
        return int(chunk)
    rounded = int(chunk) + (d - rem)
    global _warned_uneven_chunk
    if not _warned_uneven_chunk:
        _warned_uneven_chunk = True
        import warnings
        warnings.warn(
            f"chunk {chunk} is not a multiple of the {d}-device mesh; "
            f"rounded up to {rounded} ({rounded // d} frontier rows "
            "per device)", stacklevel=3)
    return rounded


# ---------------------------------------------------------------------------
# rule-matched PartitionSpec trees + shard/gather fns (the SNIPPETS.md
# pjit exemplar, adapted: rules are regexes over the carry's "|"-joined
# key paths; a rule names an AXIS KIND rather than a literal spec so
# one rule covers leaves of different ranks)
# ---------------------------------------------------------------------------

# kind -> how the leaf shards over the 1-D "d" mesh axis:
#   "slots" — dim 0 (the visited-table slot axis / 1-D row arrays)
#   "rows"  — the LAST axis (batch-last frontier/level state arrays)
#   "rep"   — replicated (scalars, shape anchors, counters)
# and over the 2-D ("jobs", "state") serving mesh (serve/batch round
# 17 — the batched wave carry leads every leaf with the [J] job axis):
#   "jobs"       — P("jobs") on dim 0 only (cursors, per-job rows)
#   "jobs_slots" — [J, VCAP, ...]: the table slot axis (dim 1) shards
#                  the "state" mesh axis — the dedup probe/claim
#                  scatter becomes a state-axis in-program collective
#   "jobs_rows"  — [J, ..., KB]: batch-last ring/level/archive rows
#                  shard the "state" mesh axis on the LAST dim
CARRY_RULES = [
    (r"^vis\|", "slots"),
    (r"^claims$", "slots"),
    (r"^(front|lvl)\|", "rows"),
    (r"^linv$", "rows"),
    (r"^(lpar|llane|jslot|lcon|fmask)$", "slots"),
    (r".*", "rep"),
]


def _leaf_path_name(key_path) -> str:
    return "|".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in key_path)


def _spec_for(kind: str, ndim: int) -> P:
    if kind == "rep" or ndim == 0:
        return P()
    if kind == "slots":
        return P(*(("d",) + (None,) * (ndim - 1)))
    if kind == "jobs" or (kind.startswith("jobs_") and ndim == 1):
        return P(*(("jobs",) + (None,) * (ndim - 1)))
    if kind == "jobs_slots":
        return P(*(("jobs", "state") + (None,) * (ndim - 2)))
    if kind == "jobs_rows":
        return P(*(("jobs",) + (None,) * (ndim - 2) + ("state",)))
    assert kind == "rows", kind
    return P(*((None,) * (ndim - 1) + ("d",)))


def match_partition_rules(rules, tree):
    """Pytree of (ShapeDtypeStruct or array) -> pytree of PartitionSpec
    by first-regex-match over the "|"-joined key path (the exemplar's
    ``match_partition_rules``, axis-kind flavored).  Every leaf must
    match some rule — the catch-all replicates."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for kp, leaf in flat:
        name = _leaf_path_name(kp)
        for rx, kind in rules:
            if re.search(rx, name):
                specs.append(_spec_for(kind, np.ndim(leaf)
                                       if not hasattr(leaf, "ndim")
                                       else leaf.ndim))
                break
        else:                                   # pragma: no cover
            raise ValueError(f"no partition rule matched {name!r}")
    return jax.tree_util.tree_unflatten(treedef, specs)


def make_shard_and_gather_fns(shardings, mesh):
    """(shard_fns, gather_fns) pytrees for a sharding pytree — the
    exemplar's boundary movers.  A shard fn re-partitions a host (or
    differently-sharded) array onto its named sharding via a jitted
    identity with ``out_shardings``; a gather fn pulls a REPLICATED
    host copy (every controller's ``np.asarray`` then reads its local
    replica — multi-controller safe)."""
    rep = jax.jit(lambda x: x,
                  out_shardings=NamedSharding(mesh, P()))

    def make_shard_fn(sh):
        return jax.jit(lambda x: x, out_shardings=sh)

    def gather_fn(x):
        return np.asarray(rep(x))

    return (jax.tree_util.tree_map(make_shard_fn, shardings),
            jax.tree_util.tree_map(lambda _sh: gather_fn, shardings))


class PjitShardedEngine(Engine):
    """The classic Engine with its whole state pjit-sharded over a
    (possibly multi-host) device mesh.

    devices — the mesh's devices; defaults to ``jax.devices()``, which
    under a multi-controller run (``multihost.init_distributed``)
    spans every process's devices.  chunk is rounded up to a multiple
    of the device count (_round_chunk_to_devices — an uneven
    override warns once; uneven shardings would compile but waste
    tiles on every step).

    Program identity: the compiled step/finalize/burst are the classic
    engine's traces — partitioning changes WHERE integer ops run,
    never their results — so every count, gid and trace is
    bit-identical to the single-device engine (and the oracle)."""

    def __init__(self, cfg: ModelConfig, devices=None, **kw):
        devices = list(devices) if devices is not None else jax.devices()
        # Auto axes: GSPMD propagates the carry shardings through the
        # set-up program's root writes (Explicit axes would reshard
        # each operand and refuse the replicated few-row updates)
        self.mesh = jax.make_mesh((len(devices),), ("d",),
                                  axis_types=(jax.sharding.AxisType.Auto,),
                                  devices=devices)
        self.D = len(devices)
        kw = dict(kw, chunk=_round_chunk_to_devices(
            kw.get("chunk", 512), self.D))
        super().__init__(cfg, **kw)
        self._rep_sh = NamedSharding(self.mesh, P())
        self._table_sh = NamedSharding(self.mesh, P("d"))
        # rule-matched spec tree over the carry template (structure
        # only; shardings are shape-free, so one tree serves every
        # capacity growth).  Setting _carry_sh makes the base engine's
        # jitted carry builders (_fresh_carry, _setup_carry) bear every
        # buffer under its named sharding: no host-side materialization
        # of the multi-GB state, the pod-scale point
        template = jax.eval_shape(
            lambda: self._fresh_carry_impl(self.LCAP, self.VCAP,
                                           self.FCAP, self.OCAP))
        self._carry_specs = match_partition_rules(CARRY_RULES, template)
        self._carry_sh = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec),
            self._carry_specs,
            is_leaf=lambda x: isinstance(x, P))
        self._shard_fns, self._gather_fns = make_shard_and_gather_fns(
            self._carry_sh, self.mesh)
        self._state_keys = list(template["front"].keys())
        rep = self._rep_sh
        n_rep = {k: rep for k in self._state_keys}
        # re-jit the drivers' entry points with explicit out_shardings:
        # the carry stays under its named shardings call after call;
        # everything the host reads comes back replicated
        self._step_jit = jax.jit(self._chunk_step_impl,
                                 donate_argnums=0, static_argnums=1,
                                 out_shardings=self._carry_sh)
        self._fin_jit = jax.jit(
            self._finalize_impl, donate_argnums=0,
            out_shardings=(self._carry_sh,
                           dict(inv_ok=rep, scal=rep)))
        self._burst_jit = jax.jit(
            self._burst_impl, donate_argnums=0, static_argnums=1,
            out_shardings=(self._carry_sh,
                           dict(stats=rep, par=rep, lane=rep,
                                st=n_rep, inv=rep)))
        self._shard_carry = jax.jit(lambda c: c,
                                    out_shardings=self._carry_sh)
        self._gather_rep = jax.jit(lambda x: x, out_shardings=rep)
        self._seed_table_cache = {}

    def _fetch(self, x) -> np.ndarray:
        """Harvest-path reads gather to a replicated array first, so
        ``np.asarray`` sees an addressable replica on EVERY controller
        (the base engines' process-local asarray would fail on
        non-addressable shards)."""
        return np.asarray(self._gather_rep(x))

    def _probe_insert(self, table, claims, keys, live, ranks,
                      counts=False):
        """The dedup claim walk with the table pinned to its slot
        sharding: the winners' key scatter is the hash-ownership
        exchange, mediated by this constraint as an in-program GSPMD
        collective (module docstring) — no all_to_all, no host
        hop."""
        table = jax.lax.with_sharding_constraint(
            table, tuple(self._table_sh for _ in table))
        claims = jax.lax.with_sharding_constraint(claims,
                                                  self._table_sh)
        return super()._probe_insert(table, claims, keys, live, ranks,
                                     counts=counts)

    # -- checkpoint / resume ------------------------------------------
    #
    # Checkpoints are written in the CLASSIC engine format: the carry
    # gathers to host (one replicated copy per controller) and process
    # 0 publishes.  That makes the file portable BOTH ways — the
    # classic engine resumes it directly and the spill engine through
    # the round-12 portable loader, and this engine resumes theirs via
    # resume_image (engine/bfs Engine._resume_portable) with the carry
    # re-partitioned onto the mesh by _commit_carry below.
    # ------------------------------------------------------------------

    def _gather_carry_host(self, carry):
        flat, treedef = jax.tree_util.tree_flatten(carry)
        gf = jax.tree_util.tree_leaves(self._gather_fns)
        return jax.tree_util.tree_unflatten(
            treedef, [g(x) for g, x in zip(gf, flat)])

    def _save_checkpoint(self, path, carry, res, depth, n_states,
                         n_vis, n_front):
        host = self._gather_carry_host(carry)
        if jax.process_index() == 0:
            Engine._save_checkpoint(self, path, host, res, depth,
                                    n_states, n_vis, n_front)

    def _load_checkpoint(self, path):
        carry, res, meta = Engine._load_checkpoint(self, path)
        return self._commit_carry(carry), res, meta

    def _commit_carry(self, carry):
        """Host/local carry -> the mesh's named shardings (the shard
        half of the exemplar, whole-tree)."""
        return self._shard_carry(carry)
