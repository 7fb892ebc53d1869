"""Multi-device BFS: frontier data parallelism + fingerprint-ownership
partitioning (SURVEY §2.14).

The reference's engine-level parallelism is TLC's multi-worker BFS with
a partitioned fingerprint table (`-workers 8`).  The TPU-native
counterpart implemented here:

- the frontier, level buffer, parent arrays and the visited/level key
  sets all carry a leading device axis and live sharded over a 1-D
  ``jax.sharding.Mesh`` (``shard_map`` over axis "d");
- each device expands its frontier shard and fingerprints its enabled
  candidates (compute data parallelism);
- every candidate is then routed to its OWNER device — owner = low
  bits of the fingerprint — via ``jax.lax.all_to_all`` over ICI; the
  owner claim-inserts into its shard of the open-addressing visited
  table (engine/bfs._probe_insert: membership + first-seen dedup +
  insert in one probe walk), and appends fresh states to its level
  shard.  The dedup authority therefore lives on device and is
  partitioned by hash, exactly like TLC's worker-local fingerprint
  table partitions, with the all-to-all exchange riding ICI instead
  of shared memory;
- because ownership is hash-uniform, the next frontier (the level
  buffer, swapped in place) is automatically load-balanced.

Global state ids are assigned device-major per level: device d's rows
get ids ``g_base + prefix[d] + row`` where ``prefix`` is the exclusive
cumsum of the per-device level counts (computed on device with an
``all_gather``).  The host reads ONE packed per-level scalar matrix.

Determinism (cf. TLC's multi-worker mode, improved — VERDICT r3 #6):
the surviving representative among equal-VIEW-fingerprint candidates
(whose non-VIEW history counters feed constraint pruning and scenario
predicates downstream) is CONTENT-CANONICAL — the lexicographic
minimum of the packed non-VIEW lanes over the whole level's candidate
multiset, implemented as a per-window min-content reduction plus
replace-if-smaller on same-level duplicate hits (`lrow` slot map).
Because the min is over the level's candidate multiset — which is
itself determined by the previous level's rows — the reachable set and
all counts are, by induction, a pure function of the model, identical
for EVERY mesh size, chunk size and all_to_all window packing
(tests/test_sharded.py::test_sharded_reference_cfg_full_constraints
pins D=4 ≡ D=8 at depth 16 under the full counter-dependent
constraint set).  TLC's multi-worker mode is run-to-run
nondeterministic here; our single-device engines keep TLC's
SEQUENTIAL first-seen policy (= the oracle).  The two policies may in
principle pick different representatives — measured on the reference
cfg micro-bounds at depth 16, content-min agrees with the oracle
exactly (82,771 distinct; the arrival-rank scheme it replaced
measured 82,751) — and each is deterministic and explores a sound
constraint semantics.  Witness provenance is mesh-invariant too
(VERDICT r4 #9): among equal-content candidates the canonical min
extends to (parent fingerprint, lane) — the parent's FINGERPRINT, not
its global id, because gids are assigned device-major and therefore
differ across mesh shapes while the fingerprint is a pure function of
the parent's content.  A violation trace reproduced on D=4 is
action-by-action identical to the D=8 trace
(tests/test_sharded.py::test_sharded_trace_mesh_invariant).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ModelConfig
from ..obs import NULL_OBS
from ..engine import driver
from ..engine.bfs import (CheckResult, Engine, U32MAX, Violation, _cat,
                          _take, ckpt_archives, ckpt_carry, ckpt_read,
                          ckpt_result, ckpt_write)
from ..engine.host_table import insert_np
from ..ops.codec import C_OVERFLOW
from ..resil.chaos import chaos_point

# sharded checkpoint format gate (shared with MultiHostEngine):
# format 2 added the content-canonical lrow table (round 4); format 3
# added the mesh-invariant provenance lpfp table (round 5); format 4
# replaced the pg_off arithmetic with the gids table and added
# trip_base (round 5, the spill-composed engine).  Older checkpoints
# fail here with a version message instead of a missing-leaf error
# deep in ckpt_carry.
_SHARDED_CKPT_FORMAT = 4
_SHARDED_FMT = ("ckpt_format", _SHARDED_CKPT_FORMAT,
                "the carry replaced pg_off with the gids table and "
                "gained trip_base")

def _shard_map(f, mesh, in_specs, out_specs):
    return shard_map(f, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# warn-once latch for uneven user chunk overrides (per process, like
# any stacklevel warning filter — the mesh size doesn't change mid-run)
_warned_uneven_chunk = False


def _round_chunk_to_devices(chunk: int, n_devices: int) -> int:
    """Round ``chunk`` up to the next multiple of the mesh size.

    The mesh engines shard the frontier chunk/D rows per device, so
    the per-device row count must divide evenly.  Defaults (512, 2048)
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


class ShardedEngine(Engine):
    """Engine whose full BFS runs sharded over a device mesh with
    hash-ownership-partitioned visited/level key sets.

    chunk — GLOBAL frontier states expanded per step (chunk/D per
    device); rounded up to a multiple of the mesh size
    (_round_chunk_to_devices — uneven overrides warn once)."""

    # the mesh burst's stats rows carry no dedup counts
    _BS_N = 8

    def __init__(self, cfg: ModelConfig, devices=None, chunk: int = 512,
                 store_states: bool = True,
                 lcap: int = 1 << 14, vcap: int = 1 << 17,
                 fcap: Optional[int] = None, scap: Optional[int] = None,
                 burst: bool = True,
                 burst_levels: Optional[int] = None,
                 guard_matmul: bool = True,
                 delta_matmul: bool = True,
                 fam_density=None,
                 sym_canon: str = "auto"):
        devices = devices if devices is not None else jax.devices()
        self.mesh = Mesh(np.array(devices), axis_names=("d",))
        self.D = len(devices)
        # pod-size-aware chunk: the frontier shards chunk/D rows per
        # device, so chunk rounds UP to the next multiple of the mesh
        # size instead of asserting — the default chunk then does the
        # right thing on any pod slice; an uneven user override warns
        # once (it was a deliberate number that no longer holds)
        chunk = _round_chunk_to_devices(chunk, self.D)
        self.BL = chunk // self.D              # frontier rows per device
        super().__init__(cfg, chunk=chunk, store_states=store_states,
                         lcap=lcap, vcap=vcap, fcap=fcap, burst=burst,
                         burst_levels=burst_levels,
                         guard_matmul=guard_matmul,
                         delta_matmul=delta_matmul,
                         fam_density=fam_density,
                         sym_canon=sym_canon)
        # the sharded step computes full per-candidate fingerprints: the
        # incremental per-action path (engine/fingerprint) is not wired
        # into _local_step yet, so make the inherited flag's inertness
        # explicit rather than silently carrying it as True
        self.incremental_fp = False
        # per-device capacities.  VB (table shard slots) power of two
        # for mask indexing.
        self.FC = max(256, (self.FCAP + self.D - 1) // self.D)
        self.VB = 1 << max(12, int(np.ceil(np.log2(
            max(vcap // self.D, 2)))))
        # send capacity per (src, dst) pair; hash-uniform routing puts
        # ~FC/D candidates per destination — 4x headroom, growable
        self.SC = int(scap) if scap else max(256, 4 * self.FC // self.D)
        # the level shard must hold the D*SC receive window on top of
        # its usable capacity
        self.LB = self._round_lb(max(lcap // self.D, 4 * self.FC,
                                     2 * self.D * self.SC))
        # per-family materialization caps are per-DEVICE (chunk/D rows)
        self.FAM_CAPS = tuple(self.expander.default_fam_caps(
            self.BL, self.fam_density))
        # step-atomic trip discipline: off here (whole-level journal
        # replay); the spill-composed subclass turns it on
        self._step_atomic = False
        # in-burst frontier policy: this engine keeps constraint-pruned
        # rows in place under fmask (prune-not-expand, engine/bfs);
        # the spill-composed subclass compacts them away at each burst
        # level commit, because its HOST path drops pruned rows before
        # re-upload — the window packing (and so the level shards' row
        # order and gid assignment) must match the un-bursted path
        # exactly
        self._burst_compact_frontier = False
        # appended rows' fingerprints ride the level shard (lkey) only
        # when the spill-composed subclass runs its host-partitioned
        # table: they feed the per-device partition sweep + cache
        # reseed (parallel/spill_mesh; engine/host_table)
        self._track_keys = False
        self._level_jit = jax.jit(self._sharded_level_call,
                                  donate_argnums=0, static_argnums=1)
        # fused K-level driver (_shard_burst): the level program's body
        # inside one more while_loop, one stats matrix back per burst
        self._burst_mesh_jit = jax.jit(self._sharded_burst_call,
                                       donate_argnums=0,
                                       static_argnums=1)

    def _round_lb(self, n: int) -> int:
        b = self.BL
        return ((int(n) + b - 1) // b) * b

    # -----------------------------------------------------------------
    def _sharded_level_call(self, carry, fam_caps):
        specs = jax.tree_util.tree_map(lambda _: P("d"), carry)
        # scal is all-gathered on device and comes back REPLICATED so
        # every controller process can read the whole [D, 10+n_fams]
        # matrix without touching non-addressable shards (multi-host
        # safe)
        out_specs = (specs, dict(inv_ok=P("d"), scal=P(None)))
        return _shard_map(
            lambda c: self._shard_level(c, fam_caps), self.mesh,
            (specs,), out_specs)(carry)

    def _shard_level(self, carry, fam_caps):
        """Whole BFS level in one device call: while any device still
        has frontier rows and no device overflowed, run lock-step chunk
        steps (the all_to_all inside needs every device participating —
        drained shards keep stepping with all-invalid rows), then
        finalize.  The seed level (n_front=0 everywhere) skips straight
        to the finalize, so this is the ONLY shard_map program the
        engine compiles."""
        c = jax.tree_util.tree_map(lambda x: x[0], carry)

        def cond(c):
            more = c["base"] < c["n_front"]
            bad = c["ovf"] | c["fovf"] | c["sovf"] | c["hovf"]
            flags = jax.lax.all_gather(jnp.stack([more, bad]), "d")
            return flags[:, 0].any() & ~flags[:, 1].any()

        c = lax.while_loop(cond, lambda cc: self._local_step(cc, fam_caps),
                           c)
        new_c, out = self._local_finalize(c)
        return (jax.tree_util.tree_map(lambda x: x[None], new_c),
                dict(inv_ok=out["inv_ok"][None], scal=out["scal"]))

    # -----------------------------------------------------------------
    # per-device chunk step (runs inside _shard_level's while_loop; all
    # leaves are the local shard, device axis stripped)
    # -----------------------------------------------------------------

    def _local_step(self, c, fam_caps):
        B, A, W, D = self.BL, self.A, self.W, self.D
        # capacities derive from carry shapes so growth always retraces
        # (fam_caps rides as a static jit arg instead)
        FC = c["cidx"].shape[0]
        SC = c["sscr"].shape[0]
        LB = c["fmask"].shape[0]
        N = B * A
        M = D * SC                     # received candidates per step
        base = c["base"]
        # frontier shards are stored narrow; widen the chunk for kernels
        sv = self.ir.widen({k: lax.dynamic_slice_in_dim(v, base, B)
                    for k, v in c["front"].items()})
        fmask = lax.dynamic_slice_in_dim(c["fmask"], base, B)
        # guard-first expansion (engine/bfs chunk-step twin).  The
        # expander APIs are batch-LAST; this engine keeps its shard
        # buffers batch-major and transposes at the boundary (the
        # virtual-CPU test mesh doesn't care about TPU tiling).
        svT = {k: jnp.moveaxis(v, 0, -1) for k, v in sv.items()}
        derT = self.expander.derived_batch_T(svT)
        ok = lax.optimization_barrier(self.expander.guards_T(svT, derT))
        valid = ((base + jnp.arange(B, dtype=jnp.int32)) <
                 c["n_front"]) & fmask
        okf = (ok & valid[:, None]).reshape(N)

        # compact enabled lanes, materialize, fingerprint them
        idx = jnp.arange(N, dtype=jnp.int32)
        epos = jnp.where(okf, jnp.cumsum(okf.astype(jnp.int32)) - 1, FC)
        n_e = okf.sum(dtype=jnp.int32)
        eidx = lax.optimization_barrier(
            jnp.full((FC,), N, jnp.int32).at[epos].set(idx, mode="drop"))
        cand_T, famx = self.expander.materialize(
            svT, derT, okf, epos, FC, fam_caps)
        cand_c = lax.optimization_barrier(
            {k: jnp.moveaxis(v, -1, 0) for k, v in cand_T.items()})
        famx = jnp.maximum(c["famx"], famx)
        fovf = c["fovf"] | (n_e > FC) | \
            jnp.any(famx > jnp.asarray(fam_caps, jnp.int32))
        elive = jnp.arange(FC, dtype=jnp.int32) < n_e
        take = jnp.clip(eidx, 0, N - 1)
        if self.act_names:
            par_c = {k: v[take // A] for k, v in sv.items()}
            act = jax.vmap(self._act_ok)(par_c, cand_c)
            elive = elive & act
        gen_inc = elive.sum(dtype=jnp.int32)
        fp = lax.optimization_barrier(
            self.fpr.fingerprint_batch(cand_c))            # [FC, W]
        # parent global ids come from the per-row gids table (the
        # commit finalize refreshes it; the spill-composed engine
        # uploads host-compacted frontiers where arithmetic ids are
        # impossible)
        pgid = c["gids"][base + take // A]
        lane = take % A
        # parent fingerprints, for mesh-invariant provenance (module
        # docstring): the canonical tiebreak among equal-content
        # candidates must not use pgid — global ids are device-major
        # and mesh-shape dependent — so the parent's content hash rides
        # along instead (B extra hashes per step vs FC candidate ones)
        pfp_par = self.fpr.fingerprint_batch(sv)           # [B, W]
        pfp = pfp_par[take // A]                           # [FC, W]

        # ---- route to owner device (hash-ownership, SURVEY §2.14) ----
        owner = jnp.where(elive, (fp[:, W - 1] % D).astype(jnp.int32), D)
        slot = jnp.arange(FC, dtype=jnp.int32)
        o_s, slot_s = lax.optimization_barrier(
            lax.sort((owner, slot), num_keys=2))
        counts = jnp.sum(o_s[None, :] == jnp.arange(D)[:, None],
                         axis=1)                            # [D]
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(FC, dtype=jnp.int32) - \
            starts[jnp.clip(o_s, 0, D - 1)]
        live_s = o_s < D
        sovf = c["sovf"] | jnp.any(live_s & (rank >= SC))
        dest = jnp.where(live_s & (rank < SC),
                         o_s * SC + jnp.clip(rank, 0, SC - 1), M)
        # inverse map: send slot -> local candidate slot
        sidx = lax.optimization_barrier(
            jnp.full((M,), FC, jnp.int32).at[dest].set(
                slot_s, mode="drop"))
        sfill = jnp.zeros((M,), bool).at[dest].set(live_s, mode="drop")
        stake = jnp.clip(sidx, 0, FC - 1)
        send_key = tuple(jnp.where(sfill, fp[stake, w], U32MAX)
                         for w in range(W))
        # rows ride the ICI all_to_all in storage dtypes (2-3x fewer
        # interconnect bytes than the kernels' int32 rows)
        send_row = self.ir.narrow(self.lay, {k: v[stake]
                                     for k, v in cand_c.items()})
        send_pgid = jnp.where(sfill, pgid[stake], -1)
        send_lane = jnp.where(sfill, lane[stake], -1)
        send_pfp = jnp.where(sfill[:, None], pfp[stake], U32MAX)
        (send_key, send_row, send_pgid, send_lane, send_pfp) = \
            lax.optimization_barrier(
                (send_key, send_row, send_pgid, send_lane, send_pfp))

        a2a = partial(lax.all_to_all, axis_name="d", split_axis=0,
                      concat_axis=0, tiled=True)
        recv_key = tuple(a2a(kw) for kw in send_key)        # [M] each
        recv_row = {k: a2a(v) for k, v in send_row.items()}
        recv_pgid = a2a(send_pgid)
        recv_lane = a2a(send_lane)
        recv_pfp = a2a(send_pfp)                            # [M, W]

        # ---- owner-side dedup: claim-insert into the table shard ----
        VB = c["vis"][0].shape[0]
        recv_live = jnp.zeros(M, bool)
        for w in range(W):
            recv_live = recv_live | (recv_key[w] != U32MAX)
        # include the CURRENT step's fovf/sovf (not just prior-step
        # flags): a step that overflowed its compaction or send buffer
        # is doomed to replay, so its claim-inserts are wasted writes
        if self._step_atomic:
            # spill-composed mode (parallel/spill_mesh): a tripping
            # step must commit on NO device — the host resumes from
            # the tripped step after spilling/growing, and there is no
            # whole-level journal rollback once shard contents have
            # spilled to host.  One tiny all_gather makes the
            # pre-insert trip decision global.
            pre_bad = jax.lax.all_gather(fovf | sovf, "d").any()
        else:
            pre_bad = fovf | sovf
        gate = ~(c["ovf"] | pre_bad | c["hovf"])

        # ---- content-canonical survivor, stage 1 (VERDICT r3 #6) ----
        # The admitted representative among equal-fingerprint candidates
        # is the one with the lexicographically SMALLEST non-VIEW
        # content (history counters + feature lanes), not the first
        # arrival: stage 1 reduces each receive window to one
        # min-content candidate per key (sort by key, then content);
        # stage 2 after the append replaces a row admitted by an
        # earlier window of the SAME level when a smaller-content
        # duplicate arrives.  Together they make the survivor the
        # content-min over the whole level's candidate multiset — see
        # the module docstring's determinism contract.
        def content_words(rows_nv):
            ws = []
            for k in self.ir.nonview_keys:
                v = rows_nv[k].astype(jnp.int32).reshape(M, -1)
                for ci in range(v.shape[1]):
                    ws.append(v[:, ci].astype(jnp.uint32)
                              ^ jnp.uint32(0x80000000))
            return ws

        cwords = content_words(recv_row)
        # provenance words extend the canonical key (module docstring):
        # among equal (key, content) candidates the rep is the one with
        # the smallest (parent fingerprint, lane) — mesh-invariant,
        # unlike arrival order.  -1 lanes cast to 0xFFFFFFFF and sort
        # last, so invalid rows never win a run.
        pwords = [recv_pfp[:, w] for w in range(W)] + \
            [recv_lane.astype(jnp.uint32)]
        ops = list(recv_key) + cwords + pwords + \
            [jnp.arange(M, dtype=jnp.uint32)]
        srt = lax.sort(tuple(ops), num_keys=len(ops))
        s_idx = srt[-1].astype(jnp.int32)
        same_prev = jnp.ones((M - 1,), bool)
        for w in range(W):
            same_prev = same_prev & (srt[w][1:] == srt[w][:-1])
        first_run = jnp.concatenate([jnp.ones((1,), bool), ~same_prev])
        rep = jnp.zeros((M,), bool).at[s_idx].set(first_run)
        live_rep = recv_live & rep & gate

        ranks = jnp.arange(M, dtype=jnp.uint32)
        table, claims, fresh, pos, hv = self._probe_insert(
            c["vis"], c["claims"], recv_key, live_rep, ranks)
        hovf = c["hovf"] | hv
        n_fresh = fresh.sum(dtype=jnp.int32)
        ovf_now = c["n_lvl"] + n_fresh > LB - M
        if self._step_atomic:
            # spill-composed mode: revert on EVERY device when ANY
            # device tripped, so the tripped step commits nowhere and
            # the host can resume from trip_base exactly
            bad_now = pre_bad | jax.lax.all_gather(ovf_now | hv,
                                                   "d").any()
            stepped = ~(c["ovf"] | c["fovf"] | c["sovf"] | c["hovf"])
            trip_base = jnp.where(stepped & bad_now, base,
                                  c["trip_base"])
        else:
            # classic mode: local revert; the whole-level journal
            # rollback at finalize handles cross-device consistency
            bad_now = ovf_now
            trip_base = c["trip_base"]
        # level shard would overflow: revert this step's inserts and
        # skip the append (the level replays; see engine/bfs)
        ridx2 = jnp.where(fresh & bad_now, pos, VB)
        table = tuple(table[w].at[ridx2].set(U32MAX, mode="drop")
                      for w in range(W))
        fresh = fresh & ~bad_now
        n_fresh = jnp.where(bad_now, 0, n_fresh)
        ovf = c["ovf"] | ovf_now
        if self._step_atomic:
            # a tripped step replays from trip_base: count its
            # generated successors only when it commits
            n_gen = c["n_gen"] + jnp.where(gate & ~bad_now, gen_inc, 0)
        else:
            # classic mode: the whole-level replay resets n_gen at the
            # finalize, so the unconditional count is exact
            n_gen = c["n_gen"] + gen_inc

        ridx = jnp.arange(M, dtype=jnp.int32)
        lpos = jnp.where(fresh,
                         jnp.cumsum(fresh.astype(jnp.int32)) - 1, M)
        lidx = lax.optimization_barrier(
            jnp.zeros((M,), jnp.int32).at[lpos].set(ridx, mode="drop"))

        start = jnp.minimum(c["n_lvl"], LB - M)
        rows = lax.optimization_barrier(
            {k: recv_row[k][lidx] for k in recv_row})   # narrow
        # invariants/constraints for every window row: the appended
        # block reads them through lidx; stage-2 replacements read
        # their own lane (counter-reading scenario predicates must
        # re-evaluate on the surviving representative's content)
        inv_all, con_all = lax.optimization_barrier(
            self._phase2_impl(self.ir.widen(recv_row)))
        inv, con = inv_all[lidx], con_all[lidx]
        lvl = {k: lax.dynamic_update_slice_in_dim(v, rows[k], start, 0)
               for k, v in c["lvl"].items()}
        extra = {}
        if self._track_keys:
            # the appended rows' dedup keys (stage-2 replacements swap
            # content behind the SAME key, so no update there)
            rkey = jnp.stack(recv_key, axis=-1)            # [M, W]
            extra["lkey"] = lax.dynamic_update_slice(
                c["lkey"], rkey[lidx], (start, 0))
        lpar = lax.dynamic_update_slice_in_dim(
            c["lpar"], recv_pgid[lidx], start, 0)
        llane = lax.dynamic_update_slice_in_dim(
            c["llane"], recv_lane[lidx], start, 0)
        lpfp = lax.dynamic_update_slice(
            c["lpfp"], recv_pfp[lidx], (start, 0))
        jslot = lax.dynamic_update_slice_in_dim(
            c["jslot"], pos[lidx], start, 0)
        linv = lax.dynamic_update_slice(c["linv"], inv, (start, 0))
        lcon = lax.dynamic_update_slice_in_dim(c["lcon"], con, start, 0)

        # ---- content-canonical survivor, stage 2: replace-if-smaller
        # for duplicates of keys admitted by an EARLIER window of this
        # level.  lrow maps table slot -> level row for this level's
        # inserts (reset to -1 at every level boundary/replay).  Rows
        # are disjoint across lanes (one rep per key per window), so
        # the scatters race-free; a replaced row keeps its jslot.
        # The comparison key is (content, parent fp, lane) — the same
        # extended canonical key stage 1 uses, so the level-wide min
        # covers provenance too (mesh-invariant witness traces).
        lrow = c["lrow"].at[jnp.where(fresh, pos, VB)].set(
            (start + lpos).astype(jnp.int32), mode="drop")
        dup = live_rep & ~fresh & ~bad_now
        tgt = lrow[jnp.clip(pos, 0, VB - 1)]
        dup = dup & (tgt >= 0)
        tgt_c = jnp.clip(tgt, 0, LB - 1)
        swords = content_words({k: lvl[k][tgt_c] for k in lvl}) + \
            [lpfp[tgt_c, w] for w in range(W)] + \
            [llane[tgt_c].astype(jnp.uint32)]
        cand_words = cwords + pwords
        less = jnp.zeros((M,), bool)
        eq = jnp.ones((M,), bool)
        for cw, sw in zip(cand_words, swords):
            less = less | (eq & (cw < sw))
            eq = eq & (cw == sw)
        repl = dup & less
        widx2 = jnp.where(repl, tgt_c, LB)
        lvl = {k: v.at[widx2].set(recv_row[k], mode="drop")
               for k, v in lvl.items()}
        lpar = lpar.at[widx2].set(recv_pgid, mode="drop")
        llane = llane.at[widx2].set(recv_lane, mode="drop")
        lpfp = lpfp.at[widx2].set(recv_pfp, mode="drop")
        linv = linv.at[widx2].set(inv_all, mode="drop")
        lcon = lcon.at[widx2].set(con_all, mode="drop")
        return dict(c, vis=table, claims=claims, lvl=lvl, lpar=lpar,
                    llane=llane, lpfp=lpfp, jslot=jslot, linv=linv,
                    lcon=lcon, lrow=lrow, **extra,
                    n_lvl=jnp.minimum(c["n_lvl"] + n_fresh, LB - M),
                    n_gen=n_gen, ovf=ovf, fovf=fovf, sovf=sovf,
                    hovf=hovf, famx=famx, trip_base=trip_base,
                    base=base + B)

    # -----------------------------------------------------------------

    def _local_finalize(self, c):
        LB = c["fmask"].shape[0]
        VB = c["vis"][0].shape[0]
        n_lvl = c["n_lvl"]
        bad_local = c["ovf"] | c["fovf"] | c["sovf"] | c["hovf"]
        # any device overflowing aborts the level everywhere
        bad = jax.lax.all_gather(bad_local, "d").any()
        validrow = jnp.arange(LB, dtype=jnp.int32) < n_lvl
        inv_ok = (c["linv"] | ~validrow[:, None]
                  if self.inv_names else c["linv"])
        con = c["lcon"]
        n_viol = (~inv_ok).sum(dtype=jnp.int32)
        faults = ((c["lvl"]["ctr"][:, C_OVERFLOW] > 0) &
                  validrow).sum(dtype=jnp.int32)

        # device-major global ids for this level
        nl_vec = jax.lax.all_gather(n_lvl, "d")             # [D]
        prefix = jnp.cumsum(nl_vec) - nl_vec
        d_idx = jax.lax.axis_index("d")
        total = nl_vec.sum()

        def commit(c):
            # the level's keys are already in the table shard; the
            # swapped-in frontier rows' global ids are device-major
            # arithmetic, materialized into the gids table here so the
            # step can read ids uniformly (host-compacted frontiers in
            # the spill-composed engine upload theirs instead)
            fmask = con & validrow
            gids = c["g_off"] + prefix[d_idx] + \
                jnp.arange(LB, dtype=jnp.int32)
            return (c["lvl"], c["front"], fmask, n_lvl, c["vis"],
                    gids, c["g_off"] + total)

        def abandon(c):
            # roll the table shard back via the journal (engine/bfs
            # _probe_insert rollback note)
            cidx = jnp.where(validrow, c["jslot"], VB)
            vis = tuple(c["vis"][w].at[cidx].set(U32MAX, mode="drop")
                        for w in range(self.W))
            return (c["front"], c["lvl"], c["fmask"], c["n_front"],
                    vis, c["gids"], c["g_off"])

        front, lvl, fmask, n_front, vis, gids, g_next = lax.cond(
            bad, abandon, commit, c)
        # [D, 10+n_fams] replicated via all_gather so every controller
        # process reads the full matrix (multi-host safe; out_specs
        # P(None)); the famx tail drives per-family cap growth
        scal = jax.lax.all_gather(jnp.concatenate([jnp.stack([
            n_lvl, n_viol, faults, n_front,
            c["ovf"].astype(jnp.int32), c["fovf"].astype(jnp.int32),
            c["n_gen"], (con & validrow).sum(dtype=jnp.int32),
            c["sovf"].astype(jnp.int32), c["hovf"].astype(jnp.int32)]),
            c["famx"]]), "d")
        new_c = dict(c, vis=vis, front=front, lvl=lvl,
                     fmask=fmask, n_front=n_front,
                     n_lvl=jnp.int32(0), n_gen=jnp.int32(0),
                     ovf=jnp.bool_(False), fovf=jnp.bool_(False),
                     sovf=jnp.bool_(False), hovf=jnp.bool_(False),
                     famx=jnp.zeros_like(c["famx"]),
                     # slot->level-row map is per-level (commit moves to
                     # the next level; abandon replays this one)
                     lrow=jnp.full_like(c["lrow"], -1),
                     trip_base=jnp.int32(-1),
                     base=jnp.int32(0), gids=gids, g_off=g_next)
        return new_c, dict(inv_ok=inv_ok, scal=scal)

    # -----------------------------------------------------------------
    # fused K-level driver (the mesh twin of engine/bfs._burst_core):
    # the _shard_level body — lock-step chunk steps over all_to_all —
    # becomes the body of ONE MORE while_loop, committing one level per
    # iteration inside the SAME shard_map program, with the per-level
    # all_gather id-assignment kept in-loop and ONE packed
    # [D, L_MAX+1, n_scalars] stats matrix read back per burst.  A
    # shard_map dispatch + scalar sync per level is "genuinely
    # expensive" (bfs.py finalize note) — this removes all but one of
    # them for runs of small levels.
    #
    # Archive discipline: per-level parent/lane/state/inv rows are
    # copied into [L_MAX, KBd]-wide ring buffers, KBd =
    # min(_burst_chunks * BL, LB) rows per shard; a level whose shard
    # outgrows KBd — or that trips ANY overflow — is abandoned via the
    # whole-level journal rollback (_local_finalize's abandon,
    # replicated here) and replayed by the per-level path.  The
    # loop-carried state adds only the ring archives on top of what
    # _shard_level already loop-carries.
    # -----------------------------------------------------------------

    def _mesh_burst_width(self) -> int:
        """Per-shard burst ring rows (the host entry gate compares the
        per-device frontier max against this)."""
        return min(self._burst_chunks * self.BL, self.LB)

    def _sharded_burst_call(self, carry, fam_caps, levels_left,
                            states_cap):
        specs = jax.tree_util.tree_map(lambda _: P("d"), carry)
        st_specs = {k: P("d") for k in carry["lvl"]}
        out_specs = (specs, dict(stats=P(None), par=P("d"),
                                 lane=P("d"), st=st_specs,
                                 inv=P("d")))
        return _shard_map(
            lambda c, ll, sc: self._shard_burst(c, fam_caps, ll, sc),
            self.mesh, (specs, P(), P()), out_specs)(
                carry, levels_left, states_cap)

    def _shard_burst(self, carry, fam_caps, levels_left, states_cap):
        c0 = jax.tree_util.tree_map(lambda x: x[0], carry)
        LB = c0["fmask"].shape[0]
        VB = c0["vis"][0].shape[0]
        KBd = self._mesh_burst_width()
        L_MAX = self.burst_levels
        n_inv = len(self.inv_names)
        d_idx = jax.lax.axis_index("d")

        st = dict(
            c=c0, li=jnp.int32(0), done=jnp.int32(0),
            bail=jnp.bool_(False), viol=jnp.bool_(False),
            stats=jnp.zeros((L_MAX, self._BS_N), jnp.int32),
            opar=jnp.full((L_MAX, KBd), -1, jnp.int32),
            olane=jnp.full((L_MAX, KBd), -1, jnp.int32),
            ost={k: jnp.zeros((L_MAX, KBd) + v.shape[1:], v.dtype)
                 for k, v in c0["lvl"].items()},
            oinv=jnp.ones((L_MAX, KBd, n_inv), bool),
        )

        def cond(st):
            # every operand is replicated (derived from all_gathers),
            # so the decision is uniform across the mesh
            more = jax.lax.all_gather(st["c"]["n_front"] > 0,
                                      "d").any()
            return (~st["bail"] & ~st["viol"]
                    & (st["li"] < levels_left) & more
                    & (st["done"] < states_cap))

        def body(st):
            def chunk_cond(cc):
                more = cc["base"] < cc["n_front"]
                bad = cc["ovf"] | cc["fovf"] | cc["sovf"] | cc["hovf"]
                flags = jax.lax.all_gather(jnp.stack([more, bad]), "d")
                return flags[:, 0].any() & ~flags[:, 1].any()

            c = lax.while_loop(
                chunk_cond, lambda cc: self._local_step(cc, fam_caps),
                st["c"])
            n_lvl = c["n_lvl"]
            bad = jax.lax.all_gather(
                c["ovf"] | c["fovf"] | c["sovf"] | c["hovf"] |
                (n_lvl > KBd), "d").any()
            validrow = jnp.arange(LB, dtype=jnp.int32) < n_lvl
            inv_ok = (c["linv"] | ~validrow[:, None]
                      if n_inv else c["linv"])
            con = c["lcon"]
            n_viol = (~inv_ok).sum(dtype=jnp.int32)
            faults = ((c["lvl"]["ctr"][:, C_OVERFLOW] > 0) &
                      validrow).sum(dtype=jnp.int32)
            n_expand = (con & validrow).sum(dtype=jnp.int32)
            nl_vec = jax.lax.all_gather(n_lvl, "d")
            prefix = jnp.cumsum(nl_vec) - nl_vec
            total = nl_vec.sum()
            viol_g = jax.lax.all_gather(n_viol, "d").sum() > 0
            gen_l = c["n_gen"]
            li = st["li"]

            def commit(op):
                c, opar, olane, ost, oinv = op
                opar = lax.dynamic_update_slice(
                    opar, c["lpar"][:KBd][None], (li, 0))
                olane = lax.dynamic_update_slice(
                    olane, c["llane"][:KBd][None], (li, 0))
                ost = {k: lax.dynamic_update_slice(
                           ost[k], c["lvl"][k][:KBd][None],
                           (li,) + (0,) * (ost[k].ndim - 1))
                       for k in ost}
                oinv = lax.dynamic_update_slice(
                    oinv, inv_ok[:KBd][None], (li, 0, 0))
                gids_all = c["g_off"] + prefix[d_idx] + \
                    jnp.arange(LB, dtype=jnp.int32)
                if self._burst_compact_frontier:
                    # spill-composed mode: drop pruned rows from the
                    # next frontier on device, exactly as the host does
                    # between levels (archives above keep ALL rows) —
                    # the window packing, and so every later level's
                    # row order and gids, must match the un-bursted
                    # path bit-for-bit
                    keep = con & validrow
                    n_keep = keep.sum(dtype=jnp.int32)
                    kpos = jnp.where(
                        keep,
                        jnp.cumsum(keep.astype(jnp.int32)) - 1, LB)
                    kidx = jnp.zeros((LB,), jnp.int32).at[kpos].set(
                        jnp.arange(LB, dtype=jnp.int32), mode="drop")
                    front = {k: c["lvl"][k][kidx] for k in c["lvl"]}
                    inrange = jnp.arange(LB, dtype=jnp.int32) < n_keep
                    gids = jnp.where(inrange, gids_all[kidx], -1)
                    fmask = inrange
                    n_front = n_keep
                else:
                    front = c["lvl"]
                    gids = gids_all
                    fmask = con & validrow
                    n_front = n_lvl
                new_c = dict(c, front=front, lvl=c["front"],
                             fmask=fmask, n_front=n_front, gids=gids,
                             g_off=c["g_off"] + total,
                             n_lvl=jnp.int32(0), n_gen=jnp.int32(0),
                             famx=jnp.zeros_like(c["famx"]),
                             lrow=jnp.full_like(c["lrow"], -1),
                             trip_base=jnp.int32(-1),
                             base=jnp.int32(0))
                return new_c, opar, olane, ost, oinv

            def abandon(op):
                # whole-level journal rollback on every shard (the
                # burst never spills mid-level, so the journal is the
                # exact record of this level's inserts — the per-level
                # path replays the level from the intact frontier)
                c, opar, olane, ost, oinv = op
                cidx = jnp.where(validrow, c["jslot"], VB)
                vis = tuple(
                    c["vis"][w].at[cidx].set(U32MAX, mode="drop")
                    for w in range(self.W))
                new_c = dict(c, vis=vis,
                             n_lvl=jnp.int32(0), n_gen=jnp.int32(0),
                             ovf=jnp.bool_(False),
                             fovf=jnp.bool_(False),
                             sovf=jnp.bool_(False),
                             hovf=jnp.bool_(False),
                             famx=jnp.zeros_like(c["famx"]),
                             lrow=jnp.full_like(c["lrow"], -1),
                             trip_base=jnp.int32(-1),
                             base=jnp.int32(0))
                return new_c, opar, olane, ost, oinv

            c2, opar, olane, ost, oinv = lax.cond(
                bad, abandon, commit,
                (c, st["opar"], st["olane"], st["ost"], st["oinv"]))
            row = jnp.where(
                bad, jnp.zeros((self._BS_N,), jnp.int32),
                jnp.stack([n_lvl, n_viol, faults, n_expand, gen_l,
                           jnp.int32(0), jnp.int32(0), jnp.int32(0)]))
            new = dict(st, c=c2, opar=opar, olane=olane, ost=ost,
                       oinv=oinv)
            new["stats"] = lax.dynamic_update_slice(
                st["stats"], row[None], (li, 0))
            new["li"] = li + (~bad).astype(jnp.int32)
            new["bail"] = st["bail"] | bad
            new["viol"] = st["viol"] | (~bad & viol_g)
            new["done"] = st["done"] + jnp.where(bad, 0, total)
            return new

        st = lax.while_loop(cond, body, st)
        meta = jnp.stack([st["li"], st["bail"].astype(jnp.int32),
                          st["c"]["n_front"],
                          st["viol"].astype(jnp.int32), st["done"],
                          jnp.int32(0), jnp.int32(0), jnp.int32(0)])
        stats = jnp.concatenate([st["stats"], meta[None]], axis=0)
        sg = jax.lax.all_gather(stats, "d")     # [D, L_MAX+1, NS]
        return (jax.tree_util.tree_map(lambda x: x[None], st["c"]),
                dict(stats=sg, par=st["opar"][None],
                     lane=st["olane"][None],
                     st={k: v[None] for k, v in st["ost"].items()},
                     inv=st["oinv"][None]))

    # -----------------------------------------------------------------

    def _fresh_sharded_carry(self):
        D, LB, VB, FC = self.D, self.LB, self.VB, self.FC
        one = self.ir.narrow(self.lay, self.ir.encode(
            self.lay, *self.ir.init_state(self.cfg)))
        zeros = {k: jnp.zeros((D, LB) + v.shape, dtype=v.dtype)
                 for k, v in one.items()}
        n_inv = len(self.inv_names)
        extra = {}
        if self._track_keys:
            extra["lkey"] = jnp.full((D, LB, self.W), U32MAX)
        return dict(
            **extra,
            vis=tuple(jnp.full((D, VB), U32MAX) for _ in range(self.W)),
            claims=jnp.full((D, VB), U32MAX),
            # table slot -> this-level row (content-canonical stage 2)
            lrow=jnp.full((D, VB), -1, jnp.int32),
            jslot=jnp.full((D, LB), -1, jnp.int32),
            linv=jnp.ones((D, LB, n_inv), bool),
            lcon=jnp.ones((D, LB), bool),
            lvl=zeros,
            lpar=jnp.full((D, LB), -1, jnp.int32),
            llane=jnp.full((D, LB), -1, jnp.int32),
            # per-row parent fingerprint: the mesh-invariant half of
            # the provenance key (stage-2 comparisons read it back)
            lpfp=jnp.full((D, LB, self.W), U32MAX),
            # per-frontier-row global ids (refreshed by the commit
            # finalize; uploaded by the spill-composed engine)
            gids=jnp.full((D, LB), -1, jnp.int32),
            trip_base=jnp.full((D,), -1, jnp.int32),
            cidx=jnp.zeros((D, FC), jnp.int32),
            # shape anchor for SC: jit caches on input avals, and SC
            # otherwise only shapes internal send/recv buffers — an SC
            # growth would silently cache-hit the stale trace
            sscr=jnp.zeros((D, self.SC), jnp.int32),
            n_lvl=jnp.zeros((D,), jnp.int32),
            n_gen=jnp.zeros((D,), jnp.int32),
            famx=jnp.zeros((D, len(self.expander.families)), jnp.int32),
            base=jnp.zeros((D,), jnp.int32),
            g_off=jnp.zeros((D,), jnp.int32),
            ovf=jnp.zeros((D,), bool),
            fovf=jnp.zeros((D,), bool),
            sovf=jnp.zeros((D,), bool),
            hovf=jnp.zeros((D,), bool),
            front={k: jnp.zeros_like(v) for k, v in zeros.items()},
            fmask=jnp.zeros((D, LB), bool),
            n_front=jnp.zeros((D,), jnp.int32),
        )

    # -----------------------------------------------------------------

    def check(self, max_depth: int = 10 ** 9, max_states: int = 10 ** 9,
              stop_on_violation: bool = False,
              seed_states: Optional[List] = None,
              checkpoint_path: Optional[str] = None,
              checkpoint_every: int = 1,
              resume_from: Optional[str] = None,
              resume_image=None,
              verbose: bool = False, obs=None) -> CheckResult:
        """``resume_image`` — a ``resil.portable.PortableImage``
        extracted from ANY engine family's checkpoint: the visited key
        set and frontier rows are re-partitioned onto THIS mesh by
        hash ownership, so a checkpoint written on a different device
        count (or by the spill/classic engines) resumes here
        (ROADMAP item-2 elastic resume)."""
        obs = self._obs = obs if obs is not None else NULL_OBS
        t0 = time.perf_counter()
        lay = self.lay
        D, W = self.D, self.W
        if resume_from is not None and resume_image is not None:
            raise ValueError(
                "resume_from and resume_image are mutually exclusive")
        if resume_from is not None:
            carry, res, meta = self._load_checkpoint(resume_from)
            n_states = meta["n_states"]
            n_vis = np.asarray(meta["n_vis"], dtype=np.int64)
            depth = meta["depth"]
            n_front = meta["n_front"]
            resumed = True
        elif resume_image is not None:
            (carry, res, n_states, n_vis, depth,
             n_front) = self._resume_portable(resume_image)
            resumed = True
        else:
            # shared root admission (engine/bfs._dedup_roots), then
            # this engine's extra step: hash-ownership routing
            roots, rk, pin_interiors = self._dedup_roots(seed_states)
            per_dev: List[List[int]] = [[] for _ in range(D)]
            for r in range(len(rk)):
                per_dev[int(rk[r, W - 1]) % D].append(r)
            # grow the level shard until the most-loaded device's seeds
            # fit with the receive-window margin (punctuated-search
            # seed sets can be thousands of states, hash-skewed across
            # devices)
            max_seed = max(len(p) for p in per_dev)
            while self.LB - self.D * self.SC < 2 * max_seed:
                self.LB = self._round_lb(2 * self.LB)
            while max_seed + self.LB > self._LOAD_MAX * self.VB:
                self.VB *= 4

            res = CheckResult(distinct_states=0,
                              generated_states=len(rk), depth=0)
            # replicated computation: every controller checks the same
            # interiors and takes identical violation counts
            self._check_pin_interiors(pin_interiors, res)
            self._states = []
            self._parents = []
            self._lanes = []
            self._arch_segs = []

            # root invariants/constraints (levels get theirs in the
            # step)
            inv_r, con_r = (np.asarray(a) for a in self._phase2(
                {k: jnp.asarray(v) for k, v in roots.items()}))

            carry_np = self._fresh_sharded_carry_host()
            nl = np.zeros((D,), np.int32)
            for d in range(D):
                for r, i in enumerate(per_dev[d]):
                    for k in roots:
                        carry_np["lvl"][k][d, r] = roots[k][i]
                    carry_np["lpar"][d, r] = -1
                    carry_np["llane"][d, r] = -1
                    carry_np["linv"][d, r] = inv_r[i]
                    carry_np["lcon"][d, r] = con_r[i]
                nl[d] = len(per_dev[d])
            # root global ids, device-major (the finalize commit swaps
            # lvl->front and recomputes gids the same way; seeding them
            # here keeps the seed finalize's abandon-path gids sane)
            pref = np.cumsum(nl) - nl
            for d in range(D):
                carry_np["gids"][d, :nl[d]] = pref[d] + \
                    np.arange(nl[d], dtype=np.int32)
                rkd = rk[per_dev[d]]                       # [n, W]
                # host-side probe placement into the empty table shard
                slots = self._host_probe_assign(rkd, vcap=self.VB)
                for r, sl in enumerate(slots):
                    for w in range(W):
                        carry_np["vis"][w][d, sl] = rkd[r, w]
                    carry_np["jslot"][d, r] = sl
            carry_np["n_lvl"] = nl
            carry = self._to_device(carry_np)

            n_states = 0
            n_vis = np.zeros((D,), np.int64)
            depth = 0
            resumed = False
        self._stamp_mode(res)

        def run_finalize(carry):
            # seed carries have n_front=0 everywhere, so the level
            # program skips straight to its finalize — no separate
            # finalize-only shard_map compile
            carry, out = self._level_jit(carry, self.FAM_CAPS)
            return carry, out, np.asarray(out["scal"])  # [D, 10+n_fams]

        def grow_table_if_needed(carry, min_add=0):
            # pessimistic per-shard load bound, checked between levels
            # (min_add: a burst can admit up to burst_levels ring-widths
            # per shard before its next host sync)
            need = int(n_vis.max()) + max(self.LB, min_add)
            if need > self._LOAD_MAX * self.VB:
                while need > self._LOAD_MAX * self.VB:
                    self.VB *= 4
                carry = self._rehash_sharded(carry)
            return carry

        def local_rows(arr):
            """[(d, np_row)] for the addressable device rows of a
            P('d')-sharded [D, ...] array — all rows on one host, only
            this process's rows under multi-controller."""
            rows = []
            for s in arr.addressable_shards:
                ix = s.index[0]
                d = (ix.start or 0) if isinstance(ix, slice) else ix
                rows.append((int(d), np.asarray(s.data)[0]))
            return sorted(rows, key=lambda t: t[0])

        def harvest(carry, out, scal):
            nonlocal n_states
            nl = scal[:, 0]
            n_lvl = int(nl.sum())
            res.distinct_states += n_lvl
            res.overflow_faults += int(scal[:, 2].sum())
            res.generated_states += int(scal[:, 6].sum())
            # global count from the replicated matrix: identical on
            # every controller (the violations LIST is shard-local)
            res.violations_global += int(scal[:, 1].sum())
            prefix = np.cumsum(nl) - nl
            rows = None
            if self.store_states or scal[:, 1].sum():
                # one device->host transfer of the front buffer, shared
                # by the state archive and violation decoding
                rows = {k: dict(local_rows(v))
                        for k, v in carry["front"].items()}
            if self.store_states:
                # archives cover this controller's shards (= everything
                # on one host; under MultiHostEngine each controller
                # archives its own devices and _arch_segs records which
                # (device, count) segments its per-level concatenation
                # holds, so per-controller archive files can be merged
                # device-major into the global id order at trace time)
                pars = local_rows(carry["lpar"])
                lns = dict(local_rows(carry["llane"]))
                self._parents.append(np.concatenate(
                    [row[:nl[d]] for d, row in pars]))
                self._lanes.append(np.concatenate(
                    [lns[d][:nl[d]] for d, _ in pars]))
                self._states.append(
                    {k: np.concatenate([rows[k][d][:nl[d]]
                                        for d, _ in pars])
                     for k in rows})
                self._arch_segs.append(
                    [(int(d), int(nl[d])) for d, _ in pars])
            if scal[:, 1].sum():
                inv_shards = local_rows(out["inv_ok"])
                for d, inv_ok in inv_shards:
                    for j, nm in enumerate(self.inv_names):
                        for s in np.nonzero(~inv_ok[:nl[d], j])[0]:
                            vsv, vh = self.ir.decode(lay, _take(
                                {k: rows[k][d] for k in rows}, s))
                            res.violations.append(Violation(
                                nm, n_states + int(prefix[d]) + int(s),
                                state=vsv, hist=vh))
            n_states += n_lvl
            for d in range(D):
                n_vis[d] += nl[d]
            # global state ids are device int32; fail loud, not wrap
            driver.guard_id_space(n_states)
            return int(scal[:, 3].max())

        if not resumed:
            carry, out, scal = run_finalize(carry)
            n_front = harvest(carry, out, scal)
        # decide from the REPLICATED count: every controller takes the
        # same branch (a process-local decision would deadlock the
        # mesh collectives under multi-controller runs)
        if stop_on_violation and res.violations_global:
            res.seconds = time.perf_counter() - t0
            return res

        # burst_ok: a burst that committed levels then bailed keeps the
        # bailing level's frontier intact — re-entering would replay
        # the identical lock-step chunks and bail again (one wasted
        # shard_map round trip); skip the burst for that one level
        burst_ok = True
        while n_front and depth < max_depth and \
                res.distinct_states < max_states:
            # chaos site: dispatch-time device/runtime error at the
            # level boundary (resil/chaos) — before any device work,
            # so the last checkpoint stays the exact resume point
            chaos_point("dispatch")
            kbd = self._mesh_burst_width()
            if self.burst and burst_ok and n_front <= kbd:
                # fused K-level burst: ONE shard_map dispatch + ONE
                # stats readback for up to burst_levels small levels
                # (_shard_burst).  nlev == 0 means the first level
                # bailed — fall through to the per-level path below.
                t1 = time.perf_counter()
                with obs.span("burst_dispatch"):
                    carry = grow_table_if_needed(
                        carry, min_add=self.burst_levels * kbd)
                    lv_left = min(self.burst_levels, max_depth - depth)
                    st_cap = max(1,
                                 min(max_states - res.distinct_states,
                                     2 ** 31 - 1))
                    carry, bout = self._burst_mesh_jit(
                        carry, self.FAM_CAPS, jnp.int32(lv_left),
                        jnp.int32(st_cap))
                    stats = np.asarray(bout["stats"])  # [D,L_MAX+1,NS]
                nlev = int(stats[0, -1, 0])
                bailed = bool(stats[0, -1, 1])
                res.burst_dispatches += 1
                res.burst_bailouts += int(bailed)
                if nlev:
                    burst_ok = not bailed
                    d0 = depth
                    viol_any = bool(stats[0, -1, 3])
                    _hv_span = obs.span("harvest")
                    _hv_span.__enter__()
                    par_rows = lane_rows = st_rows = inv_rows = None
                    if self.store_states or viol_any:
                        par_rows = dict(local_rows(bout["par"]))
                        lane_rows = dict(local_rows(bout["lane"]))
                        st_rows = {k: dict(local_rows(v))
                                   for k, v in bout["st"].items()}
                        inv_rows = dict(local_rows(bout["inv"]))

                    def _stats(li):
                        return (int(stats[:, li, 0].sum()),
                                int(stats[:, li, 1].sum()),
                                int(stats[:, li, 2].sum()),
                                int(stats[:, li, 3].sum()),
                                int(stats[:, li, 4].sum()))

                    def _arch(li, _n_lvl):
                        if not self.store_states:
                            return
                        nl = stats[:, li, 0]
                        ds = sorted(par_rows)
                        self._parents.append(np.concatenate(
                            [par_rows[d][li, :nl[d]] for d in ds]))
                        self._lanes.append(np.concatenate(
                            [lane_rows[d][li, :nl[d]] for d in ds]))
                        self._states.append(
                            {k: np.concatenate(
                                [st_rows[k][d][li, :nl[d]]
                                 for d in ds]) for k in st_rows})
                        self._arch_segs.append(
                            [(int(d), int(nl[d])) for d in ds])

                    def _viol(li, _n_lvl, gid_base):
                        nl = stats[:, li, 0]
                        prefix = np.cumsum(nl) - nl
                        for d in sorted(inv_rows):
                            inv_ok = inv_rows[d]
                            for j, nm in enumerate(self.inv_names):
                                for s in np.nonzero(
                                        ~inv_ok[li, :nl[d], j])[0]:
                                    vsv, vh = self.ir.decode(
                                        lay, _take(
                                        {k: st_rows[k][d][li]
                                         for k in st_rows}, s))
                                    res.violations.append(
                                        Violation(
                                            nm, gid_base +
                                            int(prefix[d]) + int(s),
                                            state=vsv, hist=vh))

                    def _vis(li, _n_lvl):
                        for d in range(D):
                            n_vis[d] += stats[d, li, 0]

                    depth, n_states = driver.harvest_fused_levels(
                        res, nlev, _stats, depth, n_states,
                        archive=_arch, violations=_viol,
                        visited=_vis)
                    _hv_span.__exit__(None, None, None)
                    n_front = int(stats[:, -1, 2].max())
                    if checkpoint_path is not None and \
                            driver.ckpt_due_after_burst(
                                depth, d0, checkpoint_every):
                        self._save_checkpoint(checkpoint_path, carry,
                                              res, depth, n_states,
                                              n_vis, n_front)
                    obs.dispatch(kind="burst", depth=depth,
                                 frontier=n_front,
                                 metrics=res.metrics.as_dict())
                    if stop_on_violation and res.violations_global:
                        break
                    if verbose:
                        print(f"burst: {nlev} levels to depth {depth} "
                              f"(total {res.distinct_states}), "
                              f"frontier(max/dev) {n_front}, "
                              f"{time.perf_counter() - t1:.2f}s")
                    continue
            burst_ok = True        # re-arm after a per-level level
            depth += 1
            _lvl_span = obs.span("level_dispatch")
            _lvl_span.__enter__()
            carry = grow_table_if_needed(carry)
            while True:
                carry, out = self._level_jit(carry, self.FAM_CAPS)
                scal = np.asarray(out["scal"])
                ovf = bool(scal[:, 4].any())
                fovf = bool(scal[:, 5].any())
                sovf = bool(scal[:, 8].any())
                hovf = bool(scal[:, 9].any())
                if not (ovf or fovf or sovf or hovf):
                    break
                old_caps = (self.LB, self.FC, self.SC, self.FAM_CAPS)
                if fovf:
                    famx = scal[:, 10:10 + len(self.FAM_CAPS)].max(axis=0)
                    caps = list(self.FAM_CAPS)
                    fam_over = False
                    for fi, fam in enumerate(self.expander.families):
                        hard = fam.n_lanes * self.BL
                        while caps[fi] < hard and famx[fi] > caps[fi]:
                            caps[fi] = min(2 * caps[fi], hard)
                            fam_over = True
                    self.FAM_CAPS = tuple(caps)
                    if not fam_over:
                        self.FC *= 4
                if sovf or (fovf and self.FC != old_caps[1]):
                    self.SC = max(4 * self.SC, 4 * self.FC // self.D)
                if ovf or self.LB < max(4 * self.FC,
                                        2 * self.D * self.SC):
                    self.LB = self._round_lb(
                        max((4 * self.LB) if ovf else self.LB,
                            4 * self.FC, 2 * self.D * self.SC))
                if hovf:
                    self.VB *= 4
                    carry = self._rehash_sharded(carry)
                if verbose:
                    print(f"level {depth}: overflow "
                          f"(ovf={ovf} fovf={fovf} sovf={sovf} "
                          f"hovf={hovf}), LB={self.LB} FC={self.FC} "
                          f"SC={self.SC} VB={self.VB}")
                if (self.LB, self.FC, self.SC) != old_caps[:3]:
                    carry = self._grow_sharded(carry)
                    # the replayed level can add up to the NEW LB keys
                    # per shard: re-check the table load bound
                    carry = grow_table_if_needed(carry)
            _lvl_span.__exit__(None, None, None)
            with obs.span("harvest"):
                n_front = harvest(carry, out, scal)
            depth = driver.gate_level_depth(
                res, depth, int(scal[:, 0].sum()),
                int(scal[:, 6].sum()), int(scal[:, 7].sum()))
            if checkpoint_path is not None and \
                    driver.ckpt_due_at_level(depth, checkpoint_every):
                self._save_checkpoint(checkpoint_path, carry, res,
                                      depth, n_states, n_vis, n_front)
            obs.dispatch(kind="level", depth=depth, frontier=n_front,
                         metrics=res.metrics.as_dict())
            if stop_on_violation and res.violations_global:
                break
            if verbose:
                print(f"depth {depth}: +{int(scal[:, 0].sum())} states "
                      f"(total {res.distinct_states}), "
                      f"frontier(max/dev) {n_front}")
        res.depth = depth
        res.seconds = time.perf_counter() - t0
        return res

    def _to_device(self, carry_np):
        """Host carry pytree -> device arrays.  MultiHostEngine
        overrides this to build globally-sharded arrays."""
        return jax.tree_util.tree_map(jnp.asarray, carry_np)

    def _fresh_sharded_carry_host(self):
        """Host-side (numpy) fresh carry, for seeding mutation before
        _to_device."""
        return jax.tree_util.tree_map(
            lambda x: np.array(x), self._fresh_sharded_carry())

    def _grow_sharded(self, carry):
        """Re-home the carry in bigger per-device buffers (frontier and
        the visited table survive; the level buffer resets — the level
        replays).  Table growth goes through _rehash_sharded first."""
        D = self.D
        old = carry
        assert old["vis"][0].shape[1] == self.VB, \
            "grow the table via _rehash_sharded first"
        new = self._fresh_sharded_carry()
        new["vis"] = old["vis"]
        new["claims"] = old["claims"]
        olb = old["fmask"].shape[1]
        pad = self.LB - olb
        new["front"] = {k: jnp.concatenate(
            [old["front"][k],
             jnp.zeros((D, pad) + v.shape[2:], v.dtype)], axis=1)
            for k, v in old["front"].items()}
        new["fmask"] = jnp.concatenate(
            [old["fmask"], jnp.zeros((D, pad), bool)], axis=1)
        new["n_front"] = old["n_front"]
        new["g_off"] = old["g_off"]
        # gids ride with the frontier rows they describe
        olb2 = old["gids"].shape[1]
        new["gids"] = jnp.concatenate(
            [old["gids"], jnp.full((D, self.LB - olb2), -1,
                                   jnp.int32)], axis=1)
        return new

    # ------------------------------------------------------------------
    # checkpoint / resume (sharded layout; single-controller — the
    # _save_checkpoint entry fails fast under multiple controllers;
    # MultiHostEngine overrides both methods with per-controller shard
    # files).  Same wavefront semantics as
    # engine/bfs: written at level boundaries, resume lands on
    # bit-identical counts.
    # ------------------------------------------------------------------

    def _save_checkpoint(self, path, carry, res, depth, n_states,
                         n_vis, n_front):
        if jax.process_count() > 1:
            # fail fast, not hours in: this serializer np.asarray's the
            # whole carry, which a multi-controller run cannot address
            raise NotImplementedError(
                "ShardedEngine checkpoints are single-controller; use "
                "MultiHostEngine (per-controller shard files) for "
                "multi-process runs")
        with self._obs.span("checkpoint"):
            ckpt_write(path, carry, self.store_states, self._parents,
                       self._lanes, self._states, res, dict(
                           sharded=True,
                           ckpt_format=_SHARDED_CKPT_FORMAT, D=self.D,
                           chunk=self.chunk,
                           LB=self.LB, VB=self.VB, FC=self.FC,
                           SC=self.SC,
                           fam_caps=list(self.FAM_CAPS),
                           depth=depth, n_states=n_states,
                           n_vis=[int(x) for x in n_vis],
                           n_front=int(n_front),
                           spec=self.ir.name,
                           sym_canon=self.fpr.sym_canon,
                           ir_fingerprint=self.ir.fingerprint(),
                           cfg=repr(self.cfg)),
                       keep=self.ckpt_keep)

    def _resume_portable(self, img):
        """Rebuild a level-boundary carry from a PortableImage: route
        visited keys and frontier rows to their owner devices
        (``key[W-1] % D`` — pure content, so any source shape / device
        count re-partitions here), build per-device table images with
        the host insert twin, and seed the gids table from the image.
        Constraint-pruned rows are dropped (they are never expanded;
        gids are explicit here, so no placeholder rows are needed)."""
        from ..resil.portable import validate_image
        D, W = self.D, self.W
        validate_image(img, self.ir.name, repr(self.cfg), W)
        self._restore_portable_archives(img)
        self._arch_segs = [[(0, len(p))] for p in self._parents]
        keys = img.keys
        owner = (keys[:, W - 1].astype(np.int64)) % D
        n_vis = np.bincount(owner, minlength=D).astype(np.int64)
        rows, gids = img.expandable()
        if gids.shape[0]:
            b = {k: jnp.asarray(v)
                 for k, v in self.ir.widen(rows).items()}
            fkeys = np.asarray(self._rootfp_jit(b)).astype(np.uint32)
            fowner = (fkeys[:, W - 1].astype(np.int64)) % D
        else:
            fowner = np.zeros((0,), np.int64)
        per_dev = [np.nonzero(fowner == d)[0] for d in range(D)]
        max_rows = max((len(p) for p in per_dev), default=0)
        # grow LB FIRST, then size the table against the final LB —
        # the same order as root admission: the load bound reserves
        # headroom for a whole level (up to LB keys), so sizing VB
        # against a stale smaller LB could leave the shard past its
        # probe budget right after resume
        while self.LB - self.D * self.SC < 2 * max(max_rows, 1):
            self.LB = self._round_lb(2 * self.LB)
        while int(n_vis.max()) + self.LB > self._LOAD_MAX * self.VB:
            self.VB *= 4
        carry_np = self._fresh_sharded_carry_host()
        for d in range(D):
            kd = keys[owner == d]
            if kd.shape[0]:
                tbl = np.full((W, self.VB), np.uint32(0xFFFFFFFF),
                              np.uint32)
                insert_np(tbl, kd.astype(np.uint32))
                for w in range(W):
                    carry_np["vis"][w][d] = tbl[w]
            idx = per_dev[d]
            n = len(idx)
            if n:
                for k in rows:
                    carry_np["front"][k][d, :n] = rows[k][idx]
                carry_np["gids"][d, :n] = gids[idx]
                carry_np["fmask"][d, :n] = True
            carry_np["n_front"][d] = n
        carry_np["g_off"][:] = np.int32(img.n_states)
        carry = self._to_device(carry_np)
        return (carry, img.fresh_result(), img.n_states, n_vis, img.depth,
                max_rows)

    def _load_checkpoint(self, path):
        from ..engine.bfs import CheckpointError
        if jax.process_count() > 1:
            raise NotImplementedError(
                "ShardedEngine checkpoints are single-controller; use "
                "MultiHostEngine (per-controller shard files) for "
                "multi-process runs")
        z, meta = ckpt_read(path, repr(self.cfg), self.chunk,
                            ("D", "LB", "VB", "FC", "SC", "fam_caps"),
                            sharded=True, expected_format=_SHARDED_FMT,
                            spec_name=self.ir.name,
                            sym_canon=self.fpr.sym_canon)
        if meta["D"] != self.D:
            raise CheckpointError(
                f"checkpoint was written on a {meta['D']}-device mesh; "
                f"this engine has {self.D} devices (shard ownership is "
                "mesh-size dependent)")
        self.LB, self.VB, self.FC, self.SC = (
            meta["LB"], meta["VB"], meta["FC"], meta["SC"])
        self.FAM_CAPS = tuple(int(c) for c in meta["fam_caps"])
        template = jax.eval_shape(lambda: self._fresh_sharded_carry())
        carry = ckpt_carry(path, z, template, self._to_device)
        self._parents, self._lanes, self._states = ckpt_archives(
            z, meta, template, self.store_states)
        # segment metadata is not checkpointed (only the MultiHostEngine
        # archive merge needs it, and that engine rejects store_states +
        # checkpointing); single-host trace() never reads it
        self._arch_segs = [[(0, len(p))] for p in self._parents]
        res = ckpt_result(z, meta)
        z.close()             # all arrays extracted; don't leak the fd
        return carry, res, meta

    def _rehash_sharded(self, carry):
        """Per-shard device rehash into self.VB-slot tables (sharded
        twin of Engine._rehash_tables)."""
        old_vb = int(carry["vis"][0].shape[1])
        new_vb = self.VB

        def local(table):
            t = tuple(x[0] for x in table)
            allones = jnp.ones((old_vb,), bool)
            for w in range(self.W):
                allones &= t[w] == U32MAX
            new = tuple(jnp.full((new_vb,), U32MAX)
                        for _ in range(self.W))
            ncl = jnp.full((new_vb,), U32MAX)
            ranks = jnp.arange(old_vb, dtype=jnp.uint32)
            new, ncl, _f, _p, hv = self._probe_insert(
                new, ncl, t, ~allones, ranks)
            # replicated so every controller can read it (multi-host)
            hv_all = jax.lax.all_gather(hv, "d").any()
            return (tuple(x[None] for x in new), ncl[None], hv_all)

        fn = _shard_map(
            local, self.mesh,
            (tuple(P("d") for _ in range(self.W)),),
            (tuple(P("d") for _ in range(self.W)), P("d"), P()))
        vis, claims, hv = jax.jit(fn)(carry["vis"])
        if bool(np.asarray(hv).any()):
            raise RuntimeError("sharded rehash did not converge — "
                               "table pathologically full; raise vcap")
        # lrow is slot-indexed: resize with the table (it is only ever
        # non-sentinel mid-level, and a rehash either sits between
        # levels or aborts the level into a replay)
        return dict(carry, vis=vis, claims=claims,
                    lrow=jnp.full((self.D, new_vb), -1, jnp.int32))

    # ------------------------------------------------------------------
    # collective demo kept for the driver dry run
    # ------------------------------------------------------------------

    def device_fingerprint_gather(self, svb: Dict[str, jnp.ndarray]):
        """shard_map the expansion and all_gather the fingerprint
        blocks over ICI, returning globally-assembled [B, A, streams]
        fingerprints — proves the collective path compiles+executes."""
        def local(svb_local):
            _ok, _cand, fp = self._phase1_impl(svb_local)
            return jax.lax.all_gather(fp, "d", tiled=True)

        fn = _shard_map(
            local, self.mesh,
            ({k: P("d") for k in self.ir.all_keys},), P(None))
        return fn(svb)
