"""Spill-composed sharded BFS: the mesh scale-out story and the host-
spill depth story in ONE engine (VERDICT r4 #5).

The classic ShardedEngine (parallel/mesh) keeps each device's frontier
and level shard device-resident, so a real mesh hits the same per-chip
level-buffer wall the single-device SpillEngine (engine/spill) broke;
and the SpillEngine is single-device.  TLC's distributed mode has one
story for both — every worker spills its local queue to disk.  This
engine is that composition, TPU-shaped:

- per-device visited-table shards stay device-resident (hash-ownership
  dedup over ``all_to_all`` exactly as in parallel/mesh — ownership is
  fingerprint-derived, which is ALSO the spill partition key, so
  routing is unchanged);
- each device's FRONTIER lives in host RAM as per-device blocks and
  streams through its [D, LB] shard in segments (quantized H2D);
- each device's LEVEL shard spills to host when full and at level
  ends (quantized D2H), becoming the next per-device frontier blocks;
- trips are STEP-ATOMIC (mesh._local_step's _step_atomic mode): a
  step that overflows any shard commits on NO device — one small
  all_gather makes the trip decision global — so the host can spill /
  grow and resume from the tripped step exactly.  The whole-level
  journal replay of the classic engine is impossible here: earlier
  shard contents have already left the device.

Survivor policy: stage-1 content-canonical reduction per receive
window is unchanged; the stage-2 replace-if-smaller map (lrow) only
reaches rows still ON the device, so the canonical min is per SPILL
EPOCH (first-epoch-seen across epochs).  When no mid-level spill
occurs this engine is bit-identical to ShardedEngine; with mid-level
spills counts remain fully deterministic for a fixed (mesh, seg)
configuration, and on VIEW-only constraint sets (where the
representative's non-VIEW content cannot affect reachability) counts
equal the oracle exactly regardless of spill timing
(tests/test_spill_mesh.py forces spills every few steps and pins
oracle parity).  Constraint semantics stay prune-not-expand: pruned
rows are counted, checked and dropped host-side (engine/spill's
policy, differentially tested).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..engine.bfs import (CheckResult, CheckpointError, U32MAX,
                          Violation, ckpt_read, ckpt_result,
                          ckpt_write)
from ..obs import NULL_OBS
from ..engine import driver
from ..engine.host_table import HostPartitionedTable, insert_np
from ..engine.spill import SpillEngine
from ..ops.codec import C_OVERFLOW
from ..resil.chaos import chaos_point
from .mesh import P, ShardedEngine, _shard_map

# summary row layout ([D, Z_LEN + n_fams] int32, replicated)
(Z_NLVL, Z_NGEN, Z_OVF, Z_FOVF, Z_SOVF, Z_HOVF, Z_TRIP,
 Z_LEN) = range(8)


class SpilledShardedEngine(ShardedEngine):
    """ShardedEngine whose level/frontier shards stream through host
    RAM (module docstring).  ``lcap`` is the MESH-TOTAL level
    capacity, split evenly across devices (LB = lcap/D rows per shard,
    floored by the receive-window bound) — the same convention as
    ShardedEngine; everything else follows it too."""

    def __init__(self, cfg: ModelConfig, devices=None, chunk: int = 512,
                 store_states: bool = False, host_table: bool = False,
                 partitions: int = 4, part_cap: int = 1 << 12,
                 dev_keys: Optional[int] = None,
                 archive_dir: Optional[str] = None, **kw):
        # the parent engines' store machinery is bypassed (this check()
        # owns level assembly), so init with store OFF and compose the
        # trace archive from the spilled blocks instead (ROADMAP open
        # item: mesh-scale witnesses): every harvested block appends a
        # part in gid order, flushed per level into engine/archive
        # memmaps (archive_dir) or the in-RAM lists — Engine.trace /
        # get_state walk either backing unchanged.
        super().__init__(cfg, devices=devices, chunk=chunk,
                         store_states=False, **kw)
        self.store_states = bool(store_states)
        self.archive_dir = archive_dir
        self._cur_parts: List[dict] = []
        # host-partitioned visited table, mesh composition
        # (engine/host_table): hash-ownership routes a key to its owner
        # device (fingerprint stream W-1 mod D) exactly as before, and
        # each device's authoritative visited set moves to a
        # PER-DEVICE prefix-partitioned host table (stream 0 top bits
        # — an independent axis, so the two partitionings compose).
        # The table shard becomes a bounded per-device cache, complete
        # over the running level, reseeded from the frontier at level
        # boundaries; level keys meet the host partitions once per
        # level, per device, in the engine's deterministic
        # (spill-event, device) order, so counts are exactly those of
        # the un-composed engine.
        self.host_table = bool(host_table)
        self._track_keys = self.host_table
        self.partitions = int(partitions)
        self.part_cap = int(part_cap)
        self.VB0 = self.VB
        self.dev_keys = (int(dev_keys) if dev_keys
                         else int(self._LOAD_MAX * self.VB))
        self.hpts = None               # per-device tables, per check()
        # the classic engine's LB >= 4*FC floor is a thrash heuristic
        # for whole-level replays; this engine replays only single
        # steps, so the shard capacity honors the caller's lcap down
        # to the hard receive-window bound (LB > D*SC) — tests squeeze
        # it far below the widest level to force mid-level spills
        self.LB = self._round_lb(max(kw.get("lcap", 1 << 14) // self.D,
                                     2 * self.D * self.SC))
        self._step_atomic = True      # read at first trace of the step
        # in-burst level commits compact pruned rows out of the next
        # frontier (parallel/mesh commit note): this engine's host path
        # drops them before re-upload, and the window packing — hence
        # row order and gid assignment — must match it exactly
        self._burst_compact_frontier = True
        self.mid_level_spills = 0     # diagnostics: ovf-trip spills
        self._sseg_jit = jax.jit(self._spill_seg_call,
                                 donate_argnums=0, static_argnums=1)
        self._mslice_cache = {}
        self._mpaste_cache = {}
        self._bfront_cache = {}        # post-burst frontier fetch jits

    # -- device programs ----------------------------------------------

    def _spill_seg_call(self, carry, fam_caps):
        specs = jax.tree_util.tree_map(lambda _: P("d"), carry)
        out_specs = (specs, P(None))
        return _shard_map(
            lambda c: self._spill_seg_level(c, fam_caps), self.mesh,
            (specs,), out_specs)(carry)

    def _spill_seg_level(self, carry, fam_caps):
        """Run lock-step chunk steps until every device drained its
        frontier segment or any device tripped; report the summary
        matrix WITHOUT the classic finalize (no lvl->front swap — the
        host owns level assembly here)."""
        c = jax.tree_util.tree_map(lambda x: x[0], carry)

        def cond(c):
            more = c["base"] < c["n_front"]
            bad = c["ovf"] | c["fovf"] | c["sovf"] | c["hovf"]
            flags = jax.lax.all_gather(jnp.stack([more, bad]), "d")
            return flags[:, 0].any() & ~flags[:, 1].any()

        c = lax.while_loop(cond,
                           lambda cc: self._local_step(cc, fam_caps), c)
        summ = jax.lax.all_gather(jnp.concatenate([jnp.stack([
            c["n_lvl"], c["n_gen"],
            c["ovf"].astype(jnp.int32), c["fovf"].astype(jnp.int32),
            c["sovf"].astype(jnp.int32), c["hovf"].astype(jnp.int32),
            c["trip_base"]]), c["famx"]]), "d")
        return (jax.tree_util.tree_map(lambda x: x[None], c), summ)

    # -- host-side shard plumbing -------------------------------------

    def _fetch_shards(self, carry, nl: np.ndarray):
        """D2H of every device's filled level-shard rows (one
        quantized jit'd slice — fresh buffers, donation-safe), plus
        reset of the per-level device state.  Returns per-device blocks
        [(rows batch-major narrow, lpar, llane, linv, lcon, n)]."""
        blks = [None] * self.D
        nmax = int(nl.max())
        if nmax > 0:
            nq = SpillEngine._quantize(nmax, self.LB, floor=1 << 8)
            fn = self._mslice_cache.get(nq)
            if fn is None:
                def impl(lvl, lpar, llane, linv, lcon, lkey=None,
                         nq=nq):
                    out = (
                        {k: lax.slice_in_dim(v, 0, nq, axis=1)
                         for k, v in lvl.items()},
                        lax.slice_in_dim(lpar, 0, nq, axis=1),
                        lax.slice_in_dim(llane, 0, nq, axis=1),
                        lax.slice_in_dim(linv, 0, nq, axis=1),
                        lax.slice_in_dim(lcon, 0, nq, axis=1))
                    if lkey is not None:
                        out += (lax.slice_in_dim(lkey, 0, nq, axis=1),)
                    return out
                fn = self._mslice_cache[nq] = jax.jit(impl)
            sliced = jax.tree_util.tree_map(
                np.asarray,
                fn(carry["lvl"], carry["lpar"], carry["llane"],
                   carry["linv"], carry["lcon"],
                   carry["lkey"] if self._track_keys else None))
            lvl, lpar, llane, linv, lcon = sliced[:5]
            lkey = sliced[5] if self._track_keys else None
            for d in range(self.D):
                n = int(nl[d])
                if n:
                    blks[d] = dict(
                        rows={k: np.ascontiguousarray(v[d, :n])
                              for k, v in lvl.items()},
                        lpar=np.ascontiguousarray(lpar[d, :n]),
                        llane=np.ascontiguousarray(llane[d, :n]),
                        linv=np.ascontiguousarray(linv[d, :n]),
                        lcon=np.ascontiguousarray(lcon[d, :n]),
                        n=n)
                    if lkey is not None:
                        blks[d]["lkey"] = np.ascontiguousarray(
                            lkey[d, :n])
        # reset the per-level device state.  lrow reset closes the
        # stage-2 replacement epoch (module docstring): replacements
        # must never target rows that just left the device.
        carry["n_lvl"] = jnp.zeros((self.D,), jnp.int32)
        carry["lrow"] = jnp.full((self.D, self.VB), -1, jnp.int32)
        return carry, blks

    def _upload_seg(self, carry, seg):
        """Quantized H2D of one frontier segment: seg is a per-device
        list of (rows batch-major narrow, gids) or None."""
        ns = [0 if s is None else int(s[1].shape[0]) for s in seg]
        nq = SpillEngine._quantize(max(max(ns), 1), self.LB,
                                  floor=1 << 8)
        one = self.ir.narrow(self.lay, self.ir.encode(
            self.lay, *self.ir.init_state(self.cfg)))
        rows_np = {k: np.zeros((self.D, nq) + v.shape, v.dtype)
                   for k, v in one.items()}
        gids_np = np.full((self.D, nq), -1, np.int32)
        for d, s in enumerate(seg):
            if s is None:
                continue
            rows, gids = s
            for k in rows_np:
                rows_np[k][d, :ns[d]] = rows[k]
            gids_np[d, :ns[d]] = gids
        fn = self._mpaste_cache.get(nq)
        if fn is None:
            def impl(front, fgids, blocks, bg):
                front = {k: lax.dynamic_update_slice(
                    v, blocks[k], (0, 0) + (0,) * (v.ndim - 2))
                    for k, v in front.items()}
                return front, lax.dynamic_update_slice(fgids, bg, (0, 0))
            fn = self._mpaste_cache[nq] = jax.jit(
                impl, donate_argnums=(0, 1))
        carry["front"], carry["gids"] = fn(
            carry["front"], carry["gids"],
            {k: jnp.asarray(v) for k, v in rows_np.items()},
            jnp.asarray(gids_np))
        carry["n_front"] = jnp.asarray(np.asarray(ns, np.int32))
        carry["base"] = jnp.zeros((self.D,), jnp.int32)
        # prune-not-expand ran host-side (pruned rows never uploaded),
        # so every uploaded row is expandable; the step's fmask gate
        # must not mask them (the classic engine uses fmask to keep
        # pruned rows in place instead)
        LB = carry["fmask"].shape[1]
        carry["fmask"] = jnp.ones((self.D, LB), bool)
        return carry

    @staticmethod
    def _resegment_dev(blocks_per_dev, seg: int):
        """Per-device re-segmentation, lock-step across devices: yields
        per-device [(rows, gids) or None] lists of <= seg rows."""
        cursors = [list(b) for b in blocks_per_dev]
        while any(cursors):
            out = []
            for d, q in enumerate(cursors):
                take_rows, take_gids, have = [], [], 0
                while q and have < seg:
                    rows, gids = q[0]
                    n = int(gids.shape[0])
                    t = min(seg - have, n)
                    take_rows.append({k: v[:t]
                                      for k, v in rows.items()})
                    take_gids.append(gids[:t])
                    have += t
                    if t == n:
                        q.pop(0)
                    else:
                        q[0] = ({k: v[t:] for k, v in rows.items()},
                                gids[t:])
                if have:
                    keys = take_rows[0].keys()
                    out.append((
                        {k: np.concatenate([r[k] for r in take_rows])
                         for k in keys},
                        np.concatenate(take_gids)))
                else:
                    out.append(None)
            yield out

    # -- the check loop -----------------------------------------------

    def check(self, max_depth: int = 10 ** 9, max_states: int = 10 ** 9,
              stop_on_violation: bool = False,
              seed_states: Optional[List] = None,
              checkpoint_path: Optional[str] = None,
              checkpoint_every: int = 1,
              resume_from: Optional[str] = None,
              resume_image=None,
              verbose: bool = False, obs=None) -> CheckResult:
        """Checkpointing (round 12): at a level boundary the whole
        wavefront is host-reachable here too — the frontier blocks are
        host numpy, the visited set is either the device shards (one
        pooled sparse fetch) or the per-device host partitions, and
        ownership is a pure function of key content.  The checkpoint
        therefore stores the wavefront POOLED in gid order (the
        portable form), and resume re-routes rows and keys by hash
        ownership — which also makes ``resume_image`` (a checkpoint
        from any engine family / any mesh size) the same code path."""
        assert jax.process_count() == 1, \
            "single-controller engine (MultiHostEngine composition " \
            "is future work)"
        obs = self._obs = obs if obs is not None else NULL_OBS
        t0 = time.perf_counter()
        lay = self.lay
        D, W = self.D, self.W
        if resume_from is not None and resume_image is not None:
            raise ValueError(
                "resume_from and resume_image are mutually exclusive")
        resumed = False
        if resume_from is not None:
            (carry, res, frontier, frontier_keys, n_states, n_vis,
             depth) = self._load_spill_mesh_checkpoint(resume_from)
            resumed = True
        elif resume_image is not None:
            (carry, res, frontier, frontier_keys, n_states, n_vis,
             depth) = self._resume_portable(resume_image)
            resumed = True
        else:
            self._init_store()
            self._cur_parts = []

            # ---- roots: hash-owner placement into host blocks -------
            roots, rk, pin_interiors = self._dedup_roots(seed_states)
            res = CheckResult(distinct_states=0,
                              generated_states=len(rk), depth=0)
            self._check_pin_interiors(pin_interiors, res)
            per_dev: List[List[int]] = [[] for _ in range(D)]
            for r in range(len(rk)):
                per_dev[int(rk[r, W - 1]) % D].append(r)
            inv_r, con_r = (np.asarray(a) for a in self._phase2(
                {k: jnp.asarray(v) for k, v in roots.items()}))
            roots_n = self.ir.narrow(lay, roots)

            if self.host_table:
                self.hpts = [HostPartitionedTable(
                    W, partitions=self.partitions,
                    part_cap=self.part_cap) for _ in range(D)]
            carry = self._fresh_sharded_carry()
            vis_np = [np.array(t) for t in carry["vis"]]  # writable
            root_blks = [None] * D
            for d in range(D):
                idx = per_dev[d]
                if not idx:
                    continue
                rkd = rk[idx]
                slots = self._host_probe_assign(rkd, vcap=self.VB)
                for r, sl in enumerate(slots):
                    for w in range(W):
                        vis_np[w][d, sl] = rkd[r, w]
                root_blks[d] = dict(
                    rows={k: np.stack([np.asarray(roots_n[k][i])
                                       for i in idx]) for k in roots_n},
                    lpar=np.full((len(idx),), -1, np.int32),
                    llane=np.full((len(idx),), -1, np.int32),
                    linv=inv_r[idx], lcon=con_r[idx], n=len(idx))
                if self.host_table:
                    root_blks[d]["lkey"] = rkd.astype(np.uint32)
                    # roots enter the per-device host partitions
                    # through the same sweep as every level (all fresh)
                    self.hpts[d].sweep(root_blks[d]["lkey"])
            carry["vis"] = tuple(jnp.asarray(v) for v in vis_np)

            n_states = 0
            n_vis = np.array([len(p) for p in per_dev], np.int64)
            depth = 0
        self._stamp_mode(res)

        def harvest_blocks(blks):
            """Device-major harvest of one spill event's blocks:
            counts, violations, next-frontier rows (pruned rows
            dropped, prune-not-expand).  Returns per-device
            (rows, gids) or None."""
            nonlocal n_states
            _hv = obs.span("harvest")
            _hv.__enter__()
            out = [None] * D
            for d in range(D):
                blk = blks[d]
                if blk is None:
                    continue
                n = blk["n"]
                res.distinct_states += n
                res.overflow_faults += int(
                    (blk["rows"]["ctr"][:, C_OVERFLOW] > 0).sum())
                gids = np.arange(n_states, n_states + n,
                                 dtype=np.int32)
                inv_ok = blk["linv"]
                if inv_ok.size and not inv_ok.all():
                    bad = np.nonzero(~inv_ok)
                    res.violations_global += len(bad[0])
                    for s, j in zip(*bad):
                        vsv, vh = self.ir.decode(lay, {
                            k: np.asarray(v[s])
                            for k, v in blk["rows"].items()})
                        res.violations.append(Violation(
                            self.inv_names[j], int(gids[s]),
                            state=vsv, hist=vh))
                n_states += n
                driver.guard_id_space(n_states)
                if self.store_states:
                    # archive part in gid order (this loop assigns gids
                    # device-major per harvest event, so appending here
                    # keeps the archive's row order == gid order)
                    self._cur_parts.append(dict(
                        n=n, lpar=blk["lpar"], llane=blk["llane"],
                        rows_major=blk["rows"]))
                con = blk["lcon"].astype(bool)
                if con.all():
                    out[d] = (blk["rows"], gids, blk.get("lkey"))
                elif con.any():
                    keep = np.nonzero(con)[0]
                    out[d] = ({k: v[keep]
                               for k, v in blk["rows"].items()},
                              gids[keep],
                              blk["lkey"][keep]
                              if "lkey" in blk else None)
            _hv.__exit__(None, None, None)
            return out

        if not resumed:
            frontier = [[] for _ in range(D)]
            frontier_keys = [[] for _ in range(D)]
            root_front = harvest_blocks(root_blks)
            self._flush_level_parts()
            for d in range(D):
                if root_front[d] is not None:
                    rows_r, gids_r, fk_r = root_front[d]
                    frontier[d].append((rows_r, gids_r))
                    if fk_r is not None:
                        frontier_keys[d].append(fk_r)
            res.generated_states = len(rk)
        if stop_on_violation and res.violations:
            res.seconds = time.perf_counter() - t0
            return res

        # burst_ok: a burst that committed levels then bailed keeps the
        # bailing level's frontier intact — re-entering would replay
        # the identical chunks and bail again (one wasted round trip),
        # so skip the burst for that level; the segment driver re-arms
        burst_ok = True
        while any(frontier) and depth < max_depth and \
                res.distinct_states < max_states:
            # chaos site: dispatch-time device/runtime error at the
            # level boundary (resil/chaos) — before any device work,
            # so the last checkpoint stays the exact resume point
            chaos_point("dispatch")
            if (self.burst and burst_ok and not self.host_table and
                    max(sum(int(g.shape[0]) for _r, g in q)
                        for q in frontier) <= self._mesh_burst_width()):
                d0 = depth
                (carry, frontier, depth, n_states, n_vis,
                 fused, bailed) = self._burst_mesh_levels(
                    carry, frontier, res, depth, n_states, n_vis,
                    max_depth, max_states, verbose)
                if fused:
                    burst_ok = not bailed
                    if checkpoint_path is not None and \
                            driver.ckpt_due_after_burst(
                                depth, d0, checkpoint_every):
                        self._save_spill_mesh_checkpoint(
                            checkpoint_path, carry, res, frontier,
                            frontier_keys, depth, n_states, n_vis)
                    if stop_on_violation and res.violations:
                        break
                    continue
                # first level bailed: the segment driver (with its
                # growth machinery) runs it below
            burst_ok = True        # re-arm after a per-level level
            depth += 1
            SEGB = self.LB             # per-device segment rows
            t1 = time.perf_counter()
            level_new = 0
            level_gen = 0
            next_frontier: List[List] = [[] for _ in range(D)]
            next_keys: List[List] = [[] for _ in range(D)]
            level_events: List[List] = []    # host-table: defer harvest

            def settle(blks):
                nonlocal level_new, n_vis
                for d in range(D):
                    if blks[d] is not None:
                        n_vis[d] += blks[d]["n"]
                        if not self.host_table:
                            level_new += blks[d]["n"]
                if self.host_table:
                    # harvest defers to the level-end per-device
                    # partition sweep (module docstring)
                    if any(b is not None for b in blks):
                        level_events.append(blks)
                    return
                outs = harvest_blocks(blks)
                for d in range(D):
                    if outs[d] is not None:
                        next_frontier[d].append(outs[d][:2])

            _lvl_span = obs.span("level_dispatch")
            _lvl_span.__enter__()
            for seg in self._resegment_dev(frontier, SEGB):
                carry = self._sgrow_table_if_needed(carry, n_vis)
                carry = self._upload_seg(carry, seg)
                while True:
                    carry, summ = self._sseg_jit(carry, self.FAM_CAPS)
                    s = np.asarray(summ)        # [D, Z_LEN + n_fams]
                    level_gen += int(s[:, Z_NGEN].sum())
                    carry["n_gen"] = jnp.zeros((D,), jnp.int32)
                    if not (s[:, Z_OVF].any() or s[:, Z_FOVF].any()
                            or s[:, Z_SOVF].any()
                            or s[:, Z_HOVF].any()):
                        break
                    carry = self._handle_mesh_trip(carry, s, n_vis,
                                                   settle, verbose)
            # level end: spill the remainder everywhere
            nl = np.asarray(carry["n_lvl"])
            carry, blks = self._fetch_shards(carry, nl)
            _lvl_span.__exit__(None, None, None)
            settle(blks)
            if self.host_table and level_events:
                # per-device key streams in (spill-event) order: each
                # device's keys are unique within the level (its table
                # shard is complete over the level) and disjoint across
                # devices (hash-ownership), so the sweeps are
                # independent; the keep verdicts then filter the
                # event-ordered blocks so gid assignment keeps the
                # engine's deterministic (event, device) order
                with obs.span("host_sweep"):
                    for d in range(D):
                        dev_blks = [ev[d] for ev in level_events
                                    if ev[d] is not None]
                        if not dev_blks:
                            continue
                        keys = np.concatenate(
                            [b["lkey"][:b["n"]] for b in dev_blks])
                        keep = self.hpts[d].sweep(
                            keys.astype(np.uint32))
                        off = 0
                        for b in dev_blks:
                            nb = b["n"]
                            b["_keep"] = keep[off:off + nb]
                            off += nb
                for ev in level_events:
                    fblks = [self._filter_blk(ev[d]) for d in range(D)]
                    for d in range(D):
                        if fblks[d] is not None:
                            level_new += fblks[d]["n"]
                    outs = harvest_blocks(fblks)
                    for d in range(D):
                        if outs[d] is not None:
                            rows_b, gids_b, fk_b = outs[d]
                            next_frontier[d].append((rows_b, gids_b))
                            next_keys[d].append(fk_b)
            self._flush_level_parts()
            res.generated_states += level_gen
            depth = driver.gate_level_depth(
                res, depth, level_new, level_gen,
                sum(int(g.shape[0]) for q in next_frontier
                    for _r, g in q))
            frontier = next_frontier
            frontier_keys = next_keys
            if self.host_table and int(n_vis.max()) > self.dev_keys:
                # level boundary: reseed every table shard with just
                # its frontier's keys (the host partitions answer for
                # everything archived)
                carry, n_vis = self._reseed_shards(carry, frontier_keys)
            if checkpoint_path is not None and \
                    driver.ckpt_due_at_level(depth, checkpoint_every):
                self._save_spill_mesh_checkpoint(
                    checkpoint_path, carry, res, frontier,
                    frontier_keys, depth, n_states, n_vis)
            obs.dispatch(
                kind="level", depth=depth,
                frontier=sum(int(g.shape[0])
                             for q in frontier for _r, g in q),
                metrics=res.metrics.as_dict())
            if stop_on_violation and res.violations:
                break
            if verbose:
                print(f"depth {depth}: +{level_new} states "
                      f"(total {res.distinct_states}), frontier "
                      f"{sum(int(g.shape[0]) for q in frontier for _r, g in q)}, "
                      f"{time.perf_counter() - t1:.2f}s", flush=True)
        res.depth = depth
        res.seconds = time.perf_counter() - t0
        return res

    # -- checkpoint / resume (round 12, ROADMAP item-5 closure) --------
    # At a level boundary the wavefront is host-reachable: frontier
    # blocks are host numpy, the visited set is the device shards (one
    # pooled sparse fetch) or the per-device host partitions.  The file
    # stores the wavefront POOLED in gid order — the portable form —
    # because hash ownership (key[W-1] % D) is a pure function of
    # content: resume re-routes rows and keys to their owners, which
    # reproduces the original per-device assignment exactly on the same
    # mesh, and re-partitions it on any other shape via resume_image.
    # The device-table slot layout is NOT serialized (membership is a
    # set property; rebuilt images dedup identically), and under
    # host_table the device cache resumes reseeded to the frontier's
    # keys — a state the engine itself produces at reseed boundaries,
    # so counts/gids/archives stay bit-exact (tests/test_resil.py).
    # ------------------------------------------------------------------

    _SM_EXTRA_KEYS = ("D", "LB", "VB", "FC", "SC", "fam_caps",
                      "host_table", "partitions")
    _SM_FMT = ("sm_format", 1,
               "the spill-mesh pooled-wavefront layout")

    def _pool_frontier(self, frontier, frontier_keys):
        """Per-device frontier queues -> (rows batch-major, gids,
        fkeys) pooled in global-id order (fkeys None outside
        host-table mode)."""
        rows_l, gids_l, keys_l = [], [], []
        for d in range(self.D):
            blocks = frontier[d]
            kq = (frontier_keys[d] if self.host_table
                  else [None] * len(blocks))
            for bi, (rows, gids) in enumerate(blocks):
                rows_l.append(rows)
                gids_l.append(gids)
                keys_l.append(kq[bi])
        if gids_l:
            g = np.concatenate(gids_l)
            order = np.argsort(g, kind="stable")
            keys0 = rows_l[0].keys()
            pf_rows = {k: np.ascontiguousarray(np.concatenate(
                [r[k] for r in rows_l])[order]) for k in keys0}
            pf_g = g[order].astype(np.int32)
            pfk = (np.concatenate(keys_l)[order].astype(np.uint32)
                   if self.host_table else None)
            return pf_rows, pf_g, pfk
        one = self.ir.narrow(self.lay, self.ir.encode(
            self.lay, *self.ir.init_state(self.cfg)))
        pf_rows = {k: np.zeros((0,) + v.shape, v.dtype)
                   for k, v in one.items()}
        return (pf_rows, np.zeros((0,), np.int32),
                np.zeros((0, self.W), np.uint32)
                if self.host_table else None)

    def _save_spill_mesh_checkpoint(self, path, carry, res, frontier,
                                    frontier_keys, depth, n_states,
                                    n_vis):
        with self._obs.span("checkpoint"):
            from ..resil.portable import dense_table_keys
            D, W = self.D, self.W
            ckpt = {}
            pf_rows, pf_g, pfk = self._pool_frontier(frontier,
                                                     frontier_keys)
            ckpt["pf|g"] = pf_g
            for k, v in pf_rows.items():
                ckpt[f"pf|rows|{k}"] = v
            if self.host_table:
                ckpt["pfk"] = pfk
                for d in range(D):
                    ckpt.update(self.hpts[d].state_dict(
                        prefix=f"hpt{d}"))
            else:
                vis_np = [np.asarray(t) for t in carry["vis"]]
                ckpt["keys"] = dense_table_keys(vis_np)
            parents, lanes, states, arch_meta = self._ckpt_store_args()
            ckpt_write(path, ckpt, self.store_states, parents, lanes,
                       states, res, dict(
                           spill=True, sharded=True, sm_format=1,
                           D=D, W=W, host_table=self.host_table,
                           partitions=self.partitions,
                           depth=depth, n_states=n_states,
                           n_vis=[int(x) for x in n_vis],
                           n_front=int(pf_g.shape[0]),
                           LB=self.LB, VB=self.VB, FC=self.FC,
                           SC=self.SC,
                           fam_caps=list(self.FAM_CAPS), **arch_meta,
                           layout=2, chunk=self.chunk,
                           spec=self.ir.name,
                           sym_canon=self.fpr.sym_canon,
                           ir_fingerprint=self.ir.fingerprint(),
                           cfg=repr(self.cfg)),
                       keep=self.ckpt_keep)

    def _load_spill_mesh_checkpoint(self, path):
        z, meta = ckpt_read(path, repr(self.cfg), self.chunk,
                            self._SM_EXTRA_KEYS, sharded=True,
                            spill=True, expected_format=self._SM_FMT,
                            spec_name=self.ir.name,
                            sym_canon=self.fpr.sym_canon)
        if meta["D"] != self.D:
            raise CheckpointError(
                f"checkpoint was written on a {meta['D']}-device "
                f"mesh; this engine has {self.D} devices — exact "
                "resume needs the same mesh, or re-partition with a "
                "portable resume (resume_image / --resume-portable)")
        if bool(meta.get("host_table")) != self.host_table:
            raise CheckpointError(
                f"{path}: checkpoint was written with host_table="
                f"{bool(meta.get('host_table'))}; resume with the "
                "same setting")
        if self.host_table and meta["partitions"] != self.partitions:
            raise CheckpointError(
                f"{path}: checkpoint has {meta['partitions']} "
                f"host-table partitions; engine has "
                f"{self.partitions} — resume with the same "
                "--partitions (counts are P-invariant, but the "
                "serialized images are not)")
        # capacities restore so segmentation — and therefore spill
        # event boundaries, row order and gid assignment — match the
        # interrupted run exactly
        self.LB = int(meta["LB"])
        self.VB = int(meta["VB"])
        self.FC = int(meta["FC"])
        self.SC = int(meta["SC"])
        self.FAM_CAPS = tuple(int(c) for c in meta["fam_caps"])
        rows = {}
        for nm in z.files:
            if nm.startswith("carry|pf|rows|"):
                rows[nm.split("|", 3)[3]] = np.asarray(z[nm])
        gids = np.asarray(z["carry|pf|g"]).astype(np.int32)
        if self.host_table:
            fkeys = np.asarray(z["carry|pfk"]).astype(np.uint32)
            self.hpts = [HostPartitionedTable.from_state(
                (lambda nm, _d=d: z["carry|" + nm]),
                prefix=f"hpt{d}") for d in range(self.D)]
            keys = None
        else:
            fkeys = None
            keys = np.asarray(z["carry|keys"]).astype(np.uint32)
        template = {"lvl": rows}
        self._load_archives(path, z, meta, template)
        self._cur_parts = []
        res = ckpt_result(z, meta)
        (carry, frontier, frontier_keys,
         n_vis) = self._restore_wavefront(keys, rows, gids, fkeys,
                                          exact_vb=True)
        z.close()
        return (carry, res, frontier, frontier_keys,
                meta["n_states"], n_vis, meta["depth"])

    def _resume_portable(self, img):
        """Shape-portable resume: re-partition a PortableImage (from
        ANY engine family / mesh size) onto this mesh — visited keys
        and frontier rows re-route by hash ownership; under host_table
        the archive set re-sweeps into fresh per-device partitions
        (any --partitions works)."""
        from ..resil.portable import validate_image
        validate_image(img, self.ir.name, repr(self.cfg), self.W)
        self._restore_portable_archives(img)
        self._cur_parts = []
        rows, gids = img.expandable()
        keys = img.keys.astype(np.uint32)
        if self.host_table:
            self.hpts = [HostPartitionedTable(
                self.W, partitions=self.partitions,
                part_cap=self.part_cap) for _ in range(self.D)]
            owner = keys[:, self.W - 1].astype(np.int64) % self.D
            step = 1 << 16
            for d in range(self.D):
                kd = keys[owner == d]
                for i in range(0, kd.shape[0], step):
                    self.hpts[d].sweep(
                        np.ascontiguousarray(kd[i:i + step]))
            keys = None
        (carry, frontier, frontier_keys,
         n_vis) = self._restore_wavefront(keys, rows, gids, None)
        return (carry, img.fresh_result(), frontier, frontier_keys,
                img.n_states, n_vis, img.depth)

    def _restore_wavefront(self, keys, rows, gids, fkeys,
                           exact_vb=False):
        """Pooled wavefront -> this mesh's per-device state: route
        frontier rows (and, non-host-table, the visited keys) to their
        hash owners, rebuild per-device table images with the host
        insert twin, and return (carry, frontier, frontier_keys,
        n_vis).  Under host_table the device shards reseed with the
        frontier's keys only — exactly the reseed-boundary state; the
        partitions (restored or re-swept by the caller) answer for
        everything archived."""
        D, W = self.D, self.W
        if gids.shape[0] and fkeys is None:
            b = {k: jnp.asarray(v)
                 for k, v in self.ir.widen(rows).items()}
            fkeys = np.asarray(self._rootfp_jit(b)).astype(np.uint32)
        frontier: List[List] = [[] for _ in range(D)]
        frontier_keys: List[List] = [[] for _ in range(D)]
        if gids.shape[0]:
            fowner = fkeys[:, W - 1].astype(np.int64) % D
            for d in range(D):
                idx = np.nonzero(fowner == d)[0]
                if len(idx):
                    frontier[d].append((
                        {k: np.ascontiguousarray(v[idx])
                         for k, v in rows.items()},
                        gids[idx].astype(np.int32)))
                    if self.host_table:
                        frontier_keys[d].append(
                            np.ascontiguousarray(fkeys[idx]))
        if self.host_table:
            key_src = [np.concatenate(q) if q
                       else np.zeros((0, W), np.uint32)
                       for q in frontier_keys]
        else:
            owner = keys[:, W - 1].astype(np.int64) % D
            key_src = [np.ascontiguousarray(keys[owner == d])
                       for d in range(D)]
        n_vis = np.array([k.shape[0] for k in key_src], np.int64)
        if not exact_vb:
            if self.host_table:
                self.VB = self.VB0
            while int(n_vis.max(initial=0)) + self.LB > \
                    self._LOAD_MAX * self.VB:
                self.VB *= 4
        carry = self._fresh_sharded_carry()
        vis_np = [np.full((D, self.VB), np.uint32(0xFFFFFFFF),
                          np.uint32) for _ in range(W)]
        for d in range(D):
            if key_src[d].shape[0]:
                img = np.full((W, self.VB), np.uint32(0xFFFFFFFF),
                              np.uint32)
                insert_np(img, key_src[d])
                for w in range(W):
                    vis_np[w][d] = img[w]
        carry["vis"] = tuple(jnp.asarray(v) for v in vis_np)
        return carry, frontier, frontier_keys, n_vis

    # -- trace-archive composition ------------------------------------

    def _flush_level_parts(self):
        """One finished level's harvested blocks -> the trace archive
        (engine/archive memmaps under archive_dir, else the in-RAM
        lists).  Row order within the level is exactly gid order, so
        the inherited Engine.trace / get_state_arrays walk works
        unchanged; a level that archived nothing appends nothing (the
        archives' gid->row mapping is cumulative, not per-level)."""
        if not self.store_states:
            return
        parts, self._cur_parts = self._cur_parts, []
        if not parts:
            return
        with self._obs.span("archive_io"):
            if self._arch is not None:
                self._arch.append_level_parts(parts)
                return
            self._parents.append(np.concatenate(
                [p["lpar"][:p["n"]] for p in parts]))
            self._lanes.append(np.concatenate(
                [p["llane"][:p["n"]] for p in parts]))
            keys = parts[0]["rows_major"].keys()
            self._states.append(
                {k: np.concatenate([p["rows_major"][k][:p["n"]]
                                    for p in parts]) for k in keys})

    # -- host-partitioned table composition ---------------------------

    @staticmethod
    def _filter_blk(blk):
        """Apply a sweep keep-verdict to one spilled block (rows whose
        key an earlier level archived drop before any counting)."""
        if blk is None or "_keep" not in blk:
            return blk
        kb = blk.pop("_keep")
        if kb.all():
            return blk
        kidx = np.nonzero(kb)[0]
        if not len(kidx):
            return None
        return dict(
            rows={k: np.ascontiguousarray(v[kidx])
                  for k, v in blk["rows"].items()},
            lpar=blk["lpar"][kidx], llane=blk["llane"][kidx],
            linv=blk["linv"][kidx], lcon=blk["lcon"][kidx],
            lkey=blk["lkey"][kidx], n=len(kidx))

    def _reseed_shards(self, carry, frontier_keys):
        """Reset every device's table shard to its own frontier's keys
        at (near) the initial capacity.  The shard images build
        host-side with engine/host_table.insert_np — the numpy twin of
        the device claim-insert, same home hash and probe walk — and
        upload in one piece; claims and the stage-2 lrow map reset with
        them."""
        D, W = self.D, self.W
        fk = [(np.concatenate(q).astype(np.uint32) if q else
               np.zeros((0, W), np.uint32)) for q in frontier_keys]
        nmax = max(k.shape[0] for k in fk)
        self.VB = self.VB0
        while nmax + self.LB > self._LOAD_MAX * self.VB:
            self.VB *= 4
        vis_np = [np.full((D, self.VB), np.uint32(0xFFFFFFFF),
                          np.uint32) for _ in range(W)]
        for d in range(D):
            if not fk[d].shape[0]:
                continue
            img = np.full((W, self.VB), np.uint32(0xFFFFFFFF),
                          np.uint32)
            insert_np(img, fk[d])
            for w in range(W):
                vis_np[w][d] = img[w]
        carry = dict(carry,
                     vis=tuple(jnp.asarray(v) for v in vis_np),
                     claims=jnp.full((D, self.VB), U32MAX),
                     lrow=jnp.full((D, self.VB), -1, jnp.int32))
        return carry, np.array([k.shape[0] for k in fk], np.int64)

    # -- fused multi-level burst --------------------------------------
    # While every device's frontier fits the burst ring and the
    # host-table sweep is not in play (host_table sweeps every level),
    # whole levels run inside ONE shard_map program (_shard_burst,
    # parallel/mesh) instead of the upload/window/fetch round trips of
    # the segment driver.  With no mid-level spill possible inside a
    # burst (any overflow bails the level), the stage-2
    # content-canonical epoch covers the whole level and the gid
    # assignment (device-major arithmetic in-loop) coincides exactly
    # with this engine's (event, device) harvest order — so counts,
    # archives and traces are bit-identical to the un-bursted path.
    # -----------------------------------------------------------------

    def _burst_mesh_levels(self, carry, frontier, res, depth, n_states,
                           n_vis, max_depth, max_states, verbose):
        """One fused K-level device call on tiny per-device frontiers.
        Returns (carry, frontier, depth, n_states, n_vis, fused,
        bailed) — fused=False means the first level bailed and the
        segment driver must run it (host frontier blocks left
        untouched); bailed=True means the call ended in a bail (even
        after committing levels), so re-entering the burst on the
        unchanged frontier would deterministically bail again."""
        t1 = time.perf_counter()
        lay = self.lay
        D = self.D
        obs = self._obs
        with obs.span("burst_dispatch"):
            kbd = self._mesh_burst_width()
            seg = []
            for q in frontier:
                if q:
                    keys = q[0][0].keys()
                    seg.append((
                        {k: np.concatenate([r[k] for r, _g in q])
                         for k in keys},
                        np.concatenate([g for _r, g in q])))
                else:
                    seg.append(None)
            carry = self._sgrow_table_if_needed(
                carry, n_vis, min_add=self.burst_levels * kbd)
            carry = self._upload_seg(carry, seg)
            # the burst's in-loop gid refresh is device-major
            # arithmetic from g_off; seed it at the next id this
            # engine would assign
            carry["g_off"] = jnp.full((D,), n_states, jnp.int32)
            lv_left = min(self.burst_levels, max_depth - depth)
            st_cap = max(1, min(max_states - res.distinct_states,
                                2 ** 31 - 1))
            carry, bout = self._burst_mesh_jit(
                carry, self.FAM_CAPS, jnp.int32(lv_left),
                jnp.int32(st_cap))
            stats = np.asarray(bout["stats"])       # [D, L_MAX+1, NS]
        nlev = int(stats[0, -1, 0])
        bailed = bool(stats[0, -1, 1])
        res.burst_dispatches += 1
        res.burst_bailouts += int(bailed)
        if nlev == 0:
            return (carry, frontier, depth, n_states, n_vis, False,
                    bailed)
        viol_any = bool(stats[0, -1, 3])
        _hv_span = obs.span("harvest")
        _hv_span.__enter__()
        par_h = lane_h = st_h = inv_h = None
        if self.store_states or viol_any:
            par_h = np.asarray(bout["par"])     # [D, L_MAX, kbd]
            lane_h = np.asarray(bout["lane"])
            st_h = {k: np.asarray(v) for k, v in bout["st"].items()}
            inv_h = np.asarray(bout["inv"])     # [D, L_MAX, kbd, n_inv]
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
            for d in range(D):
                if not nl[d]:
                    continue
                # archive part in gid order (device-major per level —
                # exactly harvest_blocks' order)
                self._cur_parts.append(dict(
                    n=int(nl[d]),
                    lpar=par_h[d, li, :nl[d]].copy(),
                    llane=lane_h[d, li, :nl[d]].copy(),
                    rows_major={k: st_h[k][d, li, :nl[d]].copy()
                                for k in st_h}))

        def _viol(li, _n_lvl, gid_base):
            nl = stats[:, li, 0]
            prefix = np.cumsum(nl) - nl
            for d in range(D):
                if not nl[d] or not stats[d, li, 1]:
                    continue
                inv_ok = inv_h[d, li, :nl[d]]
                for s, j in zip(*np.nonzero(~inv_ok)):
                    vsv, vh = self.ir.decode(lay, {
                        k: np.asarray(st_h[k][d, li, s])
                        for k in st_h})
                    res.violations.append(Violation(
                        self.inv_names[j],
                        gid_base + int(prefix[d]) + int(s),
                        state=vsv, hist=vh))

        def _vis(li, _n_lvl):
            # the per-level part flush rides the shared loop's
            # post-level hook (it moves archive parts only — counters
            # never read it)
            self._flush_level_parts()
            for d in range(D):
                n_vis[d] += stats[d, li, 0]

        depth, n_states = driver.harvest_fused_levels(
            res, nlev, _stats, depth, n_states, archive=_arch,
            violations=_viol, visited=_vis)
        _hv_span.__exit__(None, None, None)
        # rebuild the per-device host frontier from the device shards
        # (pruned rows drop here — prune-not-expand stays host-side
        # outside the burst)
        nf = stats[:, -1, 2]
        frontier = [[] for _ in range(D)]
        if int(nf.max()) > 0:
            nq = SpillEngine._quantize(int(nf.max()), self.LB,
                                       floor=1 << 8)
            fn = self._bfront_cache.get(nq)
            if fn is None:
                def impl(front, gids, fmask, nq=nq):
                    return ({k: lax.slice_in_dim(v, 0, nq, axis=1)
                             for k, v in front.items()},
                            lax.slice_in_dim(gids, 0, nq, axis=1),
                            lax.slice_in_dim(fmask, 0, nq, axis=1))
                fn = self._bfront_cache[nq] = jax.jit(impl)
            rows, gids, fmask = jax.tree_util.tree_map(
                np.asarray,
                fn(carry["front"], carry["gids"], carry["fmask"]))
            for d in range(D):
                n = int(nf[d])
                if not n:
                    continue
                keep = np.nonzero(fmask[d, :n])[0]
                if len(keep):
                    frontier[d].append((
                        {k: np.ascontiguousarray(v[d][keep])
                         for k, v in rows.items()},
                        gids[d][keep].astype(np.int32)))
        obs.dispatch(
            kind="burst", depth=depth,
            frontier=sum(int(g.shape[0])
                         for q in frontier for _r, g in q),
            metrics=res.metrics.as_dict())
        if verbose:
            print(f"burst: {nlev} levels to depth {depth} "
                  f"(total {res.distinct_states}), frontier "
                  f"{sum(int(g.shape[0]) for q in frontier for _r, g in q)}, "
                  f"{time.perf_counter() - t1:.2f}s", flush=True)
        return carry, frontier, depth, n_states, n_vis, True, bailed

    # -- trip handling ------------------------------------------------

    def _sgrow_table_if_needed(self, carry, n_vis, min_add=0):
        need = int(n_vis.max()) + max(self.LB, min_add)
        if need > self._LOAD_MAX * self.VB:
            while need > self._LOAD_MAX * self.VB:
                self.VB *= 4
            carry = self._rehash_sharded(carry)
        return carry

    def _handle_mesh_trip(self, carry, s, n_vis, settle, verbose):
        """Spill every shard's committed rows (the tripped step itself
        committed nowhere — step-atomic), grow whatever tripped, and
        point every device back at the tripped chunk."""
        tb = int(s[:, Z_TRIP].max())
        assert tb >= 0, "trip flags set but no trip_base"
        nl = s[:, Z_NLVL].astype(np.int64)
        if s[:, Z_OVF].any():
            self.mid_level_spills += 1
        carry, blks = self._fetch_shards(carry, nl)
        settle(blks)
        if s[:, Z_FOVF].any():
            famx = s[:, Z_LEN:Z_LEN + len(self.FAM_CAPS)].max(axis=0)
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
        if s[:, Z_SOVF].any():
            self.SC = 4 * self.SC
        # only the HARD bound forces shard growth (the level shard must
        # hold a receive window on top of usable rows).  The classic
        # engine's 4*FC anti-thrash floor is deliberately NOT applied:
        # an ovf trip here costs one spill + program re-entry, and
        # running the shard near-full IS this engine's operating mode.
        if self.LB < 2 * self.D * self.SC:
            self.LB = self._round_lb(2 * self.D * self.SC)
        # grow when any capacity outran the carry's current shapes
        old_shapes = (carry["fmask"].shape[1], carry["cidx"].shape[1],
                      carry["sscr"].shape[1])
        if (self.LB, self.FC, self.SC) != old_shapes:
            carry = self._grow_sharded(carry)
        if s[:, Z_HOVF].any():
            self.VB *= 4
            carry = self._rehash_sharded(carry)
        carry = self._sgrow_table_if_needed(carry, n_vis)
        if verbose:
            print(f"mesh trip at base {tb}: ovf={s[:, Z_OVF].any()} "
                  f"fovf={s[:, Z_FOVF].any()} sovf={s[:, Z_SOVF].any()} "
                  f"hovf={s[:, Z_HOVF].any()} -> LB={self.LB} "
                  f"FC={self.FC} SC={self.SC} VB={self.VB}",
                  flush=True)
        D = self.D
        carry["ovf"] = jnp.zeros((D,), bool)
        carry["fovf"] = jnp.zeros((D,), bool)
        carry["sovf"] = jnp.zeros((D,), bool)
        carry["hovf"] = jnp.zeros((D,), bool)
        carry["famx"] = jnp.zeros((D, len(self.expander.families)),
                                  jnp.int32)
        carry["trip_base"] = jnp.full((D,), -1, jnp.int32)
        carry["base"] = jnp.full((D,), tb, jnp.int32)
        return carry
